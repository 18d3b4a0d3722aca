"""Hierarchical ICI→DCN gradient sync (parallel/hierarchical.py): schedule
math vs the flat pmean, the PowerSGD DCN codec with error feedback, the
predicted/measured accounting twins, the Accelerator train-step wiring on a
``dcn × dp_shard`` virtual mesh, and the elastic re-shard restore."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.parallel.hierarchical import (
    dcn_comm_accounting,
    hierarchical_sync,
    init_dcn_powersgd_state,
    measure_dcn_bytes,
    ring_reduce_factor,
    slab_eligible,
    slab_geometry,
)
from accelerate_tpu.state import AcceleratorState, GradientState
from accelerate_tpu.utils.dataclasses import (
    FullyShardedDataParallelPlugin,
    GradSyncKwargs,
    ProjectConfiguration,
    ShardingStrategy,
)

from jax import shard_map as _shard_map

_NO_CHECK = {"check_vma": False}


def _fresh():
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def _no_shard():
    return FullyShardedDataParallelPlugin(sharding_strategy=ShardingStrategy.NO_SHARD)


def _dcn_mesh(dcn=2, ici=4):
    return Mesh(np.asarray(jax.devices()[: dcn * ici]).reshape(dcn, ici),
                ("dcn", "dp_shard"))


# ---------------------------------------------------------------------------
# slab geometry / schedule math
# ---------------------------------------------------------------------------


def test_slab_geometry_pads_and_near_square():
    g = slab_geometry(16 * 33, 4)
    assert g["chunk"] == 132 and g["padded"] == 528
    assert g["rows"] * g["cols"] >= g["chunk"]
    assert abs(g["rows"] - g["cols"]) <= g["cols"]  # near-square view
    # p=1 degenerates to the whole leaf
    g1 = slab_geometry(100, 1)
    assert g1["chunk"] == g1["padded"] == 100


def test_slab_eligibility_matches_factor_arithmetic():
    big = np.zeros((64, 64), np.float32)
    tiny = np.zeros((4,), np.float32)
    ints = np.zeros((64, 64), np.int32)
    assert slab_eligible(big, 4, rank=2)
    assert not slab_eligible(tiny, 4, rank=2)
    assert not slab_eligible(ints, 4, rank=2)
    assert ring_reduce_factor(1) == 0.0 and ring_reduce_factor(2) == 1.0


def test_hierarchical_dense_equals_flat_pmean():
    mesh = _dcn_mesh()
    rng = np.random.default_rng(0)
    grads = {
        "w": rng.standard_normal((8, 16, 33)).astype(np.float32),
        "b": rng.standard_normal((8, 7)).astype(np.float32),
    }
    spec = P(("dcn", "dp_shard"))

    def flat(gr):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g[0], ("dcn", "dp_shard")), gr
        )

    def hier(gr):
        local = jax.tree_util.tree_map(lambda g: g[0], gr)
        out, _, _ = hierarchical_sync(local, ("dp_shard",), "dcn")
        return out

    a = _shard_map(flat, mesh=mesh, in_specs=spec, out_specs=P(), **_NO_CHECK)(grads)
    b = _shard_map(hier, mesh=mesh, in_specs=spec, out_specs=P(), **_NO_CHECK)(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-5, atol=1e-6)


def test_powersgd_codec_error_feedback_state():
    mesh = _dcn_mesh()
    params = {"w": np.zeros((16, 33), np.float32), "b": np.zeros((7,), np.float32)}
    qs, errs = init_dcn_powersgd_state(params, rank=2, dp_world=8, ici_size=4)
    geo = slab_geometry(16 * 33, 4)
    assert qs["w"].shape == (geo["cols"], 2)
    assert errs["w"].shape == (8, geo["rows"], geo["cols"])
    assert qs["b"] is None and errs["b"] is None  # slab too small to compress

    rng = np.random.default_rng(0)
    grads = {
        "w": rng.standard_normal((8, 16, 33)).astype(np.float32),
        "b": rng.standard_normal((8, 7)).astype(np.float32),
    }
    isl = lambda x: x is None

    def hier_c(gr, qs, errs):
        local = jax.tree_util.tree_map(lambda g: g[0], gr)
        el = jax.tree_util.tree_map(lambda e: e[0], errs)
        out, nq, ne = hierarchical_sync(local, ("dp_shard",), "dcn",
                                        qs=qs, errs=el, rank=2)
        ne = jax.tree_util.tree_map(lambda e: e[None], ne)
        return out, nq, ne

    spec = P(("dcn", "dp_shard"))
    fn = _shard_map(hier_c, mesh=mesh,
                    in_specs=(spec, P(), spec),
                    out_specs=(P(), P(), spec), **_NO_CHECK)
    out, nq, ne = fn(grads, qs, errs)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree_util.tree_leaves(out))
    # error feedback engaged: the residual buffer is non-zero after one step
    assert float(np.abs(np.asarray(ne["w"])).max()) > 0
    # the ineligible leaf took the dense hop: exact world mean
    np.testing.assert_allclose(np.asarray(out["b"]), grads["b"].mean(0),
                               rtol=1e-5, atol=1e-6)


def test_accounting_twins_agree_exactly_and_order():
    """Predicted (dcn_comm_accounting) vs measured (jaxpr walk) per-device
    DCN bytes: EXACT agreement on both the dense and the compressed
    schedule, and compressed < dense < flat."""
    mesh = _dcn_mesh()
    params = {"w": np.zeros((16, 33), np.float32), "b": np.zeros((7,), np.float32)}
    rng = np.random.default_rng(0)
    grads = {
        "w": rng.standard_normal((8, 16, 33)).astype(np.float32),
        "b": rng.standard_normal((8, 7)).astype(np.float32),
    }
    spec = P(("dcn", "dp_shard"))

    def hier(gr):
        local = jax.tree_util.tree_map(lambda g: g[0], gr)
        out, _, _ = hierarchical_sync(local, ("dp_shard",), "dcn")
        return out

    f_dense = _shard_map(hier, mesh=mesh, in_specs=spec, out_specs=P(), **_NO_CHECK)
    measured = measure_dcn_bytes(jax.jit(f_dense).trace(grads).jaxpr, dcn_size=2)
    predicted = dcn_comm_accounting(params, ici_size=4, dcn_size=2)
    assert measured["dcn_bytes"] == predicted["dcn_bytes"]

    qs, errs = init_dcn_powersgd_state(params, rank=2, dp_world=8, ici_size=4)

    def hier_c(gr, qs, errs):
        local = jax.tree_util.tree_map(lambda g: g[0], gr)
        el = jax.tree_util.tree_map(lambda e: e[0], errs)
        out, nq, ne = hierarchical_sync(local, ("dp_shard",), "dcn",
                                        qs=qs, errs=el, rank=2)
        return out, nq, jax.tree_util.tree_map(lambda e: e[None], ne)

    f_c = _shard_map(hier_c, mesh=mesh, in_specs=(spec, P(), spec),
                     out_specs=(P(), P(), spec), **_NO_CHECK)
    measured_c = measure_dcn_bytes(jax.jit(f_c).trace(grads, qs, errs).jaxpr,
                                   dcn_size=2)
    predicted_c = dcn_comm_accounting(params, ici_size=4, dcn_size=2,
                                      compression="powersgd", rank=2)
    assert measured_c["dcn_bytes"] == predicted_c["dcn_bytes"]
    assert measured_c["dcn_bytes"] < measured["dcn_bytes"] < predicted["dcn_bytes_flat"]


def test_accounting_zeros_clean_without_dcn_axis():
    acct = dcn_comm_accounting({"w": np.zeros((64, 64), np.float32)},
                               ici_size=1, dcn_size=1)
    assert acct["dcn_bytes"] == 0 and acct["dcn_bytes_flat"] == 0
    assert acct["dcn_overlap_frac"] == 0.0


# ---------------------------------------------------------------------------
# Accelerator train-step wiring
# ---------------------------------------------------------------------------


def _mlp_init(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": np.asarray(jax.random.normal(k1, (8, 32))) * 0.3,
        "b1": np.zeros((32,), np.float32),
        "w2": np.asarray(jax.random.normal(k2, (32, 1))) * 0.3,
    }


def _mlp_loss(params, batch):
    h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
    return jnp.mean(((h @ params["w2"])[:, 0] - batch["y"]) ** 2)


def _batches(n=4, bs=32, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(8,)).astype(np.float32)
    out = []
    for _ in range(n):
        x = rng.normal(size=(bs, 8)).astype(np.float32)
        out.append({"x": x, "y": x @ w_true})
    return out


def _train(pcfg, handlers=None, plugin=None, steps=12, **acc_kwargs):
    import optax

    _fresh()
    acc = Accelerator(parallelism_config=pcfg, fsdp_plugin=plugin,
                      kwargs_handlers=handlers or [], **acc_kwargs)
    state = acc.create_train_state(_mlp_init(jax.random.key(0)), optax.sgd(0.05))
    step = acc.prepare_train_step(_mlp_loss)
    bs = _batches()
    losses = []
    for i in range(steps):
        state, m = step(state, bs[i % len(bs)])
        losses.append(float(m["loss"]))
    return acc, state, losses


def test_train_step_hierarchical_engages_and_matches_flat():
    acc_h, _, lh = _train(ParallelismConfig(dcn_size=2, dp_shard_size=4),
                          plugin=_no_shard())
    assert acc_h.dcn_sync == {"enabled": True, "dcn_size": 2, "ici_size": 4,
                              "compression": None, "why_not": None}
    acc_f, _, lf = _train(ParallelismConfig(dcn_size=2, dp_shard_size=4),
                          plugin=_no_shard(),
                          handlers=[GradSyncKwargs(hierarchical=False)])
    assert not acc_f.dcn_sync["enabled"]
    np.testing.assert_allclose(lh, lf, rtol=1e-5, atol=1e-6)
    # determinism: the hierarchical trajectory is bitwise-reproducible
    _, _, lh2 = _train(ParallelismConfig(dcn_size=2, dp_shard_size=4),
                       plugin=_no_shard())
    assert lh == lh2


def test_train_step_dcn_powersgd_converges():
    acc, state, losses = _train(
        ParallelismConfig(dcn_size=2, dp_shard_size=4), plugin=_no_shard(),
        handlers=[GradSyncKwargs(dcn_compression="powersgd", rank=2)], steps=60,
    )
    assert acc.dcn_sync["compression"] == "powersgd"
    assert losses[-1] < 0.1, f"dcn-compressed run failed to converge: {losses[-5:]}"
    # comm_state rode the TrainState (error feedback across steps)
    qs, errs = state.comm_state
    assert any(q is not None for q in jax.tree_util.tree_leaves(
        qs, is_leaf=lambda x: x is None))


def test_train_step_traced_dcn_bytes_below_flat_twin():
    """The acceptance pin: the prepared hierarchical step's TRACED program
    moves fewer per-device DCN bytes than the flat-reduce twin, and the
    predicted/measured twins agree (clean-run contract; small slack for the
    loss-scalar psum the predictor ignores)."""
    import optax

    for codec, handler in (
        (None, []),
        ("powersgd", [GradSyncKwargs(dcn_compression="powersgd", rank=2)]),
    ):
        _fresh()
        acc = Accelerator(parallelism_config=ParallelismConfig(dcn_size=2, dp_shard_size=4),
                          fsdp_plugin=_no_shard(), kwargs_handlers=handler)
        params = _mlp_init(jax.random.key(0))
        state = acc.create_train_state(params, optax.sgd(0.05))
        step = acc.prepare_train_step(_mlp_loss)
        b = _batches(1)[0]
        closed = step._jitted.trace(state, b).jaxpr
        measured = measure_dcn_bytes(closed, dcn_size=2)
        predicted = acc.dcn_sync_accounting(params)
        assert predicted["compression"] == codec
        assert measured["dcn_bytes"] < predicted["dcn_bytes_flat"], codec
        # twins agree: the traced step adds only the loss-scalar dcn psum
        # (4 bytes) on top of the predicted gradient traffic
        assert abs(measured["dcn_bytes"] - predicted["dcn_bytes"]) <= 16, (
            codec, measured["dcn_bytes"], predicted["dcn_bytes"],
            [r for r in measured["collectives"]],
        )


def test_incompatible_configs_fall_back_or_raise():
    # auto mode: FULL_SHARD (default for dp_shard>1) falls back to the flat
    # reduction with the blocker recorded
    acc, _, losses = _train(ParallelismConfig(dcn_size=2, dp_shard_size=4))
    assert not acc.dcn_sync["enabled"]
    assert "params sharded" in acc.dcn_sync["why_not"]
    assert all(np.isfinite(losses))
    # hierarchical=True on the same config refuses instead of degrading
    with pytest.raises(ValueError, match="cannot engage"):
        _train(ParallelismConfig(dcn_size=2, dp_shard_size=4),
               handlers=[GradSyncKwargs(hierarchical=True)])
    # the DCN codec cannot ride a mesh without a dcn axis
    with pytest.raises(ValueError, match="dcn_compression"):
        _train(ParallelismConfig(dp_shard_size=8), plugin=_no_shard(),
               handlers=[GradSyncKwargs(dcn_compression="powersgd")])
    # unknown codec name is rejected
    with pytest.raises(ValueError, match="dcn_compression"):
        _train(ParallelismConfig(dcn_size=2, dp_shard_size=4), plugin=_no_shard(),
               handlers=[GradSyncKwargs(dcn_compression="topk")])


def test_flat_powersgd_now_spans_dcn_axis():
    """The DDP-style flat PowerSGD path reduces over the FULL dp plane
    including dcn (``_compression_axes``): a dcn mesh with
    compression='powersgd' still converges, with the factor psums spanning
    both axes."""
    acc, _, losses = _train(
        ParallelismConfig(dcn_size=2, dp_shard_size=4), plugin=_no_shard(),
        handlers=[GradSyncKwargs(compression="powersgd", rank=2)], steps=40,
    )
    assert not acc.dcn_sync["enabled"]  # the flat codec owns the step
    assert losses[-1] < 0.2, losses[-5:]


def test_elastic_reshard_restore_across_chip_counts():
    """Elastic resume, the re-shard half: a checkpoint written on the
    2-slice 8-chip mesh restores BITWISE onto a 4-chip single-slice mesh
    (different process/chip topology), continues training, and the restored
    step counters/stream positions carry over."""
    import optax

    batch = _batches(1)[0]
    with tempfile.TemporaryDirectory() as tmp:
        _fresh()
        acc = Accelerator(
            parallelism_config=ParallelismConfig(dcn_size=2, dp_shard_size=4),
            fsdp_plugin=_no_shard(),
            project_config=ProjectConfiguration(project_dir=tmp,
                                                automatic_checkpoint_naming=True),
        )
        state = acc.create_train_state(_mlp_init(jax.random.key(0)), optax.adam(1e-2))
        step = acc.prepare_train_step(_mlp_loss)
        for _ in range(3):
            state, _m = step(state, batch)
        saved = {k: np.asarray(v) for k, v in state.params.items()}
        acc.save_state(train_state=state)

        _fresh()
        acc2 = Accelerator(
            parallelism_config=ParallelismConfig(
                dp_shard_size=4, devices=tuple(jax.devices()[:4])
            ),
            fsdp_plugin=_no_shard(),
            project_config=ProjectConfiguration(project_dir=tmp,
                                                automatic_checkpoint_naming=True),
        )
        state2 = acc2.create_train_state(_mlp_init(jax.random.key(1)), optax.adam(1e-2))
        restored = acc2.maybe_resume(train_state=state2)
        assert restored is not None and int(restored.step) == 3
        assert acc2.step_count == 3
        for k, v in saved.items():
            np.testing.assert_array_equal(np.asarray(restored.params[k]), v)
        step2 = acc2.prepare_train_step(_mlp_loss)
        restored, m = step2(restored, batch)
        assert np.isfinite(float(m["loss"]))
    _fresh()
