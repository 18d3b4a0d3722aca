"""Ring collective-matmul (ops/collective_matmul.py): numeric parity of the
latency-hiding ring schedules against the XLA monolithic collectives, knob
resolution, fallback gating, and the TP train-step / Ulysses-boundary wiring.

CPU-mesh contract (the acceptance bar): collective-matmul on vs off agree
within dtype tolerance for both all-gather→matmul and matmul→reduce-scatter,
for unidirectional and bidirectional rings, under ``jit`` and inside the TP
train step — plus an exact-f32 fixed-point check for the unidirectional ring
(integer-valued operands sum exactly in any reduction order, so the ring's
reordered accumulation must be bit-equal)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from accelerate_tpu.ops.collective_matmul import (
    all_gather_matmul_monolithic,
    collective_matmul,
    collective_matmul_mode,
    dense_collective_matmul,
    make_collective_dense,
    matmul_reduce_scatter_monolithic,
    normalize_mode,
    ring_all_gather_matmul,
    ring_matmul_reduce_scatter,
    ring_supported,
    set_collective_matmul,
    tp_comm_accounting,
    ulysses_sp_boundary,
)

rng = np.random.default_rng(7)


@pytest.fixture
def tp_mesh():
    return Mesh(np.asarray(jax.devices()).reshape(8), ("tp",))


def _col_run(body, mesh, x, w):
    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "tp", None), P(None, "tp")),
        out_specs=P(None, None, "tp"), check_vma=False,
    )
    return np.asarray(jax.jit(f)(x, w))


def _row_run(body, mesh, x, w):
    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "tp"), P("tp", None)),
        out_specs=P(None, "tp", None), check_vma=False,
    )
    return np.asarray(jax.jit(f)(x, w))


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# ring bodies vs the monolithic collectives (the same shard_map layout)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bidirectional", [False, True])
def test_all_gather_matmul_ring_matches_monolithic(tp_mesh, bidirectional):
    x, w = _rand((2, 16, 8)), _rand((8, 24))
    ring = functools.partial(ring_all_gather_matmul, axis_name="tp",
                             bidirectional=bidirectional)
    mono = functools.partial(all_gather_matmul_monolithic, axis_name="tp")
    got = _col_run(ring, tp_mesh, x, w)
    want = _col_run(mono, tp_mesh, x, w)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bidirectional", [False, True])
def test_matmul_reduce_scatter_ring_matches_monolithic(tp_mesh, bidirectional):
    x, w = _rand((2, 16, 8)), _rand((8, 24))
    ring = functools.partial(ring_matmul_reduce_scatter, axis_name="tp",
                             bidirectional=bidirectional)
    mono = functools.partial(matmul_reduce_scatter_monolithic, axis_name="tp")
    got = _row_run(ring, tp_mesh, x, w)
    want = _row_run(mono, tp_mesh, x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-4, atol=1e-5)


def test_unidirectional_ring_exact_f32_fixed_point(tp_mesh):
    # integer-valued f32: every partial sum is exactly representable, so the
    # unidirectional ring's reordered accumulation must be BIT-equal to the
    # monolithic result (the fixed-point contract from the issue)
    xi = jnp.asarray(rng.integers(-8, 9, (2, 16, 8)), jnp.float32)
    wi = jnp.asarray(rng.integers(-8, 9, (8, 24)), jnp.float32)
    ag = _col_run(functools.partial(ring_all_gather_matmul, axis_name="tp"), tp_mesh, xi, wi)
    rs = _row_run(functools.partial(ring_matmul_reduce_scatter, axis_name="tp"), tp_mesh, xi, wi)
    want = np.asarray(xi @ wi)
    assert np.array_equal(ag, want)
    assert np.array_equal(rs, want)


def test_ring_bodies_bf16_tolerance(tp_mesh):
    x, w = _rand((2, 16, 32), jnp.bfloat16), _rand((32, 24), jnp.bfloat16)
    got = _col_run(functools.partial(ring_all_gather_matmul, axis_name="tp"), tp_mesh, x, w)
    want = np.asarray(
        (x.astype(jnp.float32) @ w.astype(jnp.float32))
    )
    np.testing.assert_allclose(got.astype(np.float32), want, rtol=5e-2, atol=1e-1)


# ---------------------------------------------------------------------------
# global-array wrappers: jit, grads, preferred_element_type
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ring", "bidir"])
@pytest.mark.parametrize("kind", ["column", "row"])
def test_make_collective_dense_parity_and_grads(tp_mesh, kind, mode):
    x, w = _rand((2, 16, 16)), _rand((16, 32))
    fn = make_collective_dense(tp_mesh, "tp", kind, mode)
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)

    def loss_ring(x, w):
        return jnp.sum(jnp.sin(fn(x, w)))

    def loss_ref(x, w):
        return jnp.sum(jnp.sin(x @ w))

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(x, w)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(x, w)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_make_collective_dense_preferred_element_type(tp_mesh):
    x = _rand((2, 8, 16), jnp.bfloat16)
    w = _rand((16, 32), jnp.bfloat16)
    fn = make_collective_dense(tp_mesh, "tp", "column", "ring",
                               preferred_element_type=jnp.float32)
    out = fn(x, w)
    assert out.dtype == jnp.float32


# ---------------------------------------------------------------------------
# knob resolution + gating
# ---------------------------------------------------------------------------


def test_mode_normalization_and_env(monkeypatch):
    assert normalize_mode("on") == "ring"
    assert normalize_mode("BIDIRECTIONAL") == "bidir"
    assert normalize_mode("off") == "off"
    with pytest.raises(ValueError):
        normalize_mode("sideways")
    monkeypatch.setenv("ACCELERATE_COLLECTIVE_MATMUL", "on")
    assert collective_matmul_mode() == "ring"
    monkeypatch.delenv("ACCELERATE_COLLECTIVE_MATMUL")
    assert collective_matmul_mode() == "off"
    prev = set_collective_matmul("bidir")
    try:
        assert collective_matmul_mode() == "bidir"
        with collective_matmul("off"):
            assert collective_matmul_mode() == "off"
        assert collective_matmul_mode() == "bidir"
    finally:
        set_collective_matmul(prev)


def test_plugin_knob_normalizes_and_installs(monkeypatch):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    monkeypatch.setenv("ACCELERATE_COLLECTIVE_MATMUL", "bidir")
    assert FullyShardedDataParallelPlugin().collective_matmul == "bidir"
    monkeypatch.delenv("ACCELERATE_COLLECTIVE_MATMUL")
    plugin = FullyShardedDataParallelPlugin(collective_matmul="on")
    assert plugin.collective_matmul == "ring"
    with pytest.raises(ValueError):
        FullyShardedDataParallelPlugin(collective_matmul="sideways")
    # the Accelerator installs the plugin knob as the ambient mode
    Accelerator(fsdp_plugin=plugin)
    assert collective_matmul_mode() == "ring"


def test_plugin_less_accelerator_clears_stale_override():
    from accelerate_tpu import Accelerator
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    Accelerator(fsdp_plugin=FullyShardedDataParallelPlugin(collective_matmul="ring"))
    assert collective_matmul_mode() == "ring"
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    # the old accelerator's ambient mode must not leak into the next one
    assert collective_matmul_mode() == "off"
    Accelerator()
    assert collective_matmul_mode() == "off"


def test_ring_supported_gating(tp_mesh):
    assert ring_supported(tp_mesh, "tp")
    assert not ring_supported(tp_mesh, "sp")       # axis absent
    assert not ring_supported(None, "tp")
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tp",))
    assert not ring_supported(one, "tp")           # trivial ring
    multi = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("dp_shard", "tp"))
    assert ring_supported(multi, "tp")             # partial-manual over tp only


def test_dense_hook_fallbacks(monkeypatch):
    from accelerate_tpu import Accelerator, ParallelismConfig

    Accelerator(parallelism_config=ParallelismConfig(tp_size=8))
    x, w = _rand((2, 16, 16)), _rand((16, 32))
    # off -> None regardless of mesh
    assert dense_collective_matmul(x, w, "column") is None
    with collective_matmul("ring"):
        y = dense_collective_matmul(x, w, "column")
        assert y is not None
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-5, atol=1e-5)
        # non-dividing shapes fall back
        assert dense_collective_matmul(_rand((2, 15, 16)), w, "column") is None  # T % 8
        assert dense_collective_matmul(x, _rand((16, 30)), "column") is None     # N % 8
        assert dense_collective_matmul(x, _rand((15, 32))[:15], "row") is None   # K mismatch
        assert dense_collective_matmul(x[:, 0], w, "column") is None             # 2D input
        assert dense_collective_matmul(x, w, "replicated") is None               # bad kind


def test_dense_hook_without_accelerator_state_is_none():
    x, w = _rand((2, 16, 16)), _rand((16, 32))
    with collective_matmul("ring"):
        assert dense_collective_matmul(x, w, "column") is None


# ---------------------------------------------------------------------------
# wiring: TP train step and the Ulysses sp boundary
# ---------------------------------------------------------------------------


def _reset_state():
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def _train_losses(mode, pcfg, attn="native", kv_heads=2, steps=3):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn

    _reset_state()
    acc = Accelerator(parallelism_config=pcfg)
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_implementation=attn,
                           num_key_value_heads=kv_heads)
    model = LlamaForCausalLM(cfg)
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    ids = jnp.asarray(tokens)
    batch = {"input_ids": ids, "labels": ids}
    with collective_matmul(mode):
        params = model.init(jax.random.key(0), ids[:, :8])
        state = acc.create_train_state(params, optax.adam(1e-2), apply_fn=model.apply)
        step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
        losses = []
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    return losses


def _jaxpr_prims(closed):
    from accelerate_tpu.analysis import iter_eqns

    return {eqn.primitive.name for eqn in iter_eqns(closed)}


@pytest.mark.parametrize("mode", ["ring", "bidir"])
def test_tp_train_step_parity(mode):
    from accelerate_tpu import ParallelismConfig

    off = _train_losses("off", ParallelismConfig(tp_size=8))
    on = _train_losses(mode, ParallelismConfig(tp_size=8))
    assert all(np.isfinite(off)) and all(np.isfinite(on))
    np.testing.assert_allclose(on, off, rtol=2e-4)
    assert off[-1] < off[0]  # the step actually trains


def test_tp_forward_ring_engages():
    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    _reset_state()
    Accelerator(parallelism_config=ParallelismConfig(tp_size=8))
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((2, 32), jnp.int32)
    with collective_matmul("ring"):
        params = model.init(jax.random.key(0), ids[:, :8])
        prims_on = _jaxpr_prims(jax.jit(model.apply).trace(params, ids).jaxpr)
    prims_off = _jaxpr_prims(jax.jit(model.apply).trace(params, ids).jaxpr)
    assert "ppermute" in prims_on
    assert "ppermute" not in prims_off


def test_ulysses_sp_boundary_parity_and_alltoall_elision():
    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    sp_cfg = lambda: ParallelismConfig(sp_size=4, devices=tuple(jax.devices()[:4]))
    off = _train_losses("off", sp_cfg(), attn="ulysses", kv_heads=4)
    on = _train_losses("ring", sp_cfg(), attn="ulysses", kv_heads=4)
    np.testing.assert_allclose(on, off, rtol=2e-4)

    # the boundary really replaced the monolithic all_to_alls with rings
    _reset_state()
    Accelerator(parallelism_config=sp_cfg())
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_implementation="ulysses",
                           num_key_value_heads=4)
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((2, 32), jnp.int32)
    with collective_matmul("ring"):
        params = model.init(jax.random.key(0), ids[:, :8])
        prims_on = _jaxpr_prims(jax.jit(model.apply).trace(params, ids).jaxpr)
    prims_off = _jaxpr_prims(jax.jit(model.apply).trace(params, ids).jaxpr)
    assert "all_to_all" in prims_off and "ppermute" not in prims_off
    assert "ppermute" in prims_on and "all_to_all" not in prims_on


def test_ulysses_sp_boundary_gating():
    from accelerate_tpu import Accelerator, ParallelismConfig

    _reset_state()
    Accelerator(parallelism_config=ParallelismConfig(sp_size=4, devices=tuple(jax.devices()[:4])))
    assert not ulysses_sp_boundary(4, 4, 32)  # mode off
    with collective_matmul("ring"):
        assert ulysses_sp_boundary(4, 4, 32)
        assert not ulysses_sp_boundary(6, 4, 32)  # heads % sp
        assert not ulysses_sp_boundary(4, 2, 32)  # kv heads % sp
        assert not ulysses_sp_boundary(4, 4, 30)  # seq % sp
    _reset_state()
    # composed sp x tp keeps the all_to_all path (kernel dims can't be
    # manual over sp and auto over tp at once)
    from accelerate_tpu import Accelerator as Acc

    Acc(parallelism_config=ParallelismConfig(sp_size=2, tp_size=2,
                                             devices=tuple(jax.devices()[:4])))
    with collective_matmul("ring"):
        assert not ulysses_sp_boundary(4, 4, 32)


# ---------------------------------------------------------------------------
# overlap accounting
# ---------------------------------------------------------------------------


def test_tp_comm_accounting_envelope():
    rep = tp_comm_accounting(8 * 2048, 4096, 11008, 4)
    assert rep["kind"] == "predicted"
    assert 0.0 <= rep["tp_overlap_frac"] <= 1.0
    assert rep["steps"] == 3 and rep["ring_size"] == 4
    bi = tp_comm_accounting(8 * 2048, 4096, 11008, 4, bidirectional=True)
    assert bi["steps"] == 2  # ceil((p-1)/2): halved ring depth
    # trivial ring: nothing to hide, nothing to report
    triv = tp_comm_accounting(8 * 2048, 4096, 11008, 1)
    assert triv["steps"] == 0 and triv["tp_overlap_frac"] == 0.0
    # a wire-starved shape (tiny matmul over a slow link) cannot hide its hops
    starved = tp_comm_accounting(64, 64, 64, 8, ici_gibs=1e-3)
    assert starved["tp_overlap_frac"] < 1.0


def test_stream_stats_ici_fields():
    from accelerate_tpu.ops.streaming import StreamStats

    stats = StreamStats()
    rep = stats.overlap_report()
    assert "ici_bytes" not in rep and "tp_overlap_frac" not in rep  # key set stable
    stats.ici_bytes = 1024
    stats.tp_overlap_frac = 0.75
    rep = stats.overlap_report()
    assert rep["ici_bytes"] == 1024 and rep["tp_overlap_frac"] == 0.75


def test_ici_overlap_report_from_cpu_trace(tmp_path):
    from accelerate_tpu.utils.xplane import ici_overlap_report

    @jax.jit
    def f(x):
        return jnp.sin(x) @ jnp.cos(x).T

    x = _rand((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    rep = ici_overlap_report(str(tmp_path), "CPU")
    for field in ("collective_ms_inline", "collective_ms_async",
                  "collective_occupancy", "tp_overlap_frac", "kind"):
        assert field in rep, field
    assert rep["kind"] == "measured"
    assert rep["tp_overlap_frac"] == 0.0  # no collectives in this trace
