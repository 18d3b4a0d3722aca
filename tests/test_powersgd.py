"""PowerSGD gradient compression (reference DDPCommunicationHookType.POWER_SGD
analog): factor math, convergence parity on the 8-device mesh, wire-bytes
accounting, and config validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.parallel.powersgd import (
    compress_decompress,
    eligible,
    init_powersgd_state,
    wire_bytes_report,
)
from accelerate_tpu.state import AcceleratorState, GradientState
from accelerate_tpu.utils.dataclasses import (
    FullyShardedDataParallelPlugin,
    GradSyncKwargs,
    ShardingStrategy,
)


def _mlp_init(key, d_in=8, d_h=32):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d_in, d_h)) * 0.3,
        "b1": jnp.zeros((d_h,)),
        "w2": jax.random.normal(k2, (d_h, 1)) * 0.3,
    }


def _mlp_loss(params, batch):
    h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
    pred = (h @ params["w2"])[:, 0]
    return jnp.mean((pred - batch["y"]) ** 2)


def _make_batches(n_batches=8, bs=32, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(8,)).astype(np.float32)
    out = []
    for _ in range(n_batches):
        x = rng.normal(size=(bs, 8)).astype(np.float32)
        y = x @ w_true + 0.01 * rng.normal(size=(bs,)).astype(np.float32)
        out.append({"x": x, "y": y})
    return out


def _train(acc, n_epochs=30, lr=0.05):
    import optax

    state = acc.create_train_state(_mlp_init(jax.random.key(0)), acc.prepare(optax.sgd(lr)))
    step = acc.prepare_train_step(_mlp_loss)
    batches = _make_batches()
    losses = []
    for _ in range(n_epochs):
        for b in batches:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
    return state, losses


def _fresh():
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_powersgd_converges_close_to_dense():
    _fresh()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.NO_SHARD
        ),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd", rank=2)],
    )
    state, losses = _train(acc)
    assert losses[-1] < 0.05, f"powersgd run failed to converge: {losses[-10:]}"

    _fresh()
    dense_acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.NO_SHARD
        ),
    )
    dense_state, dense_losses = _train(dense_acc)
    # error feedback makes low-rank compression track the dense run's
    # convergence (not bit-exact — the approximation is the point)
    assert losses[-1] < max(dense_losses[-1] * 5, 0.05)


def test_powersgd_state_updates_and_errors_are_per_rank():
    _fresh()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.NO_SHARD
        ),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd", rank=2)],
    )
    import optax

    state = acc.create_train_state(_mlp_init(jax.random.key(0)), acc.prepare(optax.sgd(0.05)))
    qs, errs = state.comm_state
    assert qs["w1"].shape == (32, 2) and qs["b1"] is None
    assert errs["w1"].shape == (8, 8, 32)  # [dp, *leaf]
    q_before = np.asarray(qs["w1"]).copy()  # the step donates its input state
    step = acc.prepare_train_step(_mlp_loss)
    b = _make_batches(1)[0]
    state, _ = step(state, b)
    qs2, errs2 = state.comm_state
    # warm-start factors moved and residuals became nonzero
    assert float(jnp.abs(qs2["w1"] - q_before).max()) > 0
    assert float(jnp.abs(errs2["w1"]).max()) > 0
    # different ranks hold different residuals (their local grads differ)
    e = np.asarray(errs2["w1"])
    assert not np.allclose(e[0], e[1])


def test_powersgd_exact_when_rank_spans_gradient():
    """A rank-1 outer-product gradient is reproduced exactly (up to float)
    by rank>=1 compression with zero error."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp_shard",))
    g_global = jnp.outer(jnp.arange(1.0, 9.0), jnp.ones(16))  # rank 1, [8, 16]
    qs, errs = init_powersgd_state({"w": g_global}, rank=2, dp_size=4)

    def local(qs, errs):
        grads = {"w": g_global}  # identical on every rank
        e_local = jax.tree_util.tree_map(lambda e: e[0], errs)
        g_hat, new_qs, new_errs = compress_decompress(
            grads, qs, e_local, ("dp_shard",), 2
        )
        return g_hat, jax.tree_util.tree_map(lambda e: e[None], new_errs)

    from jax import shard_map

    P = jax.sharding.PartitionSpec
    g_hat, new_errs = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(("dp_shard",))), out_specs=(P(), P(("dp_shard",))),
        check_vma=False,
    ))(qs, errs)
    np.testing.assert_allclose(np.asarray(g_hat["w"]), np.asarray(g_global), rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(new_errs["w"]).max()) < 1e-4


def test_powersgd_allows_declared_full_shard_with_replicated_params():
    """FULL_SHARD with a trivial dp_shard axis shards nothing — params are
    replicated (the DDP shape powersgd targets), so the guard must accept."""
    _fresh()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_replicate_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.FULL_SHARD
        ),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd", rank=2)],
    )
    import optax

    state = acc.create_train_state(_mlp_init(jax.random.key(0)), acc.prepare(optax.sgd(0.05)))
    step = acc.prepare_train_step(_mlp_loss)
    state, metrics = step(state, _make_batches(1)[0])
    assert np.isfinite(float(metrics["loss"]))


def test_wire_bytes_report():
    params = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((1024,))}
    rep = wire_bytes_report(params, rank=4)
    assert rep["eligible_leaves"] == 1 and rep["dense_leaves"] == 1
    dense_w = 1024 * 1024 * 4
    assert rep["dense_bytes_per_step"] == dense_w + 1024 * 4
    # P psum (n*r) + Q psum (m*r) floats for the matrix, dense for the bias
    assert rep["compressed_bytes_per_step"] == 4 * (1024 + 1024) * 4 + 1024 * 4
    assert rep["ratio"] < 0.02


def test_eligibility():
    assert eligible(jnp.zeros((64, 64)), 4)
    assert not eligible(jnp.zeros((64,)), 4)        # 1-D
    assert not eligible(jnp.zeros((4, 4)), 4)       # factors beat nothing
    assert not eligible(jnp.zeros((8, 8), jnp.int32), 2)


def test_powersgd_rejects_bad_configs():
    _fresh()
    acc = Accelerator(
        gradient_accumulation_steps=2,
        kwargs_handlers=[GradSyncKwargs(compression="powersgd")],
    )
    with pytest.raises(ValueError, match="accum"):
        acc.prepare_train_step(_mlp_loss)
    _fresh()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd")],
    )
    with pytest.raises(ValueError, match="tp"):
        acc.prepare_train_step(_mlp_loss)
    _fresh()
    # dp_shard>1 with no plugin defaults to FULL_SHARD: params sharded over
    # dp would force a per-step param all-gather inside the shard_map,
    # inverting the compression's wire-bytes purpose (ADVICE r4)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd")],
    )
    with pytest.raises(ValueError, match="params-sharded"):
        acc.prepare_train_step(_mlp_loss)
    _fresh()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.HYBRID_SHARD
        ),
        kwargs_handlers=[GradSyncKwargs(compression="powersgd")],
    )
    with pytest.raises(ValueError, match="params-sharded"):
        acc.prepare_train_step(_mlp_loss)
