"""Host-offload training tests (ZeRO-offload analog — reference DeepSpeed
``offload_optimizer_device``/``offload_param_device`` dataclasses.py:1172-1187
and FSDP CPUOffload).

On the CPU test mesh, memory-kind placement is unsupported so storage stays
in device memory, but the host-compute update region (``compute_on``) — the
code path that runs on TPU — is fully exercised, and numerics are pinned
offload-vs-resident.  The real pinned-host placement is not measured on the
chip: no benchmark cell offloads (ROADMAP.md A11).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.state import AcceleratorState, GradientState
from accelerate_tpu.test_utils.training import make_regression_loader, regression_loss_fn
from accelerate_tpu.utils.dataclasses import (
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
)


def _mlp_params(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "dense": {"kernel": jax.random.normal(k1, (8, 64)) * 0.1, "bias": jnp.zeros((64,))},
        "out": {"kernel": jax.random.normal(k2, (64, 1)) * 0.1, "bias": jnp.zeros((1,))},
    }


def _mlp_loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["dense"]["kernel"] + params["dense"]["bias"])
    pred = (h @ params["out"]["kernel"] + params["out"]["bias"])[..., 0]
    return jnp.mean((pred - batch["y"]) ** 2)


def _batches(n=6, bs=16, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(bs, 8)).astype(np.float32)
        y = (x.sum(-1) * 0.5).astype(np.float32)
        out.append({"x": jnp.asarray(x), "y": jnp.asarray(y)})
    return out


def _run(offload: bool, accum_plugin=None, mixed_precision="no", n_steps=6,
         chunk_gib=None, tx=None, max_grad_norm=1.0, kwargs_handlers=None,
         pipeline=True):
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    plugin = FullyShardedDataParallelPlugin(
        min_weight_size=0, cpu_offload=offload, host_update_chunk_gib=chunk_gib,
        host_update_pipeline=pipeline,
    )
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=plugin,
        gradient_accumulation_plugin=accum_plugin,
        mixed_precision=mixed_precision,
        kwargs_handlers=kwargs_handlers,
    )
    tx = acc.prepare(tx if tx is not None else optax.adamw(1e-2))
    state = acc.create_train_state(_mlp_params(), tx)
    step = acc.prepare_train_step(_mlp_loss, max_grad_norm=max_grad_norm)
    losses = []
    for batch in _batches(n=n_steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    params = jax.device_get(state.params)
    return losses, params


def test_offload_matches_resident_simple():
    """Host-compute adamw update == resident update, bit-for-bit on CPU."""
    losses_res, params_res = _run(offload=False)
    losses_off, params_off = _run(offload=True)
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), params_off, params_res
    )


@pytest.mark.slow
def test_offload_matches_resident_across_steps_accum():
    """compute_on inside the lax.cond update boundary (across_steps mode)."""
    plugin = GradientAccumulationPlugin(num_steps=3, mode="across_steps")
    losses_res, params_res = _run(offload=False, accum_plugin=plugin)
    losses_off, params_off = _run(offload=True, accum_plugin=plugin)
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), params_off, params_res
    )


def test_offload_with_bf16_grads_tracks_resident():
    """The 7B bench recipe: cpu_offload + GradSyncKwargs(grad_dtype='bf16')
    (grads born compute-width, host upcasts inside the update region) must
    track the resident fp32-grad run."""
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    losses_res, params_res = _run(offload=False, mixed_precision="bf16",
                                  max_grad_norm=None)
    losses_off, params_off = _run(
        offload=True, mixed_precision="bf16", max_grad_norm=None,
        kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")],
    )
    # bf16 grads differ from fp32 grads in the last bits; the trajectories
    # must stay close, not bitwise-equal
    np.testing.assert_allclose(losses_off, losses_res, rtol=5e-2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=0.1, atol=5e-3
        ),
        params_off, params_res,
    )


def test_offload_matches_resident_in_step_accum():
    """compute_on after the scan accumulation (in_step mode)."""
    plugin = GradientAccumulationPlugin(num_steps=4, mode="in_step")
    losses_res, params_res = _run(offload=False, accum_plugin=plugin)
    losses_off, params_off = _run(offload=True, accum_plugin=plugin)
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), params_off, params_res
    )


def test_chunked_host_update_matches_monolithic():
    """Per-leaf-group compute_on regions == one monolithic region, bit-exact
    (VERDICT r2 next #1 done-condition).  A tiny chunk budget forces one leaf
    per group (4 groups for the MLP), exercising slice/merge and the
    serialization tokens."""
    losses_mono, params_mono = _run(offload=True)
    losses_chunk, params_chunk = _run(offload=True, chunk_gib=1e-6)
    # ulp-level tolerance: the math is identical per leaf, but XLA fuses the
    # two graphs differently (fma boundaries), so exact bitwise equality is
    # not guaranteed
    np.testing.assert_allclose(losses_chunk, losses_mono, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-8),
        params_chunk, params_mono,
    )


def test_chunked_host_update_matches_resident():
    """Chunked offload == resident training (the full parity chain)."""
    losses_res, params_res = _run(offload=False)
    losses_chunk, params_chunk = _run(offload=True, chunk_gib=1e-6)
    np.testing.assert_allclose(losses_chunk, losses_res, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), params_chunk, params_res
    )


@pytest.mark.slow
def test_chunked_host_update_with_accum_and_injected_hyperparams():
    """Chunking composes with in_step accumulation and the 7B bench's
    inject_hyperparams(lion) optimizer (traced scalars in the state tree)."""
    accum = GradientAccumulationPlugin(num_steps=2, mode="in_step")
    tx = optax.inject_hyperparams(optax.lion)(learning_rate=1e-2, b1=0.9, b2=0.99)
    losses_mono, params_mono = _run(offload=True, accum_plugin=accum, tx=tx)
    losses_chunk, params_chunk = _run(
        offload=True, accum_plugin=accum, tx=tx, chunk_gib=1e-6
    )
    np.testing.assert_allclose(losses_chunk, losses_mono, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-8),
        params_chunk, params_mono,
    )


@pytest.mark.slow
def test_chunked_host_update_unclipped():
    """max_grad_norm=None (the 7B configuration) under chunking."""
    losses_mono, params_mono = _run(offload=True, max_grad_norm=None)
    losses_chunk, params_chunk = _run(offload=True, chunk_gib=1e-6, max_grad_norm=None)
    np.testing.assert_allclose(losses_chunk, losses_mono, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-8),
        params_chunk, params_mono,
    )


@pytest.mark.slow
def test_offload_with_fp16_loss_scaling():
    """The overflow-hold wheres run inside the host region; training stays
    finite and converges under dynamic loss scaling."""
    losses, _ = _run(offload=True, mixed_precision="fp16", n_steps=8)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_offload_plugin_flag_resolution():
    p = FullyShardedDataParallelPlugin(cpu_offload=True)
    assert p.offload_params is True  # follows cpu_offload by default
    p2 = FullyShardedDataParallelPlugin(cpu_offload=True, offload_params=False)
    assert p2.offload_params is False
    p3 = FullyShardedDataParallelPlugin()
    assert p3.cpu_offload is False


@pytest.mark.slow
def test_offload_with_reference_accelerate_loop(  # the reference loop shape
):
    """Offload works through the plain prepare()/dataloader flow too."""
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=0, cpu_offload=True),
    )
    dl = acc.prepare(make_regression_loader(batch_size=16))
    tx = acc.prepare(optax.adamw(0.05))
    state = acc.create_train_state({"a": jnp.zeros(()), "b": jnp.zeros(())}, tx)
    step = acc.prepare_train_step(regression_loss_fn)
    losses = []
    for _ in range(4):
        for batch in dl:
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_offload_state_checkpoint_roundtrip(tmp_path):
    """save_state/load_state round-trips an offload-configured TrainState and
    training continues (on TPU the restore also re-pins host-resident
    members to pinned_host — checkpointing.py _restore_placement; memory
    kinds degrade to device on the CPU mesh so this covers the flow)."""
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        project_dir=str(tmp_path),
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=0, cpu_offload=True),
    )
    state = acc.create_train_state(_mlp_params(), acc.prepare(optax.adamw(1e-2)))
    step = acc.prepare_train_step(_mlp_loss)
    for batch in _batches(n=2):
        state, _ = step(state, batch)
    w_before = np.asarray(state.params["dense"]["kernel"])
    path = acc.save_state(train_state=state)
    zeroed = state.replace(params=jax.tree_util.tree_map(jnp.zeros_like, state.params))
    restored = acc.load_state(path, train_state=zeroed)
    np.testing.assert_allclose(np.asarray(restored.params["dense"]["kernel"]), w_before)
    restored, m = step(restored, _batches(n=1)[0])
    assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
def test_offload_adafactor_matches_resident():
    """adafactor under the offload step == resident, on the CPU mesh (the
    compute_on region runs either way; real pinned-host placement is the
    on-chip concern test_host_constant_hoist covers abstractly)."""
    tx = optax.adafactor(1e-2)
    res, p_res = _run(False, tx=tx, max_grad_norm=None)
    off, p_off = _run(True, tx=tx, max_grad_norm=None)
    np.testing.assert_allclose(res, off, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), p_res, p_off)


def test_host_constant_hoist():
    """_host_constant_hoist surfaces jaxpr constant arrays as pinned args
    and preserves the function's outputs (adafactor-under-offload enabler).
    On CPU we pin to a plain sharding — the mechanism, not the memory kind."""
    from accelerate_tpu.accelerator import _host_constant_hoist

    const = jnp.arange(8, dtype=jnp.float32)  # captured array -> jaxpr const

    def fn(x, y):
        return jnp.where(x > 0, x * const, y), y + const.sum()

    x = jnp.asarray(np.random.default_rng(0).normal(size=(8,)), jnp.float32)
    y = jnp.ones((8,), jnp.float32)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    hoisted = _host_constant_hoist(fn, sharding, x, y)
    assert hoisted is not fn  # the constant WAS hoisted
    for a, b in zip(fn(x, y), hoisted(x, y)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def no_const(x, y):
        return x + y
    assert _host_constant_hoist(no_const, sharding, x, y) is no_const


def test_offload_lion_sr_bf16_masters_trains():
    """The lion-sr 7B recipe (ops/stochastic_rounding.py) through the full
    offload machinery on the CPU mesh: bf16 stored params (no fp32 master
    tree), SR update inside the host-compute region, monolithic and chunked.
    Offload == resident bitwise (deterministic SR keys); chunked differs
    only in key grouping, so it is asserted to train, not to match."""
    from accelerate_tpu.ops.stochastic_rounding import lion_bf16_sr
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    def run(offload, chunk_gib=None):
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        plugin = FullyShardedDataParallelPlugin(
            min_weight_size=0, cpu_offload=offload, host_update_chunk_gib=chunk_gib
        )
        acc = Accelerator(
            parallelism_config=ParallelismConfig(dp_shard_size=8),
            fsdp_plugin=plugin, mixed_precision="bf16",
            kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")],
        )
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), _mlp_params()
        )
        state = acc.create_train_state(params, acc.prepare(lion_bf16_sr(3e-3)))
        step = acc.prepare_train_step(_mlp_loss, max_grad_norm=None)
        losses = []
        for batch in _batches(n=6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses, jax.device_get(state.params)

    losses_res, params_res = run(False)
    losses_off, params_off = run(True)
    assert jax.tree_util.tree_leaves(params_res)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_off, params_res
    )

    # quality: SR over bf16 masters must track plain lion over fp32 masters
    # at the SAME hyperparams (convergence itself is pinned at length in
    # tests/test_stochastic_rounding.py — 6 sign-steps on this landscape
    # need not decrease monotonically for either optimizer)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc_ref = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=8),
                          mixed_precision="bf16")
    # weight_decay=0.0 explicitly: optax.lion's own default is 1e-3, the SR
    # recipe's is 0.0 — the reference must run the same hyperparameters
    ref_state = acc_ref.create_train_state(
        _mlp_params(), acc_ref.prepare(optax.lion(3e-3, b1=0.9, b2=0.99,
                                                  weight_decay=0.0,
                                                  mu_dtype=jnp.bfloat16)))
    ref_step = acc_ref.prepare_train_step(_mlp_loss, max_grad_norm=None)
    ref_losses = []
    for batch in _batches(n=6):
        ref_state, m = ref_step(ref_state, batch)
        ref_losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses_res, ref_losses, rtol=0.35)

    losses_chunk, params_chunk = run(True, chunk_gib=1e-6)  # every leaf its own group
    assert jax.tree_util.tree_leaves(params_chunk)[0].dtype == jnp.bfloat16
    assert np.isfinite(losses_chunk).all()
    np.testing.assert_allclose(losses_chunk, ref_losses, rtol=0.35)


def test_offload_adamw_sr_bf16_masters_trains():
    """adamw_bf16_sr (bf16 params + bf16 SR-maintained m/v) through the
    offload machinery: same contracts as the lion-sr test — offload ==
    resident bitwise (deterministic SR keys), chunked trains, and the SR
    recipe tracks fp32 adamw at the same hyperparams."""
    from accelerate_tpu.ops.stochastic_rounding import adamw_bf16_sr
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    def run(offload, chunk_gib=None):
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        plugin = FullyShardedDataParallelPlugin(
            min_weight_size=0, cpu_offload=offload, host_update_chunk_gib=chunk_gib
        )
        acc = Accelerator(
            parallelism_config=ParallelismConfig(dp_shard_size=8),
            fsdp_plugin=plugin, mixed_precision="bf16",
            kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")],
        )
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), _mlp_params()
        )
        state = acc.create_train_state(params, acc.prepare(adamw_bf16_sr(3e-3)))
        step = acc.prepare_train_step(_mlp_loss, max_grad_norm=None)
        losses = []
        for batch in _batches(n=6):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses, jax.device_get(state.params)

    losses_res, params_res = run(False)
    losses_off, params_off = run(True)
    assert jax.tree_util.tree_leaves(params_res)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_off, params_res
    )

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc_ref = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=8),
                          mixed_precision="bf16")
    # weight_decay=0.0 explicitly: optax.adamw's own default is 1e-4, the SR
    # recipe's is 0.0 — the reference must run the same hyperparameters
    ref_state = acc_ref.create_train_state(
        _mlp_params(), acc_ref.prepare(optax.adamw(3e-3, weight_decay=0.0)))
    ref_step = acc_ref.prepare_train_step(_mlp_loss, max_grad_norm=None)
    ref_losses = []
    for batch in _batches(n=6):
        ref_state, m = ref_step(ref_state, batch)
        ref_losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses_res, ref_losses, rtol=0.35)

    losses_chunk, params_chunk = run(True, chunk_gib=1e-6)
    assert jax.tree_util.tree_leaves(params_chunk)[0].dtype == jnp.bfloat16
    assert np.isfinite(losses_chunk).all()
    np.testing.assert_allclose(losses_chunk, ref_losses, rtol=0.35)


def _run_sr8(recipe, offload, chunk_gib=None, pipeline=True):
    """The -sr8 recipes (ops/int8_state.py: bf16 SR params + int8 blockwise
    moment state) through the full offload machinery on the CPU mesh."""
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    plugin = FullyShardedDataParallelPlugin(
        min_weight_size=0, cpu_offload=offload, host_update_chunk_gib=chunk_gib,
        host_update_pipeline=pipeline,
    )
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        fsdp_plugin=plugin, mixed_precision="bf16",
        kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")],
    )
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), _mlp_params())
    state = acc.create_train_state(params, acc.prepare_optimizer(recipe))
    step = acc.prepare_train_step(_mlp_loss, max_grad_norm=None)
    losses = []
    for batch in _batches(n=6):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, jax.device_get(state.params), jax.device_get(state.opt_state)


# ---------------------------------------------------------------------------
# Pipelined (double-buffered) chunked update — ops/streaming.py
# ---------------------------------------------------------------------------


def test_pipelined_offload_update_matches_serial_bitwise():
    """The 3-stage chunk pipeline (stage A per-chunk D2H, stage C per-chunk
    write-back, only the update regions token-serialized) is BITWISE
    identical to the fully serialized schedule: same chunk boundaries, same
    per-group math — the pipeline only reorders transfers.  adamw exercises
    the congruent-moment + shared-count slicing.

    Scope on this mesh: memory kinds degrade on CPU, so stage A slices the
    same values either way, but stage C's per-chunk placements DO run here
    (deliberately not gated on kinds_ok) — pipelined and serial trace
    genuinely different programs and must still agree bit-for-bit.  The
    pinned-host transfer legs are the on-chip concern (not measured on the
    chip: ROADMAP.md A11)."""
    losses_ser, params_ser = _run(offload=True, chunk_gib=1e-6, pipeline=False)
    losses_pipe, params_pipe = _run(offload=True, chunk_gib=1e-6, pipeline=True)
    assert losses_pipe == losses_ser
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_pipe, params_ser
    )


@pytest.mark.parametrize("recipe", ["lion-sr8", "adamw-sr8"])
def test_pipelined_offload_sr8_matches_serial_bitwise(recipe):
    """The SR-hash contract under the pipeline: -sr8 salts its SR streams
    with group-relative leaf indices, so identical chunk boundaries must
    give identical codes/scales/params no matter how the transfers are
    scheduled — pipelined == serial bit-for-bit, including the int8/uint8
    moment state."""
    losses_ser, params_ser, opt_ser = _run_sr8(recipe, offload=True,
                                               chunk_gib=1e-6, pipeline=False)
    losses_pipe, params_pipe, opt_pipe = _run_sr8(recipe, offload=True,
                                                  chunk_gib=1e-6, pipeline=True)
    assert losses_pipe == losses_ser
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_pipe, params_ser
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), opt_pipe, opt_ser
    )


def test_pipelined_offload_with_clipping_matches_serial():
    """max_grad_norm forces the host-side global-norm barrier (stage A
    degrades to bulk staging); the pipeline must still match the serial
    schedule exactly."""
    losses_ser, params_ser = _run(offload=True, chunk_gib=1e-6, pipeline=False,
                                  max_grad_norm=1.0)
    losses_pipe, params_pipe = _run(offload=True, chunk_gib=1e-6, pipeline=True,
                                    max_grad_norm=1.0)
    assert losses_pipe == losses_ser
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_pipe, params_ser
    )


@pytest.mark.parametrize("recipe", ["lion-sr8", "adamw-sr8"])
def test_offload_sr8_matches_resident_bitwise(recipe):
    """Bitwise expectation, documented: the -sr8 update is per-leaf
    deterministic (hashed SR keys from (count, leaf, value, grad) — no RNG
    state), so the host-compute offload run must reproduce the resident run
    EXACTLY: same losses, bit-identical bf16 params, bit-identical int8/uint8
    codes and fp32 scales.  Chunked grouping re-keys the per-leaf salts
    (group-relative leaf indices), so the chunked run is asserted to train,
    not to match bitwise."""
    losses_res, params_res, opt_res = _run_sr8(recipe, offload=False)
    losses_off, params_off, opt_off = _run_sr8(recipe, offload=True)
    assert jax.tree_util.tree_leaves(params_res)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(losses_off, losses_res, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params_off, params_res
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), opt_off, opt_res
    )
    # the moment codes really are 8-bit storage
    assert opt_off.mu["dense"]["kernel"].dtype == jnp.int8
    if recipe == "adamw-sr8":
        assert opt_off.nu["dense"]["kernel"].dtype == jnp.uint8

    losses_chunk, params_chunk, _ = _run_sr8(recipe, offload=True, chunk_gib=1e-6)
    assert jax.tree_util.tree_leaves(params_chunk)[0].dtype == jnp.bfloat16
    assert np.isfinite(losses_chunk).all()
    # chunked offload must still land in the resident run's loss neighborhood
    np.testing.assert_allclose(losses_chunk, losses_res, rtol=0.35)
