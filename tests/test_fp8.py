"""fp8 end-to-end training tests (reference fp8 integration: ao.py /
transformer_engine.py / fp8_utils, wired via mixed_precision="fp8" —
examples/torch_native_parallelism/README.md claims ~25% throughput on
H100s; here the path is QuantizableDense -> fp8_current_scaled_dot under
the fp8_autocast trace-time region).

On the CPU mesh fp8 dtypes are emulated, so these tests pin semantics
(routing, gradients, loss parity with bf16), not speed; the one v5e delta
ever taken (−7%, before PR 1 on another toolchain) is in ROADMAP.md C9.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
from accelerate_tpu.models.layers import QuantizableDense
from accelerate_tpu.ops.precision import (
    Fp8Meta,
    fp8_autocast,
    fp8_current_scaled_dot,
    fp8_dot,
    fp8_enabled,
)
from accelerate_tpu.state import AcceleratorState, GradientState


def test_fp8_autocast_flag_nesting():
    assert not fp8_enabled()
    with fp8_autocast():
        assert fp8_enabled()
        with fp8_autocast(enabled=False):
            assert not fp8_enabled()
        assert fp8_enabled()
    assert not fp8_enabled()


def test_fp8_current_scaled_dot_accuracy_and_grads():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)

    def loss8(x, w):
        return jnp.mean(fp8_current_scaled_dot(x, w).astype(jnp.float32) ** 2)

    def loss16(x, w):
        return jnp.mean(jnp.dot(x, w).astype(jnp.float32) ** 2)

    l8, (gx8, gw8) = jax.value_and_grad(loss8, argnums=(0, 1))(x, w)
    l16, (gx16, gw16) = jax.value_and_grad(loss16, argnums=(0, 1))(x, w)
    assert abs(float(l8) - float(l16)) < 0.1 * float(l16)
    # straight-through bwd: grads close to the bf16 reference
    for a, b in ((gx8, gx16), (gw8, gw16)):
        num = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        den = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-6
        assert num / den < 0.15, num / den


@pytest.mark.slow
def test_fp8_dot_delayed_scaling_meta_updates():
    x = jnp.ones((4, 16), jnp.bfloat16) * 3.0
    w = jnp.ones((16, 8), jnp.bfloat16) * 0.5
    out, (xm, wm) = fp8_dot(x, w, Fp8Meta.init(), Fp8Meta.init())
    assert out.shape == (4, 8)
    assert float(xm.amax_history[0]) == pytest.approx(3.0)
    assert float(wm.amax_history[0]) == pytest.approx(0.5)
    assert float(xm.scale) > 1.0  # 448 / 3


def test_quantizable_dense_routes_fp8():
    m = QuantizableDense(features=32, use_bias=False, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 64)), jnp.bfloat16)
    params = m.init(jax.random.PRNGKey(0), x)
    ref = m.apply(params, x)
    with fp8_autocast():
        out = m.apply(params, x)
    # fp8 introduces quantization error — close but not identical
    diff = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert 0 < diff < 0.1 * (float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-6)


def _train_llama(mixed_precision, n_steps=8):
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=8),
        mixed_precision=mixed_precision,
    )
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    state = acc.create_train_state(params, optax.adamw(1e-3), apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
    rng = np.random.default_rng(0)
    # one fixed batch: the convergence signal is memorization, which shows
    # in 8 steps where fresh random tokens would not
    toks = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    losses = []
    for _ in range(n_steps):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses


@pytest.mark.slow
def test_fp8_hardware_gate_warns(caplog):
    """Requesting fp8 on hardware without fp8 matmul units warns loudly but
    honors the request (the CPU mesh has no fp8 units, so the gate fires
    here exactly as it does on TPU v5e)."""
    import logging

    from accelerate_tpu.ops.precision import fp8_hardware_supported

    assert not fp8_hardware_supported()  # CPU mesh
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    with caplog.at_level(logging.WARNING, logger="accelerate_tpu.state"):
        acc = Accelerator(mixed_precision="fp8")
    assert acc.mixed_precision == "fp8"  # explicit opt-out preserved
    assert any("no fp8 matmul units" in r.message for r in caplog.records)


@pytest.mark.slow
def test_fp8_hardware_gate_env_fallback(monkeypatch, caplog):
    """ACCELERATE_FP8_FALLBACK_BF16=true degrades to bf16 on unsupported
    hardware instead of training slower in fp8."""
    import logging

    monkeypatch.setenv("ACCELERATE_FP8_FALLBACK_BF16", "true")
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    with caplog.at_level(logging.WARNING, logger="accelerate_tpu.state"):
        acc = Accelerator(mixed_precision="fp8")
    assert acc.mixed_precision == "bf16"
    assert any("falling back to bf16" in r.message for r in caplog.records)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_fp8_hardware_probe_kinds():
    """The capability probe keys on TPU generation (v6/Trillium+ have fp8
    MXU paths; v5e and earlier do not)."""
    from accelerate_tpu.ops.precision import _tpu_kind_has_fp8

    for kind, want in [("TPU v5 lite", False), ("TPU v4", False), ("TPU v5p", False),
                       ("TPU v6e", True), ("TPU v6 lite", True), ("TPU v7x", True)]:
        assert _tpu_kind_has_fp8(kind) is want, kind


# ---------------------------------------------------------------------------
# delayed scaling: fp8_state rides TrainState (ISSUE 17 tentpole leg 1)
# ---------------------------------------------------------------------------


def test_fp8_recipe_kwargs_env_and_validation(monkeypatch):
    from accelerate_tpu import FP8RecipeKwargs

    assert FP8RecipeKwargs().amax_history_len == 16  # TE default
    monkeypatch.setenv("ACCELERATE_FP8_AMAX_HISTORY_LEN", "32")
    monkeypatch.setenv("ACCELERATE_FP8_MARGIN", "2")
    r = FP8RecipeKwargs()
    assert r.amax_history_len == 32 and r.margin == 2
    assert FP8RecipeKwargs(amax_history_len=8).amax_history_len == 8  # explicit wins
    with pytest.raises(ValueError, match="amax_history_len"):
        FP8RecipeKwargs(amax_history_len=0)
    with pytest.raises(ValueError, match="margin"):
        FP8RecipeKwargs(margin=-1)
    with pytest.raises(ValueError, match="amax_compute_algo"):
        FP8RecipeKwargs(amax_compute_algo="mean")


def test_fp8_state_rides_train_state_and_checkpoints(tmp_path):
    """The delayed-scaling amax histories are TrainState citizens: sized by
    the FP8RecipeKwargs recipe, seeded with each kernel's current amax,
    rolled once per optimizer step (TE DelayedScaling contract), and they
    survive a save_state/load_state roundtrip."""
    import optax as _optax

    from accelerate_tpu import FP8RecipeKwargs
    from accelerate_tpu.utils.dataclasses import ProjectConfiguration

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(tmp_path), automatic_checkpoint_naming=True
        ),
        mixed_precision="fp8",
        kwargs_handlers=[FP8RecipeKwargs(amax_history_len=4, margin=1)],
    )
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    state = acc.create_train_state(params, _optax.adamw(1e-3), apply_fn=model.apply)
    assert state.fp8_state is not None
    # snapshot with a REAL copy: the jitted step donates the state's
    # buffers, and on CPU np.asarray aliases them zero-copy — a donated
    # buffer would mutate the "snapshot" in place
    hists = [np.array(x, copy=True)
             for x in jax.tree_util.tree_leaves(state.fp8_state)
             if getattr(x, "ndim", 0) == 1]
    assert hists and all(h.shape == (4,) for h in hists)  # recipe honored
    # seeded with the kernel's current amax: step 0 quantizes with exactly
    # the current-scaling scale
    assert all(float(h[0]) > 0 for h in hists)

    step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    state2, _ = step(state, batch)
    new_hists = [x for x in jax.tree_util.tree_leaves(state2.fp8_state)
                 if getattr(x, "ndim", 0) == 1]
    # one tick: the history rolled, slot 1 now carries the seed amax
    for old, new in zip(hists, new_hists):
        assert float(new[1]) == float(old[0])
        assert float(new[0]) > 0

    ckpt = acc.save_state(train_state=state2)
    template = acc.create_train_state(
        model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)),
        _optax.adamw(1e-3), apply_fn=model.apply,
    )
    restored = acc.load_state(ckpt, train_state=template)
    for a, b in zip(jax.tree_util.tree_leaves(restored.fp8_state),
                    jax.tree_util.tree_leaves(state2.fp8_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_fp8_ops_pass_gl110_scaling_audit():
    """Clean sweep: the repo's own fp8 matmuls carry their descale through
    the GL110 jaxpr audit (every fp8 dot's output feeds a mul/div by the
    combined scale before any other consumer)."""
    from accelerate_tpu.analysis.jaxpr_audit import audit_traced
    from accelerate_tpu.ops.fp8 import fp8_delayed_dot

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16)
    meta = Fp8Meta.init(4).updated(jnp.float32(2.0), 448.0, 0)
    reports = {
        "current": audit_traced(
            jax.jit(lambda a, b: fp8_current_scaled_dot(a, b)).trace(x, w)),
        "delayed": audit_traced(
            jax.jit(lambda a, b: fp8_delayed_dot(a, b, meta)).trace(x, w)),
        "delayed_grad": audit_traced(jax.jit(jax.grad(
            lambda a, b: jnp.sum(fp8_delayed_dot(a, b, meta).astype(jnp.float32))
        )).trace(x, w)),
    }
    for name, rep in reports.items():
        hits = [f for f in rep.findings if f.rule == "GL110"]
        assert not hits, (name, [f.message for f in hits])


@pytest.mark.slow
def test_fp8_training_tracks_bf16():
    """mixed_precision="fp8" trains the tiny Llama to parity-class loss with
    bf16 (VERDICT r1 next #5 done-condition, on the CPU mesh)."""
    bf16 = _train_llama("bf16")
    fp8 = _train_llama("fp8")
    assert all(np.isfinite(fp8))
    # same trajectory within fp8 quantization noise
    for a, b in zip(fp8, bf16):
        assert abs(a - b) < 0.05 * abs(b) + 0.05, (fp8, bf16)
    # and it actually learns
    assert fp8[-1] < fp8[0]
