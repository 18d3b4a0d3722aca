"""Mixed-precision policies, dynamic loss scaling, and fp8 matmul.

TPU-native re-design of the reference precision subsystem (SURVEY §2.6):
- AMP autocast (reference accelerator.py:561-612, modeling.py:2049) becomes a
  declarative :class:`Policy` — params kept fp32, compute in bf16/fp16, output
  upcast — applied functionally at the train-step boundary (no context
  manager needed under jit; XLA fuses the casts).
- GradScaler (reference modeling.py:2092, scheduler hold on overflow
  scheduler.py:66-68) becomes :class:`DynamicLossScale`, a pure pytree carried
  in the train state; fp16-only (bf16 on TPU needs no scaling).
- FP8 (reference TE/AO/MSAMP backends, dataclasses.py:311-483) becomes
  :func:`fp8_dot` — native ``float8_e4m3fn``/``e5m2`` matmul with delayed
  per-tensor scaling, which XLA lowers onto the MXU directly.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..utils.dataclasses import FP8Format, MixedPrecisionType


def _cast_floating(tree, dtype):
    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(_cast, tree)


@dataclass(frozen=True)
class Policy:
    """Param/compute/output dtype triple (jmp-style; the autocast analog)."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree):
        return _cast_floating(tree, self.compute_dtype)

    def cast_to_param(self, tree):
        return _cast_floating(tree, self.param_dtype)

    def cast_to_output(self, tree):
        return _cast_floating(tree, self.output_dtype)

    @property
    def needs_loss_scaling(self) -> bool:
        return self.compute_dtype == jnp.float16


def fp8_hardware_supported() -> bool:
    """Whether the local accelerator has native fp8 matmul paths.

    TPU generations before v6 (Trillium) have no fp8 MXU: ``fp8_dot``'s
    quantize/descale work is pure overhead there (−7% vs bf16 on v5e,
    measured before PR 1 on another toolchain — ROADMAP.md C9).  The reference's fp8 backend auto-pick
    degrades gracefully on unsupported hardware (reference
    accelerator.py:480-503); this is the capability probe behind the
    equivalent gate here."""
    try:
        dev = jax.devices()[0]
    except RuntimeError:  # pragma: no cover - no backend
        return False
    if dev.platform == "tpu":
        return _tpu_kind_has_fp8(getattr(dev, "device_kind", ""))
    if dev.platform == "gpu":  # pragma: no cover - no GPU in CI
        return True  # XLA:GPU lowers fp8 dots natively on Ada/Hopper+
    return False


def _tpu_kind_has_fp8(device_kind: str) -> bool:
    import re

    m = re.search(r"v(\d+)", device_kind.lower())
    return bool(m and int(m.group(1)) >= 6)


def get_policy(mixed_precision: str | MixedPrecisionType) -> Policy:
    """Map the reference's ``mixed_precision`` strings to a Policy
    (reference AcceleratorState precision resolution state.py:940-985)."""
    mp = MixedPrecisionType(str(mixed_precision))
    if mp == MixedPrecisionType.NO:
        return Policy()
    if mp == MixedPrecisionType.BF16:
        return Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, output_dtype=jnp.float32)
    if mp == MixedPrecisionType.FP16:
        return Policy(param_dtype=jnp.float32, compute_dtype=jnp.float16, output_dtype=jnp.float32)
    if mp == MixedPrecisionType.FP8:
        # fp8 applies at matmul granularity (fp8_dot); activations ride bf16
        return Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, output_dtype=jnp.float32)
    raise ValueError(f"unsupported mixed precision {mixed_precision!r}")


# ---------------------------------------------------------------------------
# Dynamic loss scaling (fp16) — pure-pytree GradScaler
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class DynamicLossScale:
    """Pure functional GradScaler (reference get_grad_scaler modeling.py:2092).

    Carried inside the train state; ``update`` returns a *new* instance.
    Matches torch.cuda.amp semantics: scale doubles every ``growth_interval``
    consecutive finite steps, halves on overflow, and overflowed steps skip
    the optimizer update (reference optimizer.py:163-177 skipped-step detect).
    """

    def __init__(self, scale=None, growth_factor=2.0, backoff_factor=0.5, growth_interval=2000, counter=None):
        self.scale = jnp.float32(2.0**16) if scale is None else scale
        self.growth_factor = growth_factor
        self.backoff_factor = backoff_factor
        self.growth_interval = growth_interval
        self.counter = jnp.int32(0) if counter is None else counter

    def scale_loss(self, loss):
        return loss * self.scale

    def unscale(self, grads):
        inv = 1.0 / self.scale
        return jax.tree_util.tree_map(lambda g: (g * inv).astype(g.dtype), grads)

    def update(self, grads_finite):
        new_counter = jnp.where(grads_finite, self.counter + 1, 0).astype(jnp.int32)
        grow = new_counter >= self.growth_interval
        new_scale = jnp.where(
            grads_finite,
            jnp.where(grow, self.scale * self.growth_factor, self.scale),
            self.scale * self.backoff_factor,
        )
        new_counter = jnp.where(grow, 0, new_counter).astype(jnp.int32)
        return DynamicLossScale(
            scale=new_scale,
            growth_factor=self.growth_factor,
            backoff_factor=self.backoff_factor,
            growth_interval=self.growth_interval,
            counter=new_counter,
        )

    def tree_flatten(self):
        return (self.scale, self.counter), (self.growth_factor, self.backoff_factor, self.growth_interval)

    @classmethod
    def tree_unflatten(cls, aux, children):
        scale, counter = children
        growth_factor, backoff_factor, growth_interval = aux
        return cls(scale, growth_factor, backoff_factor, growth_interval, counter)


def all_finite(tree) -> jax.Array:
    """True iff every element of every leaf is finite (overflow detector)."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "dtype")]
    if not leaves:
        return jnp.bool_(True)
    return jnp.stack(leaves).all()


# ---------------------------------------------------------------------------
# FP8 matmul with delayed scaling (the TE/torchao analog)
# ---------------------------------------------------------------------------

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


@jax.tree_util.register_pytree_node_class
class Fp8Meta:
    """Per-tensor amax history + derived scales (TE DelayedScaling analog,
    reference TERecipeKwargs dataclasses.py:359)."""

    def __init__(self, amax_history, scale):
        self.amax_history = amax_history
        self.scale = scale

    @classmethod
    def init(cls, history_len: int = 16):
        return cls(jnp.zeros((history_len,), jnp.float32), jnp.float32(1.0))

    def updated(self, amax, fp8_max: float, margin: int = 0):
        hist = jnp.roll(self.amax_history, 1).at[0].set(amax)
        amax_ref = jnp.max(hist)
        scale = jnp.where(amax_ref > 0, fp8_max / (amax_ref * (2.0**margin)), 1.0)
        return Fp8Meta(hist, scale.astype(jnp.float32))

    def tree_flatten(self):
        return (self.amax_history, self.scale), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def quantize_fp8(x, meta: Fp8Meta, dtype=jnp.float8_e4m3fn, fp8_max: float = E4M3_MAX):
    """Scale + saturate-cast to fp8; returns (q, new_meta)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    new_meta = meta.updated(amax, fp8_max)
    q = jnp.clip(x.astype(jnp.float32) * new_meta.scale, -fp8_max, fp8_max).astype(dtype)
    return q, new_meta


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fp8_matmul(x, w, x_scale, w_scale, preferred_element_type):
    """Scaled-e4m3 matmul on the MXU with a bf16 straight-through backward
    (the HYBRID e5m2-bwd behavior approximated by bf16 — strictly more
    accurate, same speed class on TPU)."""
    qx = jnp.clip(x.astype(jnp.float32) * x_scale, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
    qw = jnp.clip(w.astype(jnp.float32) * w_scale, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
    out = jax.lax.dot_general(
        qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (out / (x_scale * w_scale)).astype(preferred_element_type)


def _fp8_matmul_fwd(x, w, x_scale, w_scale, preferred_element_type):
    return _fp8_matmul(x, w, x_scale, w_scale, preferred_element_type), (x, w)


def _fp8_matmul_bwd(preferred_element_type, res, g):
    x, w = res
    g = g.astype(preferred_element_type)
    dx = jax.lax.dot_general(
        g, w.astype(preferred_element_type), (((g.ndim - 1,), (1,)), ((), ()))
    ).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1]).astype(preferred_element_type)
    g2 = g.reshape(-1, g.shape[-1])
    dw = jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ()))).astype(w.dtype)
    return dx, dw, None, None


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


def fp8_dot(
    x,
    w,
    x_meta: Fp8Meta,
    w_meta: Fp8Meta,
    fp8_format: FP8Format = FP8Format.HYBRID,
    preferred_element_type=jnp.bfloat16,
):
    """fp8 matmul with TE-style delayed scaling: quantize both operands to
    e4m3 using amax-history scales, matmul on the MXU, de-scale the result.
    Returns (out, (new_x_meta, new_w_meta))."""
    del fp8_format
    amax_x = jnp.max(jnp.abs(x)).astype(jnp.float32)
    amax_w = jnp.max(jnp.abs(w)).astype(jnp.float32)
    new_x_meta = x_meta.updated(amax_x, E4M3_MAX)
    new_w_meta = w_meta.updated(amax_w, E4M3_MAX)
    out = _fp8_matmul(x, w, new_x_meta.scale, new_w_meta.scale, preferred_element_type)
    return out, (new_x_meta, new_w_meta)


def fp8_current_scaled_dot(x, w, preferred_element_type=jnp.bfloat16):
    """Stateless fp8 matmul with current-step scaling.

    The delayed-scaling history (TE DelayedScaling) exists on GPUs to avoid
    an extra amax pass over the operands; on TPU the amax reduction fuses
    into the producing op, so fresh per-call scales are both simpler (no
    meta state threaded through the step) and strictly more accurate.  This
    is the form :class:`~accelerate_tpu.models.layers.QuantizableDense`
    uses under :func:`fp8_autocast`."""
    amax_x = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-12)
    amax_w = jnp.maximum(jnp.max(jnp.abs(w)).astype(jnp.float32), 1e-12)
    return _fp8_matmul(
        x, w, E4M3_MAX / amax_x, E4M3_MAX / amax_w, preferred_element_type
    )


# Trace-time fp8 region flag (the TE fp8_autocast analog, reference
# utils/transformer_engine.py / ao.py).  The prepared train/eval steps wrap
# the loss under this context when mixed_precision="fp8"; QuantizableDense
# reads it at trace time and routes its matmul through fp8.
_FP8_STATE = threading.local()


@contextlib.contextmanager
def fp8_autocast(enabled: bool = True):
    prev = getattr(_FP8_STATE, "enabled", False)
    _FP8_STATE.enabled = enabled
    try:
        yield
    finally:
        _FP8_STATE.enabled = prev


def fp8_enabled() -> bool:
    return getattr(_FP8_STATE, "enabled", False)


# ---------------------------------------------------------------------------
# layerwise casting (reference attach_layerwise_casting_hooks
# big_modeling.py:654: per-module storage dtype vs compute dtype)
# ---------------------------------------------------------------------------


def layerwise_casting(
    params,
    storage_dtype=jnp.float8_e4m3fn,
    compute_dtype=jnp.bfloat16,
    skip_patterns: tuple = ("norm", "embed", "bias", "scale"),
):
    """Shrink parameter storage per-leaf while keeping compute precision.

    The reference walks modules attaching pre/post-forward casting hooks; on
    TPU the same capability is a pytree map: matching floating leaves are
    stored in ``storage_dtype`` (e.g. fp8 — half the HBM footprint of bf16)
    and :func:`layerwise_cast_apply` upcasts them to ``compute_dtype``
    *inside* jit, where XLA fuses the cast into the consuming op.

    Returns ``(cast_params, apply_wrapper)``.
    """
    import re

    from ..parallel.sharding import path_str

    def _store(path, leaf):
        if not (hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        if any(re.search(p, path_str(path).lower()) for p in skip_patterns):
            return leaf
        return leaf.astype(storage_dtype)

    cast_params = jax.tree_util.tree_map_with_path(_store, params)

    def apply_wrapper(apply_fn):
        def wrapped(p, *args, **kwargs):
            upcast = jax.tree_util.tree_map(
                lambda x: x.astype(compute_dtype)
                if hasattr(x, "dtype") and x.dtype == jnp.dtype(storage_dtype)
                else x,
                p,
            )
            return apply_fn(upcast, *args, **kwargs)

        return wrapped

    return cast_params, apply_wrapper
