"""Mixed-precision policies and dynamic loss scaling.

TPU-native re-design of the reference precision subsystem (SURVEY §2.6):
- AMP autocast (reference accelerator.py:561-612, modeling.py:2049) becomes a
  declarative :class:`Policy` — params kept fp32, compute in bf16/fp16, output
  upcast — applied functionally at the train-step boundary (no context
  manager needed under jit; XLA fuses the casts).
- GradScaler (reference modeling.py:2092, scheduler hold on overflow
  scheduler.py:66-68) becomes :class:`DynamicLossScale`, a pure pytree carried
  in the train state; fp16-only (bf16 on TPU needs no scaling).

There is no fp8 matmul path: the chips this package runs on have no fp8 matmul
units, and ``mixed_precision="fp8"`` is refused by name.  A float8 STORAGE
dtype is an argument of :func:`layerwise_casting`, and quantised KV pages
(``kv_dtype="fp8"``) are ``ops/paged_cache.py``'s.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..utils.dataclasses import MixedPrecisionType


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
