"""Shared model layers.

:class:`QuantizableDense` is the integration point for weight-only
quantization (reference bnb int8 inference path, ``utils/bnb.py:469``,
where ``Linear8bitLt`` modules are swapped in): a drop-in ``nn.Dense``
whose kernel may be a :class:`~accelerate_tpu.utils.quantization.QuantizedTensor`
pytree leaf.  When it is, the matmul runs through the Pallas int8 kernel
(``ops/quantized_matmul.py``) — codes stream HBM→VMEM at one byte per
weight and dequantize in-tile, so decode reads half the bytes of bf16
weights and the full-width tensor never materializes in HBM.  (The previous
integration, ``quantized_apply``'s whole-tree dequantize-then-apply, left
int8 decode ~700x slower than bf16 because XLA re-materialized every
weight every step.)

Non-quantized kernels take the standard ``jnp.dot`` path; NF4 kernels fall
back to an in-layer dequantize that XLA fuses into the consumer.

It is also the integration point for **multi-tenant batched LoRA**
(``ops/lora.py``): when the module holds ``a``/``b`` stacks in the ``lora``
variable collection and the caller passes per-row ``adapter_ids``, the
segment-batched adapter contribution ``(x @ A[ids]) @ B[ids]`` joins the
base matmul as one gathered einsum — fixed shapes for any tenant mix, so
the serving decode step never recompiles on adapter routing.

What the served expert families share (``models/keye_vl2.py``,
``models/k_exaone.py``, ``models/joyai_flash.py``) also lives here, under
public names: :func:`rotary_angles` / :func:`apply_rotary` (angles computed
from the positions, rotate-half, float32), :func:`bias_free_proj` (a bf16
projection), :class:`Float32Out` (bf16 operands, the float32 accumulator
handed on: these families' residual stream is float32) and
:class:`Float32Dense` (float32 at the highest precision, for outputs that feed
a discrete choice).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.collective_matmul import dense_collective_matmul
from ..ops.lora import lora_apply
from ..ops.quantized_matmul import quantized_matmul
from ..utils.quantization import is_quantized


class QuantizableDense(nn.Module):
    """``nn.Dense`` that accepts an int8/NF4 ``QuantizedTensor`` kernel.

    The quantized kernel is fetched with ``get_variable`` (``self.param``
    would flatten the QuantizedTensor pytree and fail its leaf-wise shape
    check); init mode always creates a full-precision kernel.

    ``tp_mode`` declares the layer's Megatron role ("column": output dim
    tp-sharded, "row": input dim tp-sharded) so that, when the collective-
    matmul knob is on (``ops/collective_matmul.py``), the matmul runs as a
    latency-hiding ring over ``tp_axis`` instead of leaving the monolithic
    all-gather / reduce-scatter to GSPMD.  The ring falls back to the plain
    ``jnp.dot`` path whenever it cannot engage (trivial axis, non-dividing
    shapes, decode-length inputs) — global values are identical either way.
    """

    features: int
    use_bias: bool = True
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()
    tp_mode: Optional[str] = None  # None | "column" | "row"
    tp_axis: str = "tp"

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        stored = None
        if not self.is_initializing() and self.has_variable("params", "kernel"):
            stored = self.get_variable("params", "kernel")
        dtype = self.dtype or x.dtype
        if is_quantized(stored):
            y = quantized_matmul(x.astype(dtype), stored, out_dtype=dtype)
        else:
            kernel = self.param(
                "kernel", self.kernel_init, (x.shape[-1], self.features), self.param_dtype
            )
            y = None
            if self.tp_mode is not None:
                y = dense_collective_matmul(
                    x.astype(dtype), kernel.astype(dtype), self.tp_mode,
                    axis_name=self.tp_axis,
                )
            if y is None:
                y = jnp.dot(x.astype(dtype), kernel.astype(dtype))
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
            y = y + bias.astype(dtype)
        if adapter_ids is not None and self.has_variable("lora", "a"):
            # segment-batched multi-adapter LoRA (ops/lora.py): the a/b
            # stacks live in the "lora" collection (the AdapterStore's
            # device pool), adapter_ids are per-row pool-slot indices, and
            # id-0 rows come back bitwise-unchanged
            y = lora_apply(
                x.astype(dtype), y,
                self.get_variable("lora", "a"), self.get_variable("lora", "b"),
                adapter_ids,
            )
        return y


# -- shared by the served expert families ----------------------------------------


def rotary_angles(positions, dim: int, theta: float, sections=None):
    """Angles ``[B, T, dim / 2]`` (float32) computed from the positions.
    ``positions`` ``[B, T]``, or ``[3, B, T]`` with ``sections``: frequency
    pair ``i`` takes its angle from axis 0, 1 or 2 by the section it lies in."""
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    if positions.ndim == 2:
        return ang
    axis = np.repeat(np.arange(3), sections)                       # [dim / 2]
    return sum(jnp.where(axis == a, ang[a], 0.0) for a in range(3))


def apply_rotary(x, angles):
    """x ``[B, T, heads, D]`` (rotate-half); computed and returned in float32."""
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def bias_free_proj(features: int, cfg, name: str):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


class Float32Out(nn.Module):
    """A bias-free projection with operands in ``dtype`` (one MXU pass) whose
    float32 accumulator is handed on unrounded: the residual stream of this
    family is float32, so that what reaches the next router and indexer has
    been rounded once (the operands), not at every addition."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return lax.dot_general(x.astype(self.dtype), kernel.astype(self.dtype),
                               (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


class Float32Dense(nn.Module):
    """A bias-free projection of float32 operands at the highest matmul
    precision.  For the router's logits and the indexer, whose outputs feed a
    discrete choice (top-8 of 128, top-2048 of the context): a bf16 product
    moves the choice, and these matrices are small (2048 x 128, 2048 x 1104)."""

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return lax.dot_general(x.astype(jnp.float32), kernel.astype(jnp.float32),
                               (((x.ndim - 1,), (0,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
