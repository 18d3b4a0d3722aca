"""In-jit collectives over named mesh axes — the GSPMD hot-path plane.

These are the explicit collectives used inside ``shard_map`` bodies (ring
attention KV rotation, Ulysses all-to-alls, MoE dispatch).  Everything else in
the framework relies on *implicit* collectives: XLA derives psum/all-gather/
reduce-scatter from sharding annotations on jitted computations — the
TPU-native replacement for the reference's NCCL calls (SURVEY §2.5).

Axis-name arguments accept a single name or a tuple (joint dims like
``("dp_replicate", "dp_shard")`` — the reference's flattened mesh dims,
parallelism_config.py:157-164).
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisNames = Union[str, Sequence[str]]


def _normalize(axis_names: AxisNames):
    if isinstance(axis_names, str):
        return axis_names
    return tuple(axis_names)


def psum(x, axis_names: AxisNames):
    """All-reduce sum across mesh axes (NCCL all_reduce analog)."""
    return lax.psum(x, _normalize(axis_names))


def pmean(x, axis_names: AxisNames):
    return lax.pmean(x, _normalize(axis_names))


def pmax(x, axis_names: AxisNames):
    return lax.pmax(x, _normalize(axis_names))


def pmin(x, axis_names: AxisNames):
    return lax.pmin(x, _normalize(axis_names))


def all_gather(x, axis_names: AxisNames, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` (NCCL all_gather analog)."""
    return lax.all_gather(x, _normalize(axis_names), axis=axis, tiled=tiled)


def reduce_scatter(x, axis_names: AxisNames, axis: int = 0):
    """Sum-reduce then scatter along ``axis`` (NCCL reduce_scatter analog)."""
    return lax.psum_scatter(x, _normalize(axis_names), scatter_dimension=axis, tiled=True)


def ppermute(x, axis_name: str, perm: Sequence[tuple[int, int]]):
    """Point-to-point ring permutation — the KV-rotation primitive for ring
    attention (reference CP 'alltoall' rotate, accelerator.py:1641-1654)."""
    return lax.ppermute(x, axis_name, perm)


def partial_manual_kwargs(axis_names) -> dict:
    """``jax.shard_map`` kwargs for a region manual over only
    ``axis_names`` with the replication check off."""
    return {"axis_names": set(axis_names), "check_vma": False}


def ring_permute(x, axis_name: str, shift: int = 1):
    """Rotate shards around the ring by ``shift`` (ICI-neighbor traffic)."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int, tiled: bool = True):
    """All-to-all resharding — the Ulysses heads<->sequence swap primitive
    (reference UlyssesSPAttentionHF, accelerator.py:2370-2394)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)


def broadcast_from(x, axis_name: str, src: int = 0):
    """Broadcast the ``src`` shard to all members of the axis.

    One-hot mask + psum: every rank contributes zeros except ``src``, so the
    sum IS the source shard — O(n) wire/memory per rank.  (The previous
    implementation all-gathered the full [devices, ...] stack just to index
    one row: O(n * devices) memory on every rank.)  ``where`` rather than
    multiply-by-mask so non-finite values on non-source ranks cannot poison
    the sum; bools ride as int32 through the reduction.
    """
    n = lax.axis_size(axis_name)  # static int (axis extents are trace-time)
    if isinstance(n, int) and not 0 <= src < n:
        # the old gather-then-index form raised at trace time on a bad src;
        # an unmatched one-hot would instead psum to silent zeros
        raise ValueError(f"broadcast_from src={src} out of range for axis "
                         f"{axis_name!r} of size {n}")
    idx = lax.axis_index(axis_name)
    as_bool = x.dtype == jnp.bool_
    payload = x.astype(jnp.int32) if as_bool else x
    masked = jnp.where(idx == src, payload, jnp.zeros_like(payload))
    out = lax.psum(masked, axis_name)
    return out.astype(jnp.bool_) if as_bool else out
