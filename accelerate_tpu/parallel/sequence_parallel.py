"""Ulysses/ALST sequence parallelism over the ``sp`` mesh axis.

TPU-native re-design of reference P9 (DeepSpeed ``UlyssesSPAttentionHF``
head-scatter all-to-all + ``UlyssesSPDataLoaderAdapter`` sequence sharding,
reference accelerator.py:2370-2409): activations are sharded along the
*sequence* dim everywhere except inside attention, where two ``all_to_all``s
re-shard to the *head* dim so every rank computes full-sequence attention for
its subset of heads — 'two all_to_alls around attention', the natural
``shard_map`` over ICI (SURVEY §2.4 P9).

Requires num_heads % sp == 0 and seq_len % sp == 0.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import axis_size, partial_manual_kwargs


def ulysses_attention_sharded(q, k, v, seg=None, *, axis_name: str = "sp", causal: bool = True,
                              inner_attn: Optional[Callable] = None,
                              heads_sharded: bool = False):
    """shard_map body.  q/k/v local: [B, T/sp, H, D] → out [B, T/sp, H, D].

    all_to_all #1: seq-sharded → head-sharded ([B, T, H/sp, D]);
    full-sequence attention on local heads;
    all_to_all #2: back to seq-sharded.

    GQA runs at kv-head width through the all_to_alls when ``Hkv % sp == 0``
    (head-group alignment is preserved per rank: q heads [r·H/sp, …) map to
    kv heads [r·Hkv/sp, …)).  ``seg`` [B, T/sp] local segment ids are
    all-gathered to the full sequence each rank attends over (packed
    sequences; int16-sized traffic, negligible next to KV).

    ``heads_sharded``: the collective-matmul boundary contract
    (``ops/collective_matmul.ulysses_sp_boundary``) — q/k/v arrive already
    full-sequence head-sharded ([B, T, H/sp, D], the ring all-gather→matmul
    q/k/v projections absorbed all_to_all #1) and the output leaves
    head-sharded (the o_proj ring matmul→reduce-scatter absorbs all_to_all
    #2); ``seg`` then arrives full-sequence too.  Both monolithic
    all_to_alls disappear from this body.
    """
    sp = axis_size(axis_name)

    def seq2head(x):
        # split heads across ranks, concat sequence: [B, T/sp, H, D] -> [B, T, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    if heads_sharded:
        q_h, k_h, v_h = q, k, v
        seg_full = seg
    else:
        q_h, k_h, v_h = seq2head(q), seq2head(k), seq2head(v)
        seg_full = None
        if seg is not None:
            seg_full = lax.all_gather(seg, axis_name, axis=1, tiled=True)  # [B, T]
    if inner_attn is None:
        from ..models.llama import native_attention

        inner_attn = native_attention
    # keyword only when present: custom inner_attn callables without a
    # segment_ids parameter stay compatible
    kwargs = {"segment_ids": seg_full} if seg_full is not None else {}
    out_h = inner_attn(q_h, k_h, v_h, causal=causal, **kwargs)
    return out_h if heads_sharded else head2seq(out_h)


@functools.lru_cache(maxsize=None)
def make_ulysses_attention(mesh: Mesh, axis_name: str = "sp", inner_attn: Optional[Callable] = None):
    """Mesh-bound Ulysses attention on GLOBAL arrays (seq dim sharded over
    ``axis_name``)."""
    if inner_attn is None and mesh.devices.flat[0].platform == "tpu":
        # post-all_to_all attention is plain full-sequence attention over the
        # local heads — the Pallas flash kernel applies directly.  Decided
        # from the mesh's own devices (not the process default backend) so a
        # CPU debug mesh on a TPU-attached host still gets the native path.
        from ..ops.flash_attention import flash_attention

        inner_attn = flash_attention

    # Partial-manual: only sp is manualized — the head dim may itself be
    # tp-sharded outside and keeps that sharding through the all_to_alls
    # (sp splits the LOCAL tp head shard; sp×tp needs H/tp % sp == 0), and a
    # dp-sharded batch is not gathered into the body.  jax 0.9's eager
    # partial-manual validator rejects multi-axis meshes spuriously, so the
    # shard_map runs under a cached jit (inlined under an outer jit).
    @functools.lru_cache(maxsize=None)
    def _build(causal: bool, with_seg: bool, heads_sharded: bool = False):
        # heads_sharded (the collective-matmul sp boundary): q/k/v enter
        # full-sequence with the HEAD dim manual over sp, and leave the same
        # way — the surrounding ring matmuls own the sequence resharding
        spec = (P(None, None, axis_name, None) if heads_sharded
                else P(None, axis_name, None, None))
        seg_spec = P(None, None) if heads_sharded else P(None, axis_name)
        body = functools.partial(ulysses_attention_sharded, axis_name=axis_name, causal=causal,
                                 inner_attn=inner_attn, heads_sharded=heads_sharded)
        in_specs = (spec, spec, spec) + ((seg_spec,) if with_seg else ())
        return jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=spec,
                                 **partial_manual_kwargs({axis_name})))

    def attn(q, k, v, *, causal: bool = True, segment_ids=None, heads_sharded: bool = False):
        h_q, h_kv = q.shape[2], k.shape[2]
        sp = mesh.shape[axis_name]
        if h_kv != h_q and h_kv % sp != 0:
            if heads_sharded:
                raise ValueError(
                    f"heads_sharded ulysses needs kv heads {h_kv} divisible by sp={sp}"
                )
            # kv heads don't split across sp — broadcast to q width (the
            # aligned case keeps kv at Hkv width through the all_to_alls)
            rep = h_q // h_kv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if h_q % sp != 0:
            raise ValueError(f"num_heads {h_q} must be divisible by sp={sp}")
        if segment_ids is None:
            return _build(causal, False, heads_sharded)(q, k, v)
        return _build(causal, True, heads_sharded)(q, k, v, jnp.asarray(segment_ids, jnp.int32))

    return attn


def ulysses_attention(q, k, v, *, causal: bool = True, segment_ids=None,
                      heads_sharded: bool = False):
    """Config-name entry resolving the ambient mesh."""
    from ..state import AcceleratorState

    state = AcceleratorState()
    return make_ulysses_attention(state.mesh)(
        q, k, v, causal=causal, segment_ids=segment_ids, heads_sharded=heads_sharded
    )


# ---------------------------------------------------------------------------
# Sequence-sharding dataloader adapter
# (reference UlyssesSPDataLoaderAdapter accelerator.py:2396-2409)
# ---------------------------------------------------------------------------


def shard_batch_along_sequence(batch, mesh: Mesh, axis_name: str = "sp", seq_axis: int = 1,
                               batch_axes=("dp_replicate", "dp_shard")):
    """Re-spec a global batch so its sequence dim is sharded over sp/cp.

    The loss must then be averaged with the sequence shards in the
    denominator — use ``cross_rank_token_mean`` below (the reference's
    dp_cp loss-averaging dims, parallelism_config.py:146-155)."""
    from jax.sharding import NamedSharding

    def _respec(x):
        if np.ndim(x) <= seq_axis:
            return x
        entries: list = [tuple(a for a in batch_axes if mesh.shape[a] > 1) or None]
        entries += [None] * (np.ndim(x) - 1)
        entries[seq_axis] = axis_name
        return jax.device_put(x, NamedSharding(mesh, P(*entries)))

    return jax.tree_util.tree_map(_respec, batch)


def cross_rank_token_mean(per_token_loss, mask, axis_names):
    """Differentiable cross-rank loss aggregation (reference Ulysses loss
    helper): sum(loss*mask)/sum(mask) with both sums psum'd over the sequence
    (and dp) axes — call inside shard_map or rely on GSPMD reductions."""
    num = jnp.sum(per_token_loss * mask)
    den = jnp.sum(mask)
    num = lax.psum(num, axis_names)
    den = lax.psum(den, axis_names)
    return num / jnp.maximum(den, 1.0)
