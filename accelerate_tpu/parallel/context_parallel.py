"""Context parallelism: ring attention over the ``cp`` mesh axis.

TPU-native re-design of reference CP (P8, accelerator.py:1641-1654 +
maybe_context_parallel :4076-4140): the sequence dimension is sharded over
``cp`` and attention runs blockwise while KV shards rotate around the ring.

Two rotate methods, matching the reference's ``set_rotate_method``:
- ``allgather``: gather all KV once, one local attention (cheap at moderate
  seq, one collective);
- ``alltoall`` (ring): KV streams neighbor-to-neighbor via ``ppermute`` over
  ICI; memory O(T/cp), comm overlapped with compute by XLA's latency-hiding
  scheduler — this is ring attention proper.

Causal masking across shards uses **zigzag load balancing** (reference CP
docs' load-balanced ordering): shard i holds chunks (i, 2cp-1-i) so every
rank does equal causal work.  Helpers ``zigzag_shard``/``zigzag_unshard``
reorder the sequence on the host before sharding.

Numerics: blockwise online-softmax combine across ring steps (same math as
flash attention's running max/denom, applied shard-to-shard in fp32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import axis_size, partial_manual_kwargs

NEG_INF = -1e30


def _block_attend(q, k, v, scores_mask, sm_scale):
    """One (q-shard, kv-shard) block: returns (numerator, denom, max) in fp32.

    q: [B, Tq, H, D]; k/v: [B, Tk, Hkv, D] (GQA broadcast here); scores_mask:
    [Tq, Tk] or [B, Tq, Tk] bool, or None.
    """
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * sm_scale
    if scores_mask is not None:
        if scores_mask.ndim == 2:
            scores_mask = scores_mask[None]
        scores = jnp.where(scores_mask[:, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)  # [B,H,Tq,1]
    # fully-masked rows: exp(NEG_INF - NEG_INF) would be 1 — zero them
    row_valid = m > NEG_INF / 2
    p = jnp.where(row_valid, jnp.exp(scores - m), 0.0)
    num = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v).astype(jnp.float32)
    denom = jnp.sum(p, axis=-1)[..., None].transpose(0, 2, 1, 3)  # [B,Tq,H,1]
    m = m[..., 0].transpose(0, 2, 1)[..., None]  # [B,Tq,H,1]
    return num, denom, m


def _combine(acc, new):
    """Online-softmax combine of two partial attentions."""
    num_a, den_a, m_a = acc
    num_n, den_n, m_n = new
    m = jnp.maximum(m_a, m_n)
    alpha = jnp.exp(m_a - m)
    beta = jnp.exp(m_n - m)
    return (num_a * alpha + num_n * beta, den_a * alpha + den_n * beta, m)


def _chunk_index_map(cp: int):
    """Zigzag layout: rank i holds global chunks (i, 2cp-1-i)."""
    return [(i, 2 * cp - 1 - i) for i in range(cp)]


def zigzag_shard(x, cp: int, axis: int = 1):
    """Reorder a [B, T, ...] array so contiguous per-rank shards carry zigzag
    chunk pairs.  Apply on host before forming the global array."""
    t = x.shape[axis]
    assert t % (2 * cp) == 0, f"seq len {t} must divide 2*cp={2*cp}"
    chunks = np.split(np.asarray(x), 2 * cp, axis=axis)
    order = [c for pair in _chunk_index_map(cp) for c in pair]
    return np.concatenate([chunks[i] for i in order], axis=axis)


def zigzag_unshard(x, cp: int, axis: int = 1):
    t = x.shape[axis]
    chunks = np.split(np.asarray(x), 2 * cp, axis=axis)
    order = [c for pair in _chunk_index_map(cp) for c in pair]
    inverse = np.argsort(order)
    return np.concatenate([chunks[i] for i in inverse], axis=axis)


def _zigzag_positions(t_local: int, t_global: int, cp_rank, cp: int):
    """Global token positions held by ``cp_rank`` under zigzag layout."""
    chunk = t_global // (2 * cp)
    first = cp_rank * chunk
    second = (2 * cp - 1 - cp_rank) * chunk
    return jnp.concatenate([first + jnp.arange(chunk), second + jnp.arange(chunk)])


def _combine_lse(a, b):
    """Combine two (out, lse) partial attentions (out [B,T,H,D], lse
    [B,T,H]) — the flash-kernel-block path; fully differentiable."""
    out_a, lse_a = a
    out_b, lse_b = b
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    den = jnp.maximum(wa + wb, 1e-30)
    out = out_a * (wa / den)[..., None] + out_b * (wb / den)[..., None]
    return out, m + jnp.log(den)


def ring_attention_sharded(
    q, k, v, seg=None, *, axis_name: str = "cp", causal: bool = True,
    sm_scale: Optional[float] = None, rotate_method: str = "alltoall",
    zigzag: bool = True, use_flash: Optional[bool] = None,
):
    """The shard_map body: q/k/v are LOCAL shards [B, T/cp, H, D] / [B, T/cp,
    Hkv, D] (GQA: kv heads stay un-repeated — the flash kernel maps q heads
    to their group's kv head, the XLA path broadcasts per block — so ppermute
    moves only Hkv-sized tensors over ICI).

    With ``alltoall`` KV rotates ``cp`` times around the ring (ppermute);
    with ``allgather`` KV is gathered once and attention is a single local
    block.  Causal masks are built from global zigzag positions.

    ``seg`` [B, T/cp] are local segment ids (packed sequences): the query
    side stays put while the KV side travels with K/V around the ring, and
    cross-segment pairs are masked in-kernel.

    ``use_flash`` (default: on TPU) computes each (q-shard, kv-shard) block
    with the Pallas flash kernel — global zigzag positions feed the kernel's
    position-based causal mask, and blocks combine via the kernel's
    differentiable logsumexp output.  Off-TPU the XLA blockwise path runs.
    """
    cp = axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    t_global = t_local * cp
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    # Zigzag needs 2 chunks per rank; indivisible lengths (e.g. a short
    # model.init trace) cannot have been zigzag_shard-ed by the caller, so
    # they are contiguous — use contiguous positions.
    if t_global % (2 * cp) != 0:
        zigzag = False
    if use_flash is None:
        # fallback when called directly as a shard_map body; make_ring_attention
        # resolves this from the mesh's own devices instead
        from ..ops.flash_attention import _on_tpu

        use_flash = _on_tpu()

    if zigzag and causal:
        q_pos = _zigzag_positions(t_local, t_global, rank, cp)
    else:
        q_pos = rank * t_local + jnp.arange(t_local)

    def pos_for(kv_rank):
        if zigzag and causal:
            return _zigzag_positions(t_local, t_global, kv_rank, cp)
        return kv_rank * t_local + jnp.arange(t_local)

    def mask_for(kv_rank, kv_seg=None):
        """[Tq, Tk] or [B, Tq, Tk] mask for the XLA path (causal ∧ segment)."""
        mask = None
        if causal:
            mask = q_pos[:, None] >= pos_for(kv_rank)[None, :]
        if kv_seg is not None:
            seg_mask = seg[:, :, None] == kv_seg[:, None, :]
            mask = seg_mask if mask is None else mask[None] & seg_mask
        return mask

    if use_flash:
        from ..ops.flash_attention import flash_attention

        pos_q_b = jnp.broadcast_to(q_pos, (b, t_local))

        def attend(kv_pos, k_blk, v_blk, kv_seg=None):
            out, lse = flash_attention(
                q, k_blk, v_blk, causal=causal, sm_scale=sm_scale,
                segment_ids=seg, kv_segment_ids=kv_seg,
                positions=pos_q_b if causal else None,
                kv_positions=jnp.broadcast_to(kv_pos, (b, t_local)) if causal else None,
                return_lse=True,
            )
            return out.astype(jnp.float32), lse

        zero = (
            jnp.zeros((b, t_local, h, d), jnp.float32),
            jnp.full((b, t_local, h), NEG_INF, jnp.float32),
        )
        combine = _combine_lse
    else:
        zero = (
            jnp.zeros((b, t_local, h, d), jnp.float32),
            jnp.zeros((b, t_local, h, 1), jnp.float32),
            jnp.full((b, t_local, h, 1), NEG_INF, jnp.float32),
        )
        combine = _combine

    if rotate_method == "allgather":
        k_all = lax.all_gather(k, axis_name, axis=0, tiled=False)  # [cp, B, T/cp, Hkv, D]
        v_all = lax.all_gather(v, axis_name, axis=0, tiled=False)
        seg_all = lax.all_gather(seg, axis_name, axis=0, tiled=False) if seg is not None else None
        acc = zero
        for kv_rank in range(cp):
            kv_seg = seg_all[kv_rank] if seg is not None else None
            if use_flash:
                part = attend(pos_for(kv_rank), k_all[kv_rank], v_all[kv_rank], kv_seg)
            else:
                part = _block_attend(
                    q, k_all[kv_rank], v_all[kv_rank], mask_for(kv_rank, kv_seg), sm_scale
                )
            acc = combine(acc, part)
    else:
        # ring: step s sees KV originally from rank (rank - s) mod cp
        perm = [(i, (i + 1) % cp) for i in range(cp)]

        def ring_step(s, carry):
            k_cur, v_cur, seg_cur, acc = carry
            kv_rank = (rank - s) % cp
            if use_flash:
                part = attend(pos_for(kv_rank), k_cur, v_cur, seg_cur)
            else:
                mask = None
                if causal:
                    # select the right causal mask for this step's kv source rank
                    mask = jnp.stack([mask_for(r) for r in range(cp)])[kv_rank]
                    if seg_cur is not None:
                        mask = mask[None] & (seg[:, :, None] == seg_cur[:, None, :])
                elif seg_cur is not None:
                    mask = seg[:, :, None] == seg_cur[:, None, :]
                part = _block_attend(q, k_cur, v_cur, mask, sm_scale)
            acc = combine(acc, part)
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            seg_nxt = lax.ppermute(seg_cur, axis_name, perm) if seg_cur is not None else None
            return (k_nxt, v_nxt, seg_nxt, acc)

        carry = (k, v, seg, zero)
        for s in range(cp):  # unrolled: cp is small; lets XLA overlap ppermute+compute
            carry = ring_step(s, carry)
        acc = carry[3]

    if use_flash:
        out, _ = acc
        return out.astype(q.dtype)
    num, den, _ = acc
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def make_ring_attention(mesh: Mesh, axis_name: str = "cp", rotate_method: str = "alltoall",
                        zigzag: bool = True, use_flash: Optional[bool] = None):
    """Build the mesh-bound ring attention usable inside a jitted model.

    Returns ``attn(q, k, v, causal=True, segment_ids=None)`` operating on
    GLOBAL arrays whose sequence dim is sharded over ``axis_name``.
    """
    if use_flash is None:
        # decide from the mesh's own devices, not the process default backend
        # (a CPU debug mesh on a TPU-attached host must take the XLA path)
        use_flash = mesh.devices.flat[0].platform == "tpu"

    # Partial-manual: only the ring axis is manualized; every other mesh
    # axis stays under GSPMD inside the body, so a tp-sharded head dim or a
    # dp-sharded batch dim keeps its sharding through the ring (a
    # full-manual region would all-gather them per step — cp×tp and cp×dp
    # compositions rely on this).  jax 0.9's eager partial-manual validator
    # rejects multi-axis meshes spuriously, so the shard_map runs under a
    # cached jit (inlined when the caller is itself jitted).
    @functools.lru_cache(maxsize=None)
    def _build(causal: bool, with_seg: bool):
        spec = P(None, axis_name, None, None)
        body = functools.partial(
            ring_attention_sharded, axis_name=axis_name, causal=causal,
            rotate_method=rotate_method, zigzag=zigzag, use_flash=use_flash,
        )
        in_specs = (spec, spec, spec) + ((P(None, axis_name),) if with_seg else ())
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=spec,
            **partial_manual_kwargs({axis_name}),
        ))

    def attn(q, k, v, *, causal: bool = True, segment_ids=None):
        if segment_ids is None:
            return _build(causal, False)(q, k, v)
        # NOTE: under zigzag layout the caller shards segment_ids with the
        # same zigzag_shard reorder as the tokens
        # (Accelerator.maybe_context_parallel does this for step buffers)
        # so local ids line up with local tokens.
        return _build(causal, True)(q, k, v, jnp.asarray(segment_ids, jnp.int32))

    return attn


def ring_attention(q, k, v, *, causal: bool = True, segment_ids=None):
    """Config-name entry (models.llama attn_implementation='ring'): resolves
    the mesh from the ambient AcceleratorState."""
    from ..state import AcceleratorState

    state = AcceleratorState()
    cfg = state.parallelism_config
    rotate = "alltoall"
    return make_ring_attention(state.mesh, rotate_method=rotate)(
        q, k, v, causal=causal, segment_ids=segment_ids
    )
