"""Fused flash attention — Pallas TPU kernel.

The MFU-critical op (SURVEY §7 hard parts: '≥45% MFU on v5e requires fused
flash attention').  Blockwise online-softmax attention: K/V stream through
VMEM in (block_k, head_dim) tiles while a (block_q, head_dim) fp32 accumulator
and running (max, denom) stats live in scratch — memory O(T) instead of
O(T²), and every matmul lands on the MXU at 128-aligned tiles.

Causal masking skips fully-masked KV blocks (upper-triangular blocks cost
zero compute — the grid still visits them but predication makes them free).

Backward: ONE fused Pallas kernel, recompute-based — the forward saves
(q, k, v, out, logsumexp); the backward rebuilds each probability tile from
(q, k, lse) once and feeds dq, dk and dv from it (five matmuls and one exp a
tile), so the [T, T] tensors of the naive backward never touch HBM.  dq sums
over kv and dk/dv over q: a kv head's K, V and f32 dk/dv accumulators stay
in VMEM for the whole sequence while the kernel walks the kv sub-blocks of
each q block itself, so neither sum needs atomics or a second pass.

On a TPU backend every kernel compiles (or the run fails); on the CPU
backend the same kernels run in Pallas interpret mode so the tests run on
the CPU mesh.  ``interpret=`` overrides the choice for tests.
reference parity: the engines' flash kernels (torch sdpa/TE fused attn) the
reference delegates to (SURVEY §2.4 P8 note — 'blockwise = flash-attention
Pallas kernel tiling').
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _on_tpu() -> bool:
    """Whether kernels compile for a TPU (else: interpret mode, the CPU
    tests' path).  A failed device probe — e.g. a chip held by another
    process — raises; it never reads as "not a TPU"."""
    return jax.default_backend() == "tpu"


# VMEM budget the block-size heuristic designs against: ~16 MiB/core on
# v4/v5e-class chips, minus headroom for double-buffered input tiles and the
# compiler's own scratch.
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def default_block_sizes(t: int, s: int, d: int, backward: bool = False) -> tuple[int, int]:
    """Heuristic (block_q, block_k) of one pass, keyed on sequence lengths and
    head dim.  The forward and the backward are different kernels and take
    their tiles apart.

    Forward: start from the sweet spot measured at seq 2048-8192 /
    head_dim≤128 on v5e ((1024, 1024) — the autotune sweep at those shapes,
    worth ~1.5% end-to-end over (512, 1024) on the headline bench); clamp to
    the actual sequence lengths rounded up to the MXU tile (128); then shrink
    while the fp32 working set (q/k/v tiles + scores tile + accumulator)
    exceeds the VMEM budget — at large head_dim the 1024-tiles no longer
    double-buffer.

    Backward (the one-pass kernel, which holds a kv head's whole K/V and
    walks ``block_k`` sub-blocks of it per ``block_q`` rows): (512, 512),
    clamped to the lengths alike.  Kernel-only sweep on a v5e at the train
    cells' per-chip shapes [2, 4096, 32/8, 128] and [2, 4096, 28/4, 128], ms
    a layer: (512, 512) 4.82 / 4.20; (1024, 1024) 5.00 / 4.37; (1024, 512)
    5.08 / 4.44; (512, 1024) 5.11 / 4.47; (512, 256) 5.15 / 4.47; (256, 512)
    5.21 / 4.54; (256, 256) 6.63 / 5.78; (128, 512) 7.17 / 6.24 (PERF.md
    section 6, PR 35).  Its VMEM is planned and stated to the compiler
    (:func:`_bwd_vmem_plan`), so no budget shrinks these tiles.
    """
    round_up = lambda x: max(128, -(-x // 128) * 128)
    if backward:
        return min(512, round_up(t)), min(512, round_up(s))
    block_q = min(1024, round_up(t))
    block_k = min(1024, round_up(s))
    if round_up(t) >= 32768 or d >= 128:
        # Measured with a backward that shared these tiles (its (1024, 1024)
        # tile overran the Mosaic scoped-VMEM stack limit at long sequence
        # or head_dim >= 128; at 16k/d<128 the 1024 tile was ~6% faster
        # end-to-end, so the clamp stays off there).  The backward has its
        # own tiles now; the forward keeps (512, 1024) at these shapes until
        # it is swept alone (ROADMAP A7).
        block_q = min(block_q, 512)

    def working_set(bq, bk):
        # q, k, v, out-acc tiles in fp32 + the [bq, bk] scores/probs tile
        return 4 * (bq * d + 2 * bk * d + bq * d + bq * bk)

    while working_set(block_q, block_k) > _VMEM_BUDGET_BYTES and block_k > 128:
        block_k //= 2
    while working_set(block_q, block_k) > _VMEM_BUDGET_BYTES and block_q > 128:
        block_q //= 2
    return block_q, block_k


def _tiles(block_q, block_k, t: int, s: int, d: int, backward: bool = False):
    """A pass's (block_q, block_k): the caller's pins, else the pass's own
    default, clamped to the sequence lengths."""
    bq, bk = default_block_sizes(t, s, d, backward)
    return min(block_q or bq, t), min(block_k or bk, s)


def autotune_block_sizes(
    b: int, t: int, h: int, d: int, hkv: Optional[int] = None, *,
    dtype=jnp.bfloat16, causal: bool = True, backward: bool = False,
    candidates=None, iters: int = 3,
) -> tuple[int, int]:
    """Measure the best (block_q, block_k) of ONE pass for a shape on the
    current device.

    The forward and the backward are different kernels with tiles of their
    own, so each is swept apart: ``backward=False`` times the forward kernel
    alone, ``backward=True`` the backward kernel alone on a forward's saved
    (out, lse).  Results are cached per (shape, pass, device kind) for the
    process.  Meant for offline tuning (bench setup), not the hot path —
    each candidate pays a compile.
    """
    key = (b, t, h, d, hkv, str(dtype), causal, backward,
           getattr(jax.devices()[0], "device_kind", "cpu"))
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    import time

    hkv = hkv or h
    rng = np.random.default_rng(0)
    mk = lambda heads: jnp.asarray(rng.normal(size=(b * heads, t, d)), dtype)
    none = jnp.zeros((b, 1, t), jnp.int32)
    static = (causal, 1.0 / float(np.sqrt(d)))
    flags = (False, False, not _on_tpu())  # segmented, positioned, interpret
    args = (mk(h), mk(hkv), mk(hkv))
    if backward:  # + the forward's saved (out, lse) and a cotangent
        args += (*_flash_fwd(*args, none, none, none, none, *static, None, None, *flags), mk(h))
    if candidates is None:
        base_q, base_k = default_block_sizes(t, t, d, backward)
        candidates = {
            (base_q, base_k), (max(base_q // 2, 128), base_k), (base_q, max(base_k // 2, 128)),
            (min(1024, base_q * 2), base_k), (base_q, min(1024, base_k * 2)), (256, 256),
        }
        # keep MXU-aligned tiles; the kernels clamp to t internally, so
        # oversized candidates just duplicate the largest feasible tiling
        candidates = {(bq, bk) for bq, bk in candidates if bq % 128 == 0 and bk % 128 == 0}
    best, best_dt = None, float("inf")
    for bq, bk in sorted(candidates):
        def one_pass(q, k, v, *saved, bq=bq, bk=bk):
            if backward:
                return _flash_bwd(q, k, v, none, none, none, none, *saved, None,
                                  *static, bq, bk, *flags)
            return _flash_fwd(q, k, v, none, none, none, none, *static, bq, bk, *flags)

        # graft-lint: disable=GL306 -- autotuner: one jit per (bq, bk) candidate is the point; each tiling is a distinct program, compiled and measured exactly once
        f = jax.jit(one_pass)
        try:
            jax.block_until_ready(f(*args))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                res = f(*args)
            jax.block_until_ready(res)
            dt = time.perf_counter() - t0
        except Exception:  # a sweep may skip a tiling the compiler refuses (VMEM)
            continue
        if dt < best_dt:
            best, best_dt = (bq, bk), dt
    if best is None:
        raise RuntimeError(
            f"autotune_block_sizes: every candidate tiling failed for {key}"
        )
    _AUTOTUNE_CACHE[key] = best
    return best


_AUTOTUNE_CACHE: dict = {}


def _zero_oob_rows(x, start: int, limit: int):
    """Zero-fill tile rows past ``limit`` — padded rows of a non-divisible
    last block read garbage (NaN in interpret mode), and 0 * NaN = NaN would
    leak through the accumulating dots even at zero probability."""
    rows = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < limit, x, jnp.zeros_like(x))


def _masked_scores(q, k, sm_scale, q_start, k_start, t_len, s_len, causal,
                   block_q, block_k, seg_q=None, seg_k=None, pos_q=None, pos_k=None):
    """Scaled q@kᵀ tile with causal + segment + out-of-bounds masking.

    The forward's tile (the backward builds the same masks kv-major, and
    only those a sub-block needs: :func:`_bwd_kernel`).  Returns (scores,
    valid): padded rows/cols of the last (non-divisible) blocks,
    cross-segment pairs (packed sequences), and causally-forbidden entries
    get DEFAULT_MASK_VALUE; ``valid`` is the boolean tile.

    With ``pos_q/pos_k`` (explicit global token positions — the ring-CP
    zigzag layout), the causal comparison uses positions instead of local
    tile indices.
    """
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [block_q, block_k]
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = (rows < t_len) & (cols < s_len)
    if causal:
        if pos_q is not None:
            valid = valid & (pos_q[:, None] >= pos_k[None, :])
        else:
            valid = valid & (rows >= cols)
    if seg_q is not None:
        valid = valid & (seg_q[:, None] == seg_k[None, :])
    return jnp.where(valid, scores, DEFAULT_MASK_VALUE), valid


def _attn_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_kv_ref, pos_q_ref, pos_kv_ref, o_ref, lse_ref, m_scratch, l_scratch, acc_scratch, *, causal, sm_scale, block_q, block_k, t_len, s_len, segmented, positioned):
    """Grid: (batch*heads, q_blocks, kv_blocks); kv dim is innermost/serial."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal: skip blocks entirely above the diagonal (with explicit
    # positions the diagonal is data-dependent, so no block skipping)
    should_compute = (not causal) or positioned or (q_start + block_q - 1 >= k_start)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = _zero_oob_rows(k_ref[0], k_start, s_len)  # [block_k, d]
        v = _zero_oob_rows(v_ref[0], k_start, s_len)
        seg_q = seg_q_ref[0, 0] if segmented else None
        seg_k = seg_kv_ref[0, 0] if segmented else None
        pos_q = pos_q_ref[0, 0] if positioned else None
        pos_k = pos_kv_ref[0, 0] if positioned else None
        scores, _ = _masked_scores(
            q, k, sm_scale, q_start, k_start, t_len, s_len, causal, block_q, block_k,
            seg_q, seg_k, pos_q, pos_k,
        )

        m_prev = m_scratch[:]  # [block_q, 1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scratch[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scratch[:] + jnp.log(safe_l))[:, 0]


def _flash_fwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, causal: bool, sm_scale: float,
               block_q: int, block_k: int, segmented: bool, positioned: bool,
               interpret: bool):
    """q: [B*H, T, D]; k/v: [B*Hkv, S, D] (GQA: no head repeat — the kv
    BlockSpec maps each q head to its group's kv head); seg/pos:
    [B, 1, T]/[B, 1, S] int32.  Returns (out [B*H, T, D], lse [B*H, T])."""
    bh, t, d = q.shape
    s = k.shape[1]
    n_batch = seg_q.shape[0]
    n_heads = bh // n_batch
    n_rep = bh // k.shape[0]
    block_q, block_k = _tiles(block_q, block_k, t, s, d)
    grid = (bh, pl.cdiv(t, block_q), pl.cdiv(s, block_k))

    kernel = functools.partial(
        _attn_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        t_len=t, s_len=s, segmented=segmented, positioned=positioned,
    )
    scratch_shapes = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )

    def kv_map(b, i, j):  # q head b -> its GQA group's kv head
        return (b // n_rep, j, 0)

    row_q = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b // n_heads, 0, i))
    row_kv = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // n_heads, 0, j))
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            row_q, row_kv, row_q, row_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse carried as [BH, 1, T] so the block's last two dims meet
            # the (8, 128) tiling rule: (1, block_q) with 1 == array dim
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=scratch_shapes,
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, seg_q, seg_kv, pos_q, pos_kv)
    return out, lse[:, 0, :]


def _both(a, b):
    """``a & b`` where either mask may be absent (None = everything valid)."""
    return b if a is None else a if b is None else a & b


def _bwd_kernel(*refs, causal, sm_scale, block_q, block_k, t_len, s_len, chunk_blocks,
                q_blocks, segmented, positioned):
    """Grid: (batch*kv_heads, kv_chunks, group*q_blocks); innermost/serial dim
    walks every (GQA group member, q block) pair of the kv head.

    K and V of the kv head (``chunk_blocks`` sub-blocks of ``block_k`` rows;
    the whole sequence unless :func:`_bwd_vmem_plan` had to cut it) stay in
    VMEM with their f32 dk/dv accumulators; one grid step walks the kv
    sub-blocks its q block can see and, per sub-block, rebuilds the
    probability tile ONCE — kv-major, ``[block_k, block_q]``, so lse/delta
    broadcast along sublanes as they lie and four of the five matmuls need
    no transpose: s = k qᵀ, dp = v gᵀ, dv += p g, dk += ds q, dq += dsᵀ k.
    dq sums over the walk in f32 and is cast once; dk/dv sum over the q
    blocks and the group's q heads and are written when the kv head is done.

    Causal (by index): the walk ends at the diagonal, exact to ``block_k``,
    and only the sub-blocks the diagonal crosses (the last of the walk) build
    the causal mask.  ``positioned``: the diagonal is data-dependent, every
    sub-block is walked and masked by position.  Bounds masks exist only
    where a length is not a multiple of its block; p is hard-zeroed there
    (padded rows read garbage lse/delta, so masked scores are not enough).
    """
    q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, *refs = refs
    seg_q_ref = seg_kv_ref = pos_q_ref = pos_kv_ref = None
    if segmented:
        seg_q_ref, seg_kv_ref, *refs = refs
    if positioned:
        pos_q_ref, pos_kv_ref, *refs = refs
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    ci = pl.program_id(1)
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = (gi % q_blocks) * block_q
    chunk_start = ci * (chunk_blocks * block_k)
    ragged_q = t_len % block_q != 0
    ragged_k = s_len % block_k != 0
    ragged = ragged_q or ragged_k
    tile = (block_k, block_q)

    q = q_ref[0]
    g = g_ref[0]
    lse = lse_ref[0]      # [1, block_q]
    delta = delta_ref[0]  # [1, block_q]
    if ragged_q:
        q = _zero_oob_rows(q, q_start, t_len)
        g = _zero_oob_rows(g, q_start, t_len)
        # 0 * garbage = NaN would leak through ds where p is zeroed
        cols = q_start + jax.lax.broadcasted_iota(jnp.int32, delta.shape, 1)
        delta = jnp.where(cols < t_len, delta, 0.0)
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def sub_block(diagonal):
        def body(j, carry):
            off = pl.multiple_of(j * block_k, block_k)
            k_start = chunk_start + off
            k = k_ref[0, pl.ds(off, block_k), :]
            v = v_ref[0, pl.ds(off, block_k), :]
            if ragged_k:
                k = _zero_oob_rows(k, k_start, s_len)
                v = _zero_oob_rows(v, k_start, s_len)
            s = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * sm_scale  # [block_k, block_q]
            valid = None
            by_index = diagonal and not positioned
            if ragged or by_index:
                kv_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, tile, 0)
                q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, tile, 1)
                if ragged:
                    valid = (q_idx < t_len) & (kv_idx < s_len)
                if by_index:
                    valid = _both(valid, q_idx >= kv_idx)
            if diagonal and positioned:
                pos_k = pos_kv_ref[0, 0, pl.ds(off, block_k)]
                valid = _both(valid, pos_q_ref[0] >= pos_k[:, None])
            if segmented:
                seg_k = seg_kv_ref[0, 0, pl.ds(off, block_k)]
                valid = _both(valid, seg_q_ref[0] == seg_k[:, None])
            p = jnp.exp(s - lse)
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            dp = jax.lax.dot_general(
                v, g, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
            rows = pl.ds(off, block_k)
            dv_acc[rows, :] += jax.lax.dot_general(
                p.astype(q.dtype), g, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc[rows, :] += jax.lax.dot_general(
                ds, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            dq_acc[:] += jax.lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            return carry

        return body

    # sub-blocks of this chunk that hold real kv rows; of those, the ones
    # wholly at or below the diagonal (no causal mask) come first
    n_kv = jnp.minimum(chunk_blocks, pl.cdiv(s_len, block_k) - ci * chunk_blocks)
    n_free = n_kv
    if causal:
        n_free = 0
        if not positioned:
            rel = q_start - chunk_start
            n_kv = jnp.minimum(n_kv, (jnp.maximum(rel + block_q, 0) + block_k - 1) // block_k)
            n_free = jnp.minimum(jnp.maximum(rel, 0) // block_k, n_kv)
    jax.lax.fori_loop(0, n_free, sub_block(diagonal=False), None)
    if causal:
        jax.lax.fori_loop(n_free, n_kv, sub_block(diagonal=True), None)
    dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(gi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# What the backward may ask of a core's VMEM: a v5e / v6e core has 128 MiB
# (the 16 MiB that `_VMEM_BUDGET_BYTES` designs against is Mosaic's DEFAULT
# scoped limit, which `vmem_limit_bytes` raises); the rest is left to Mosaic's
# own scratch and to what XLA keeps around the call.
_BWD_VMEM_BYTES = 88 * 1024 * 1024


def _bwd_vmem_plan(kv_blocks, block_q, block_k, d, itemsize):
    """(kv sub-blocks resident at once, buffers per resident array, bytes).

    Resident per kv row: K, V in and dk, dv out (``buffers`` each, in the
    operands' dtype) and two f32 accumulators; per grid step: q and g
    double-buffered, dq out (f32 at worst) double-buffered, the f32 dq
    accumulator, and eight live f32 ``[block_k, block_q]`` tiles (scores,
    probabilities, dp, ds, their casts and masks).  The whole sequence
    double-buffered where that fits, single-buffered where only that fits
    (the next kv head's 2 x S x D then loads behind the last step, not under
    it), else the kv sequence in equal chunks, each with its own dq partial.
    At D 128 in bf16: two buffers to 16k, one to 32k, chunks beyond.
    """
    lanes = max(d, 128)  # VMEM pads the minor dim to a lane tile
    step = block_q * lanes * (4 * itemsize + 12) + 8 * block_q * block_k * 4

    def need(blocks, buffers):
        return blocks * block_k * lanes * (4 * buffers * itemsize + 8) + step

    for buffers in (2, 1):
        if need(kv_blocks, buffers) <= _BWD_VMEM_BYTES:
            return kv_blocks, buffers, need(kv_blocks, buffers)
    fit = max(1, (_BWD_VMEM_BYTES - step) // (need(1, 1) - step))
    blocks = pl.cdiv(kv_blocks, pl.cdiv(kv_blocks, fit))
    return blocks, 1, need(blocks, 1)


def _flash_bwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, out, lse, g, g_lse, causal,
               sm_scale, block_q, block_k, segmented, positioned, interpret):
    """One-pass blockwise backward: dq [B*H, T, D], dk/dv [B*Hkv, S, D] from
    ONE kernel (:func:`_bwd_kernel`; its ``pallas_call`` keeps the name
    ``flash_bwd_dkv`` that the trace readers look for).

    ``g_lse`` is the cotangent of the lse output (nonzero when callers
    combine partial attentions by logsumexp — ring CP): its score-gradient
    contribution is ``p * g_lse``, which folds into the existing
    ``ds = p * (dp - delta)`` as ``delta - g_lse``.
    """
    bh, t, d = q.shape
    bhkv, s_len, _ = k.shape
    n_batch = seg_q.shape[0]
    n_heads = bh // n_batch
    hkv = bhkv // n_batch  # kv heads per batch element
    n_rep = bh // bhkv
    block_q, block_k = _tiles(block_q, block_k, t, s_len, d, backward=True)
    q_blocks = pl.cdiv(t, block_q)
    kv_blocks = pl.cdiv(s_len, block_k)
    positioned = positioned and causal
    chunk_blocks, buffers, vmem_bytes = _bwd_vmem_plan(
        kv_blocks, block_q, block_k, d, q.dtype.itemsize)
    chunk = chunk_blocks * block_k
    kv_chunks = pl.cdiv(kv_blocks, chunk_blocks)

    # delta_i = g_i . out_i — one cheap fused XLA pass, carried as [BH, 1, T]
    # (same tiling-friendly layout as lse)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = delta[:, None, :]
    lse3 = lse[:, None, :]

    def q_head(b, i):  # kv head b, serial step i -> the group member's q-head row
        return (b // hkv) * n_heads + (b % hkv) * n_rep + i // q_blocks

    resident = pl.Buffered(buffers)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, c, i: (q_head(b, i), i % q_blocks, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, c, i: (q_head(b, i), 0, i % q_blocks))
    kspec = pl.BlockSpec((1, chunk, d), lambda b, c, i: (b, c, 0), pipeline_mode=resident)
    batch_q = pl.BlockSpec((1, 1, block_q), lambda b, c, i: (b // hkv, 0, i % q_blocks))
    batch_kv = pl.BlockSpec((1, 1, chunk), lambda b, c, i: (b // hkv, 0, c))
    operands = [q, g, lse3, delta, k, v]
    in_specs = [qspec, qspec, rowspec, rowspec, kspec, kspec]
    if segmented:
        operands += [seg_q, seg_kv]
        in_specs += [batch_q, batch_kv]
    if positioned:
        operands += [pos_q, pos_kv]
        in_specs += [batch_q, batch_kv]
    # one dq partial per kv chunk: with the whole sequence resident (every
    # shape up to 32k x 128 in bf16) that is dq itself, cast in the kernel
    dq_dtype = q.dtype if kv_chunks == 1 else jnp.float32
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            t_len=t, s_len=s_len, chunk_blocks=chunk_blocks, q_blocks=q_blocks,
            segmented=segmented, positioned=positioned,
        ),
        name="flash_bwd_dkv",
        grid=(bhkv, kv_chunks, n_rep * q_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, c, i: (c, q_head(b, i), i % q_blocks, 0)),
            kspec, kspec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kv_chunks, bh, t, d), dq_dtype),
            jax.ShapeDtypeStruct((bhkv, s_len, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, s_len, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((chunk, d), jnp.float32),
            pltpu.VMEM((chunk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes + vmem_bytes // 4 + (4 << 20),
        ),
        interpret=interpret,
    )(*operands)
    dq = dq[0] if kv_chunks == 1 else jnp.sum(dq, axis=0).astype(q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, seg_q, seg_kv, pos_q, pos_kv, causal, sm_scale, block_q,
           block_k, segmented, positioned, interpret):
    """(out, lse) with a fully differentiable lse — ring CP's logsumexp
    combine backpropagates through both outputs."""
    return _flash_fwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, causal, sm_scale,
                      block_q, block_k, segmented, positioned, interpret)


def _flash_vjp_fwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, causal, sm_scale,
                   block_q, block_k, segmented, positioned, interpret):
    out, lse = _flash_fwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, causal, sm_scale,
                          block_q, block_k, segmented, positioned, interpret)
    return (out, lse), (q, k, v, seg_q, seg_kv, pos_q, pos_kv, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, segmented, positioned,
                   interpret, res, gbar):
    q, k, v, seg_q, seg_kv, pos_q, pos_kv, out, lse = res
    g, g_lse = gbar
    dq, dk, dv = _flash_bwd(
        q, k, v, seg_q, seg_kv, pos_q, pos_kv, out, lse, g, g_lse, causal,
        sm_scale, block_q, block_k, segmented, positioned, interpret,
    )
    zero_int = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dq, dk, dv, zero_int(seg_q), zero_int(seg_kv), zero_int(pos_q), zero_int(pos_kv))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Paged decode attention (the serving core's ragged kernel)
# ---------------------------------------------------------------------------


def _paged_tile_update(scores, v, row_pos, kv_start, m_scratch, l_scratch,
                       acc_scratch):
    """One online-softmax update shared by every paged kernel: mask the
    page's kv indices against per-row positions, rescale the running
    max/sum/accumulator.  ``scores``: [rows, page_size] f32 (pre-scaled);
    ``v``: [page_size, D] f32; ``row_pos``: [rows, 1] int32."""
    idx = kv_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(idx <= row_pos, scores, DEFAULT_MASK_VALUE)
    m_prev = m_scratch[:]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scratch[:] = alpha * l_scratch[:] + jnp.sum(p, axis=1, keepdims=True)
    acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_scratch[:] = m_new


def _paged_finalize(o_ref, l_scratch, acc_scratch):
    l = l_scratch[:]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scratch[:] / safe_l).astype(o_ref.dtype)


def _dequant_tile(tile_ref, scale_ref, kv_qmax):
    """In-kernel page dequant: codes stream HBM->VMEM at one byte per
    element and widen in-tile (``codes * amax / QMAX``) — the full-width
    page never exists in HBM."""
    t = tile_ref[0, 0].astype(jnp.float32)
    if scale_ref is not None:
        t = t * (scale_ref[0, 0] / kv_qmax)  # [1, 1] block broadcasts
    return t


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scratch, l_scratch, acc_scratch,
                         *, page_size, sm_scale):
    """Grid: (slots, kv_heads, pages_per_slot); pages innermost/serial.

    Each program attends one slot's GQA group of queries against ONE of its
    KV pages, located through the scalar-prefetched block table (the
    BlockSpec index_map already routed the right physical page into VMEM —
    this body only sees a contiguous ``[page_size, D]`` tile).  Online
    softmax accumulates across pages exactly like the dense flash kernel."""
    _paged_decode_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, None, None,
                       o_ref, m_scratch, l_scratch, acc_scratch,
                       page_size=page_size, sm_scale=sm_scale, kv_qmax=None)


def _paged_decode_kernel_quant(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                               vs_ref, o_ref, m_scratch, l_scratch,
                               acc_scratch, *, page_size, sm_scale, kv_qmax):
    """Quantized-page variant: the per-(kv-head, page) scale rides as its
    own scalar-sized block (same block-table index map as the page) and the
    codes dequantize in-tile."""
    _paged_decode_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                       o_ref, m_scratch, l_scratch, acc_scratch,
                       page_size=page_size, sm_scale=sm_scale, kv_qmax=kv_qmax)


def _paged_decode_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                       o_ref, m_scratch, l_scratch, acc_scratch,
                       *, page_size, sm_scale, kv_qmax):
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    pos = pos_ref[s]
    kv_start = j * page_size

    @pl.when(kv_start <= pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [group, D]
        k = _dequant_tile(k_ref, ks_ref, kv_qmax)  # [page_size, D]
        v = _dequant_tile(v_ref, vs_ref, kv_qmax)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [group, page_size]
        _paged_tile_update(scores, v, pos, kv_start, m_scratch, l_scratch,
                           acc_scratch)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _paged_finalize(o_ref, l_scratch, acc_scratch)


# Max code magnitude per quantized page dtype (mirrors
# ops/paged_cache.py:KV_QUANT_QMAX): symmetric int8 uses the full [-127, 127]
# band; fp8 pages store e4m3 codes whose saturation point is 448.
_KV_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0}


def _kv_qmax_for(pages) -> float:
    name = jnp.dtype(pages.dtype).name
    if name not in _KV_QMAX:
        raise ValueError(
            f"quantized KV pages must be int8 or float8_e4m3fn, got {name}"
        )
    return _KV_QMAX[name]


def _page_specs(page_size, d, n, quantized):
    """K/V page BlockSpecs (+ per-page scale specs when quantized), all
    routed through the scalar-prefetched block table.  Scales ride as a
    ``[Hkv, P, 1, 1]`` view (:func:`_scale_view`) so the one-element block's
    last two dims equal the array's — the TPU lowering's block-shape rule."""
    page = lambda s, h, j, bt, *_: (h, bt[s * n + j], 0, 0)
    specs = [pl.BlockSpec((1, 1, page_size, d), page)] * 2
    if quantized:
        specs += [pl.BlockSpec((1, 1, 1, 1), page)] * 2
    return specs


def _scale_view(scales):
    """``[Hkv, P]`` per-page amax -> the ``[Hkv, P, 1, 1]`` f32 kernel operand."""
    return scales.astype(jnp.float32)[:, :, None, None]


def paged_decode_attention(
    q,
    k_pages,
    v_pages,
    block_tables,
    positions,
    *,
    k_scales=None,
    v_scales=None,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Ragged single-token decode attention over a paged KV pool.

    The serving core's hot op (``accelerate_tpu/serving/``): every decode
    slot attends its own sequence, whose K/V live scattered across
    fixed-size pages located by a block table — no dense per-sequence cache
    strip, no gather materialization.  The block table and per-slot
    positions ride as **scalar-prefetch** operands, so each grid step's
    BlockSpec index_map DMAs exactly the one physical page the slot needs.

    q: ``[S, H, D]`` (one token per slot); k_pages/v_pages:
    ``[Hkv, P, page_size, D]``; block_tables: ``[S, n]`` int32; positions:
    ``[S]`` int32 — the token's position, kv indices ``0..position`` are
    live (dead slots simply mask everything and return zeros).  GQA runs
    without repeating K/V, like :func:`flash_attention`.  Returns
    ``[S, H, D]``.

    **Quantized pages** (``ops/paged_cache.py`` int8/fp8 pools): pass
    the per-(kv-head, page) amax arrays ``k_scales``/``v_scales``
    (``[Hkv, P]`` f32).  Each page's scale rides as its own block through
    the same block-table index map and the codes dequantize in-tile
    (``codes * amax / QMAX``) — decode reads half the KV bytes of bf16 and
    the full-width page never exists in HBM.

    Multi-token windows (speculative verify's ``[S, k+1]``, chunked
    prefill) go through :func:`paged_multitoken_attention` — same grid
    family, ``k+1``-wide query tile.
    """
    s_slots, h, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    if h % hkv != 0:
        raise ValueError(f"num q heads {h} not divisible by kv heads {hkv}")
    group = h // hkv
    n = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = not _on_tpu()
    quantized = k_scales is not None

    qg = q.reshape(s_slots, hkv, group, d)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, hkv, n),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda s, h, j, bt, p: (s, h, 0, 0)),
            *_page_specs(page_size, d, n, quantized),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), lambda s, h, j, bt, p: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    if quantized:
        kernel = functools.partial(
            _paged_decode_kernel_quant, page_size=page_size,
            sm_scale=sm_scale, kv_qmax=_kv_qmax_for(k_pages),
        )
        operands = (bt_flat, pos, qg, k_pages, v_pages,
                    _scale_view(k_scales), _scale_view(v_scales))
    else:
        kernel = functools.partial(
            _paged_decode_kernel, page_size=page_size, sm_scale=sm_scale
        )
        operands = (bt_flat, pos, qg, k_pages, v_pages)
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(s_slots, h, d)


def _paged_multitoken_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                           vs_ref, o_ref, m_scratch, l_scratch, acc_scratch,
                           *, page_size, sm_scale, group, width, kv_qmax):
    """Grid: (slots, kv_heads, pages_per_slot).  The query tile is the
    slot's whole ``[width * group, D]`` window (``width`` contiguous
    tokens x the GQA group, token-major rows); each row masks kv indices
    against its own live position ``pos0 + row // group``."""
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    pos0 = pos_ref[s]
    kv_start = j * page_size

    # pages past the window's LAST row are dead for every row; pages in
    # between are handled by the per-row mask below
    @pl.when(kv_start <= pos0 + width - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # [width*group, D]
        k = _dequant_tile(k_ref, ks_ref, kv_qmax)  # [page_size, D]
        v = _dequant_tile(v_ref, vs_ref, kv_qmax)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [width*group, page_size]
        rows = width * group
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        _paged_tile_update(scores, v, pos0 + lane, kv_start, m_scratch,
                           l_scratch, acc_scratch)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _paged_finalize(o_ref, l_scratch, acc_scratch)


def _paged_multitoken_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                             m_scratch, l_scratch, acc_scratch,
                             *, page_size, sm_scale, group, width):
    _paged_multitoken_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, None, None,
                           o_ref, m_scratch, l_scratch, acc_scratch,
                           page_size=page_size, sm_scale=sm_scale,
                           group=group, width=width, kv_qmax=None)


def _paged_multitoken_kernel_quant(bt_ref, pos_ref, q_ref, k_ref, v_ref,
                                   ks_ref, vs_ref, o_ref, m_scratch,
                                   l_scratch, acc_scratch,
                                   *, page_size, sm_scale, group, width,
                                   kv_qmax):
    _paged_multitoken_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                           vs_ref, o_ref, m_scratch, l_scratch, acc_scratch,
                           page_size=page_size, sm_scale=sm_scale,
                           group=group, width=width, kv_qmax=kv_qmax)


def paged_multitoken_attention(
    q,
    k_pages,
    v_pages,
    block_tables,
    positions,
    *,
    k_scales=None,
    v_scales=None,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Multi-token paged attention: the Pallas verify/chunked-prefill kernel.

    Same block-tables-as-scalar-prefetch grid as
    :func:`paged_decode_attention`, with a ``T``-token query tile per slot:
    the speculative verify window (``T = k+1`` — draft + bonus token) and
    fixed-chunk prefill both attend ``T`` contiguous tokens per slot
    against that slot's paged K/V.  The query tile is ``[T * group, D]``
    (token-major rows); each row causal-masks against its own position
    ``positions[s, 0] + token_index``, and whole pages beyond the window's
    last row are skipped by predication, so at small ``T`` the op stays
    HBM-bound on the same page reads as decode.

    q: ``[S, T, H, D]``; positions: ``[S, T]`` int32 — **contiguous per
    row** (``positions[s, i] == positions[s, 0] + i``), which both the
    verify and prefill callers guarantee by construction; only column 0 is
    read.  Quantized pools pass ``k_scales``/``v_scales`` ``[Hkv, P]``
    exactly as in decode.  Returns ``[S, T, H, D]``.
    """
    s_slots, width, h, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    if h % hkv != 0:
        raise ValueError(f"num q heads {h} not divisible by kv heads {hkv}")
    group = h // hkv
    n = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = not _on_tpu()
    quantized = k_scales is not None

    # [S, T, Hkv, group, D] -> [S, Hkv, T*group, D]: token-major rows so
    # row // group recovers the token lane in-kernel
    qg = (
        q.reshape(s_slots, width, hkv, group, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(s_slots, hkv, width * group, d)
    )
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    pos0 = positions[:, 0].astype(jnp.int32)
    rows = width * group

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots, hkv, n),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda s, h, j, bt, p: (s, h, 0, 0)),
            *_page_specs(page_size, d, n, quantized),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), lambda s, h, j, bt, p: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    if quantized:
        kernel = functools.partial(
            _paged_multitoken_kernel_quant, page_size=page_size,
            sm_scale=sm_scale, group=group, width=width,
            kv_qmax=_kv_qmax_for(k_pages),
        )
        operands = (bt_flat, pos0, qg, k_pages, v_pages,
                    _scale_view(k_scales), _scale_view(v_scales))
    else:
        kernel = functools.partial(
            _paged_multitoken_kernel, page_size=page_size,
            sm_scale=sm_scale, group=group, width=width,
        )
        operands = (bt_flat, pos0, qg, k_pages, v_pages)
    out = pl.pallas_call(
        kernel,
        name="paged_multitoken",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return (
        out.reshape(s_slots, hkv, width, group, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(s_slots, width, h, d)
    )


def _fused_bgmv_decode_body(bt_ref, pos_ref, ids_ref, q_ref, x_ref, a_ref,
                            b_ref, cos_ref, sin_ref, k_ref, v_ref, ks_ref,
                            vs_ref, o_ref, q_scratch, m_scratch, l_scratch,
                            acc_scratch, *, page_size, sm_scale, group,
                            kv_qmax):
    """Grid: (slots, kv_heads, pages_per_slot).  At ``j == 0`` the slot's
    LoRA query delta for THIS kv-head's group — ``(x @ A[ids]) @ B[ids]``,
    roped in-kernel at the slot's position — lands in ``q_scratch`` on top
    of the pre-roped base query; the page loop then attends out of scratch.
    Rope is linear, so ``rope(base + delta) == rope(base) + rope(delta)``
    and adding the in-kernel-roped delta to the already-roped base is
    exact.  Id-0 rows gate the delta to zero (the ``lora_apply``
    bitwise-unchanged contract), not by branching — the gather and dots run
    unconditionally, so the step keeps one shape for any tenant mix."""
    s = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _project():
        m_scratch[:] = jnp.full_like(m_scratch, -jnp.inf)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)
        xv = x_ref[0].astype(jnp.float32)            # [1, d_in]
        a = a_ref[0].astype(jnp.float32)             # [d_in, r]
        t = jax.lax.dot_general(
            xv, a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [1, r]
        # one [1, r] @ [r, D] dot per group member: a single contraction
        # against the [r, group, D] block needs a (group, D) -> group*D
        # shape cast Mosaic refuses when D is not lane-aligned (D=96)
        delta = jnp.concatenate([
            jax.lax.dot_general(
                t, b_ref[0, :, 0, g, :].astype(jnp.float32),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            ) for g in range(group)
        ], axis=0)  # [group, D]
        dh = delta.shape[-1] // 2
        c = cos_ref[0]                               # [1, D/2]
        sn = sin_ref[0]
        d1, d2 = delta[:, :dh], delta[:, dh:]
        delta_roped = jnp.concatenate(
            [d1 * c - d2 * sn, d2 * c + d1 * sn], axis=1
        )
        gate = (ids_ref[s] != 0).astype(jnp.float32)
        q_scratch[:] = q_ref[0, 0].astype(jnp.float32) + gate * delta_roped

    pos = pos_ref[s]
    kv_start = j * page_size

    @pl.when(kv_start <= pos)
    def _compute():
        q = q_scratch[:]                           # [group, D]
        k = _dequant_tile(k_ref, ks_ref, kv_qmax)  # [page_size, D]
        v = _dequant_tile(v_ref, vs_ref, kv_qmax)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        _paged_tile_update(scores, v, pos, kv_start, m_scratch, l_scratch,
                           acc_scratch)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _paged_finalize(o_ref, l_scratch, acc_scratch)


def _fused_bgmv_decode_kernel(bt_ref, pos_ref, ids_ref, q_ref, x_ref, a_ref,
                              b_ref, cos_ref, sin_ref, k_ref, v_ref, o_ref,
                              q_scratch, m_scratch, l_scratch, acc_scratch,
                              *, page_size, sm_scale, group):
    _fused_bgmv_decode_body(bt_ref, pos_ref, ids_ref, q_ref, x_ref, a_ref,
                            b_ref, cos_ref, sin_ref, k_ref, v_ref, None,
                            None, o_ref, q_scratch, m_scratch, l_scratch,
                            acc_scratch, page_size=page_size,
                            sm_scale=sm_scale, group=group, kv_qmax=None)


def _fused_bgmv_decode_kernel_quant(bt_ref, pos_ref, ids_ref, q_ref, x_ref,
                                    a_ref, b_ref, cos_ref, sin_ref, k_ref,
                                    v_ref, ks_ref, vs_ref, o_ref, q_scratch,
                                    m_scratch, l_scratch, acc_scratch,
                                    *, page_size, sm_scale, group, kv_qmax):
    _fused_bgmv_decode_body(bt_ref, pos_ref, ids_ref, q_ref, x_ref, a_ref,
                            b_ref, cos_ref, sin_ref, k_ref, v_ref, ks_ref,
                            vs_ref, o_ref, q_scratch, m_scratch, l_scratch,
                            acc_scratch, page_size=page_size,
                            sm_scale=sm_scale, group=group, kv_qmax=kv_qmax)


def fused_bgmv_paged_decode(
    x,
    q_base,
    a_stack,
    b_stack,
    adapter_ids,
    cos,
    sin,
    k_pages,
    v_pages,
    block_tables,
    positions,
    *,
    k_scales=None,
    v_scales=None,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Fused per-tenant LoRA query projection + paged decode attention.

    The tenant-mix decode step's two Pallas trips — bgmv (``ops/lora.py``)
    for the per-slot query adapter delta, then :func:`paged_decode_attention`
    — consolidated into one kernel: the adapter's A/B blocks are gathered
    by the scalar-prefetched ``adapter_ids`` through BlockSpec index maps
    (the bgmv trick), the delta is roped in-kernel at the slot's position
    and added to the pre-roped base query in VMEM scratch, and the page
    loop attends out of scratch.  One kernel launch, no ``[S, H, D]``
    delta round-trip through HBM, fixed shapes for any tenant mix.

    x: ``[S, d_in]`` attention input (post-norm hidden states);
    q_base: ``[S, H, D]`` base queries, already roped; a_stack:
    ``[N, d_in, r]``; b_stack: ``[N, r, H*D]`` (the AdapterStore pool
    layout — row 0 is the id-0 base slot); adapter_ids: ``[S]`` int32;
    cos/sin: ``[max_len, D/2]`` rope tables; remaining operands as in
    :func:`paged_decode_attention`, including quantized-page
    ``k_scales``/``v_scales``.  Returns ``[S, H, D]``.
    """
    s_slots, h, d = q_base.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    if h % hkv != 0:
        raise ValueError(f"num q heads {h} not divisible by kv heads {hkv}")
    group = h // hkv
    n = block_tables.shape[1]
    d_in = x.shape[-1]
    num_adapters, _, rank = a_stack.shape
    if b_stack.shape != (num_adapters, rank, h * d):
        raise ValueError(
            f"b_stack shape {b_stack.shape} != {(num_adapters, rank, h * d)}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = not _on_tpu()
    quantized = k_scales is not None

    qg = q_base.reshape(s_slots, hkv, group, d)
    # [N, r, H*D] -> [N, r, Hkv, group, D] so each program blocks out only
    # its kv-head group's columns
    b5 = b_stack.reshape(num_adapters, rank, hkv, group, d)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    ids = adapter_ids.astype(jnp.int32)
    cos = jnp.asarray(cos, jnp.float32)[:, None, :]
    sin = jnp.asarray(sin, jnp.float32)[:, None, :]
    max_len = cos.shape[0]

    def rope_idx(s, h, j, bt, p, ids_):
        # dead slots can carry stale positions; clamp to the table
        return (jnp.minimum(p[s], max_len - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_slots, hkv, n),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda s, h, j, bt, p, ids_: (s, h, 0, 0)),
            # x and the rope rows carry a unit middle axis so a one-row
            # block's last two dims equal the array's (block-shape rule)
            pl.BlockSpec((1, 1, d_in), lambda s, h, j, bt, p, ids_: (s, 0, 0)),
            pl.BlockSpec((1, d_in, rank), lambda s, h, j, bt, p, ids_: (ids_[s], 0, 0)),
            pl.BlockSpec((1, rank, 1, group, d), lambda s, h, j, bt, p, ids_: (ids_[s], 0, h, 0, 0)),
            pl.BlockSpec((1, 1, d // 2), rope_idx),
            pl.BlockSpec((1, 1, d // 2), rope_idx),
            *_page_specs(page_size, d, n, quantized),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), lambda s, h, j, bt, p, ids_: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    if quantized:
        kernel = functools.partial(
            _fused_bgmv_decode_kernel_quant, page_size=page_size,
            sm_scale=sm_scale, group=group, kv_qmax=_kv_qmax_for(k_pages),
        )
        operands = (bt_flat, pos, ids, qg, x[:, None, :], a_stack, b5, cos,
                    sin, k_pages, v_pages,
                    _scale_view(k_scales), _scale_view(v_scales))
    else:
        kernel = functools.partial(
            _fused_bgmv_decode_kernel, page_size=page_size,
            sm_scale=sm_scale, group=group,
        )
        operands = (bt_flat, pos, ids, qg, x[:, None, :], a_stack, b5, cos,
                    sin, k_pages, v_pages)
    out = pl.pallas_call(
        kernel,
        name="fused_bgmv_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, group, d), q_base.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(s_slots, h, d)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    segment_ids=None,
    kv_segment_ids=None,
    positions=None,
    kv_positions=None,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    return_lse: bool = False,
    interpret: Optional[bool] = None,
):
    """Drop-in replacement for :func:`models.llama.native_attention`.

    q: [B, T, H, D]; k/v: [B, S, Hkv, D].  GQA runs without repeating K/V —
    the forward's BlockSpecs map each q head to its group's kv head, and the
    backward walks a kv head's whole group against its resident K/V, so
    dk/dv accumulate the group sum in VMEM scratch.

    ``block_q``/``block_k`` pin BOTH passes' tiles; left None, each pass
    takes its own measured default (:func:`default_block_sizes`).

    ``segment_ids`` [B, T] masks cross-segment attention in-kernel (packed
    sequences at flash speed).  ``kv_segment_ids`` [B, S] gives the KV side
    its own ids when it differs from the query side (ring CP, where KV
    shards rotate between ranks); without it, self-attention shapes (T == S)
    are required and the query ids are reused.

    ``positions``/``kv_positions`` [B, T]/[B, S] give explicit global token
    positions for the causal mask — the ring-CP path, where each shard holds
    non-contiguous (zigzag) slices of the global sequence.

    ``return_lse`` additionally returns the per-token logsumexp [B, T, H]
    (differentiable) so partial attentions can be combined blockwise.
    """
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if h % hkv != 0:
        raise ValueError(f"num q heads {h} not divisible by kv heads {hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = not _on_tpu()
    segmented = segment_ids is not None
    if segmented:
        if kv_segment_ids is None:
            if s != t:
                raise ValueError(
                    "segment_ids without kv_segment_ids requires self-attention (T == S)"
                )
            kv_segment_ids = segment_ids
        seg_q = jnp.asarray(segment_ids, jnp.int32)[:, None, :]  # [B, 1, T]
        seg_kv = jnp.asarray(kv_segment_ids, jnp.int32)[:, None, :]  # [B, 1, S]
        if seg_q.shape[-1] != t:
            raise ValueError("segment_ids length must match the query sequence")
        if seg_kv.shape[-1] != s:
            raise ValueError("kv_segment_ids length must match the KV sequence")
    else:
        if kv_segment_ids is not None:
            raise ValueError("kv_segment_ids requires segment_ids")
        seg_q = jnp.zeros((b, 1, t), jnp.int32)
        seg_kv = jnp.zeros((b, 1, s), jnp.int32)

    positioned = positions is not None
    if positioned:
        pos_q = jnp.asarray(positions, jnp.int32)[:, None, :]
        pos_kv = jnp.asarray(
            positions if kv_positions is None else kv_positions, jnp.int32
        )[:, None, :]
        if pos_q.shape[-1] != t:
            raise ValueError("positions length must match the query sequence")
        if pos_kv.shape[-1] != s:
            raise ValueError("kv_positions length must match the KV sequence")
    else:
        pos_q = jnp.zeros((b, 1, t), jnp.int32)
        pos_kv = jnp.zeros((b, 1, s), jnp.int32)

    def to_bhd(x, heads, length):  # [B, L, H, D] -> [B*H, L, D]
        return x.transpose(0, 2, 1, 3).reshape(b * heads, length, d)

    out, lse = _flash(
        to_bhd(q, h, t), to_bhd(k, hkv, s), to_bhd(v, hkv, s), seg_q, seg_kv,
        pos_q, pos_kv, causal, sm_scale, block_q, block_k, segmented, positioned,
        interpret,
    )
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse.reshape(b, h, t).transpose(0, 2, 1)
    return out


# ---------------------------------------------------------------------------
# Mosaic calls under a device mesh
# ---------------------------------------------------------------------------


def per_shard(kernel, in_specs, out_specs):
    """``kernel`` wrapped to run once per device on that device's block.

    GSPMD cannot partition a Mosaic call (the TPU compiler refuses a sharded
    program that holds one: "wrap the call in a shard_map"), so every mesh
    axis the enclosing region still leaves to GSPMD goes manual around it.
    The mesh is the context's when the call is traced inside a ``shard_map``
    (a pipeline stage, the PowerSGD / hierarchical grad-sync region — jax
    rejects any other there), else the Accelerator's.

    ``in_specs``/``out_specs`` are callables ``free -> specs`` where ``free``
    maps each axis that is not yet manual and wider than one device to its
    size: the caller names only those (a dim nothing names is seen whole,
    gathered at the boundary).  Axes the context already made manual hold
    local blocks and need no spec.  No mesh, or no free axis left:
    ``kernel`` itself — the bare call.
    """
    from ..state import free_mesh_axes

    mesh, rest, free = free_mesh_axes()
    if not free:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs(free),
                         out_specs=out_specs(free), axis_names=rest, check_vma=False)


def mesh_flash_attention(q, k, v, *, causal: bool = True, segment_ids=None, **kwargs):
    """:func:`flash_attention` under the ambient mesh (:func:`per_shard`).

    Attention is independent per (batch row, head): the batch dim splits
    over the data-parallel axes and the head dim over ``tp``, and each device
    runs the kernel on its own block.  An axis that does not divide its dim
    stays out of the spec.  One device, a region that is already fully
    manual, or no Accelerator state (plain ``model.apply``): the bare kernel.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallelism_config import BATCH_AXES

    attn = functools.partial(flash_attention, causal=causal, **kwargs)

    def qkv_spec(free):
        batch = tuple(a for a in BATCH_AXES if a in free)
        if q.shape[0] % int(np.prod([free[a] for a in batch] or [1])):
            batch = ()
        tp = free.get("tp", 1)
        heads = "tp" if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
        return P(batch or None, None, heads, None)

    if segment_ids is None:
        return per_shard(attn, lambda free: (qkv_spec(free),) * 3, qkv_spec)(q, k, v)
    return per_shard(
        lambda q, k, v, seg: attn(q, k, v, segment_ids=seg),
        lambda free: (qkv_spec(free),) * 3 + (P(qkv_spec(free)[0], None),),
        qkv_spec,
    )(q, k, v, jnp.asarray(segment_ids, jnp.int32))

