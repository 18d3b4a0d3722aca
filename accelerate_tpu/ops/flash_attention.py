"""Fused flash attention — Pallas TPU kernel.

The MFU-critical op (SURVEY §7 hard parts: '≥45% MFU on v5e requires fused
flash attention').  Blockwise online-softmax attention: K/V stream through
VMEM in (block_k, head_dim) tiles while a (block_q, head_dim) fp32 accumulator
and running (max, denom) stats live in scratch — memory O(T) instead of
O(T²), and every matmul lands on the MXU at 128-aligned tiles.

Causal masking skips fully-masked KV blocks (upper-triangular blocks cost
zero compute — the grid still visits them but predication makes them free).

Backward: fused Pallas kernels (dq + dk/dv), recompute-based — the forward
saves (q, k, v, out, logsumexp); each backward tile rebuilds its probability
block from (q, k, lse) and accumulates gradients in VMEM scratch, so the
[T, T] tensors of the naive backward never touch HBM.  Split into two kernels
(dq accumulates over kv, dk/dv over q) instead of atomics — the TPU idiom.

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


def default_block_sizes(t: int, s: int, d: int) -> tuple[int, int]:
    """Heuristic (block_q, block_k) keyed on sequence lengths and head dim.

    Start from the sweet spot measured at seq 2048-8192 / head_dim≤128 on
    v5e ((1024, 1024) — the autotune sweep at those shapes, worth ~1.5%
    end-to-end over (512, 1024) on the headline bench); clamp to the actual
    sequence lengths rounded up to the MXU tile (128); then shrink while the
    fp32 working set (q/k/v tiles + scores tile + accumulator) exceeds the
    VMEM budget — at large head_dim the 1024-tiles no longer double-buffer.
    """
    round_up = lambda x: max(128, -(-x // 128) * 128)
    block_q = min(1024, round_up(t))
    block_k = min(1024, round_up(s))
    if round_up(t) >= 32768 or d >= 128:
        # The (1024, 1024) backward tile exceeds the Mosaic scoped-VMEM
        # stack limit (by ~160KB) once the remat'd layer context is fused
        # around it, at long sequence or at head_dim >= 128 (7B-class
        # models) — and at 32k it is 1.55x slower standalone anyway; halve
        # block_q.  (At 16k/d<128 the 1024 tile is ~6% faster end-to-end,
        # so the clamp stays off there.)
        block_q = min(block_q, 512)

    def working_set(bq, bk):
        # q, k, v, out-acc tiles in fp32 + the [bq, bk] scores/probs tile
        return 4 * (bq * d + 2 * bk * d + bq * d + bq * bk)

    while working_set(block_q, block_k) > _VMEM_BUDGET_BYTES and block_k > 128:
        block_k //= 2
    while working_set(block_q, block_k) > _VMEM_BUDGET_BYTES and block_q > 128:
        block_q //= 2
    return block_q, block_k


def autotune_block_sizes(
    b: int, t: int, h: int, d: int, hkv: Optional[int] = None, *,
    dtype=jnp.bfloat16, causal: bool = True, candidates=None, iters: int = 3,
) -> tuple[int, int]:
    """Measure the best (block_q, block_k) for a shape on the current device.

    Runs a short sweep of forward+backward over candidate tilings and returns
    the fastest.  Results are cached per (shape, device kind) for the
    process.  Meant for offline tuning (bench setup), not the hot path —
    each candidate pays a compile.
    """
    key = (b, t, h, d, hkv, str(dtype), causal,
           getattr(jax.devices()[0], "device_kind", "cpu"))
    if key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    import time

    hkv = hkv or h
    rng = np.random.default_rng(0)
    mk = lambda heads: jnp.asarray(rng.normal(size=(b, t, heads, d)), dtype)
    inputs = [(mk(h), mk(hkv), mk(hkv)) for _ in range(iters + 1)]
    if candidates is None:
        base_q, base_k = default_block_sizes(t, t, d)
        candidates = {
            (base_q, base_k), (max(base_q // 2, 128), base_k), (base_q, max(base_k // 2, 128)),
            (min(1024, base_q * 2), base_k), (256, 256), (512, 512),
        }
        # keep MXU-aligned tiles; the kernel clamps to t internally, so
        # oversized candidates just duplicate the largest feasible tiling
        candidates = {(bq, bk) for bq, bk in candidates if bq % 128 == 0 and bk % 128 == 0}
    best, best_dt = None, float("inf")
    for bq, bk in sorted(candidates):
        # sum-of-grad-norms: one scalar whose fetch ends the timed work
        def score(q, k, v, bq=bq, bk=bk):
            g = jax.grad(lambda q: jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk).astype(jnp.float32)))(q)
            return jnp.sum(jnp.abs(g).astype(jnp.float32))

        # graft-lint: disable=GL306 -- autotuner: one jit per (bq, bk) candidate is the point; each tiling is a distinct program, compiled and measured exactly once
        f = jax.jit(score)
        try:
            float(f(*inputs[0]))  # compile + warm
            t0 = time.perf_counter()
            for i in range(iters):
                acc = f(*inputs[i + 1])
            float(acc)
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

    Shared by the forward and both backward kernels so the masking convention
    cannot drift between them.  Returns (scores, valid): padded rows/cols of
    the last (non-divisible) blocks, cross-segment pairs (packed sequences),
    and causally-forbidden entries get DEFAULT_MASK_VALUE; ``valid`` is the
    boolean tile for callers that must hard-zero probabilities (the backward,
    where lse of padded rows is garbage).

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
    block_q = min(block_q, t)
    block_k = min(block_k, s)
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


def _bwd_tile(q, k, v, g, lse, delta, sm_scale, q_start, k_start, t_len, s_len,
              causal, block_q, block_k, seg_q=None, seg_k=None, pos_q=None, pos_k=None):
    """(p, ds) for one backward tile — the recompute shared by dq and dk/dv.

    p is hard-zeroed on invalid entries (padded rows read garbage lse/delta,
    so masking via scores alone is not enough); ds = p * (dp - delta) * scale.
    """
    s, valid = _masked_scores(
        q, k, sm_scale, q_start, k_start, t_len, s_len, causal, block_q, block_k,
        seg_q, seg_k, pos_q, pos_k,
    )
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = jnp.where(valid, p * (dp - delta) * sm_scale, 0.0)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref,
               pos_q_ref, pos_kv_ref, dq_ref, dq_scratch,
               *, causal, sm_scale, block_q, block_k, t_len, s_len, segmented, positioned):
    """Grid: (batch*heads, q_blocks, kv_blocks); kv innermost/serial.

    Blockwise flash backward for dq: recompute the probability tile from
    (q, k, lse), form ds = p * (dp - delta), accumulate ds @ k.  Memory stays
    O(block²) in VMEM — the [T, T] tensors of the naive backward never exist.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    q_start = qi * block_q
    k_start = ki * block_k
    should_compute = (not causal) or positioned or (q_start + block_q - 1 >= k_start)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0]
        k = _zero_oob_rows(k_ref[0], k_start, s_len)
        v = _zero_oob_rows(v_ref[0], k_start, s_len)
        g = _zero_oob_rows(g_ref[0], q_start, t_len)
        lse = lse_ref[0, 0][:, None]      # [block_q, 1]
        delta = delta_ref[0, 0][:, None]  # [block_q, 1]
        _, ds = _bwd_tile(
            q, k, v, g, lse, delta, sm_scale,
            q_start, k_start, t_len, s_len, causal, block_q, block_k,
            seg_q_ref[0, 0] if segmented else None,
            seg_kv_ref[0, 0] if segmented else None,
            pos_q_ref[0, 0] if positioned else None,
            pos_kv_ref[0, 0] if positioned else None,
        )
        dq_scratch[:] += jax.lax.dot_general(
            ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref,
                pos_q_ref, pos_kv_ref, dk_ref, dv_ref,
                dk_scratch, dv_scratch, *, causal, sm_scale, block_q, block_k,
                t_len, s_len, q_blocks, segmented, positioned):
    """Grid: (batch*kv_heads, kv_blocks, group*q_blocks); innermost/serial dim
    walks every (GQA group member, q block) pair.

    Same tile recompute as :func:`_dq_kernel`, accumulated along q — and,
    under GQA, across the group's q heads (dk/dv sum over the group here
    instead of a post-hoc reduction over repeated heads): dv += pᵀ @ g and
    dk += dsᵀ @ q — separate kernel per accumulation direction instead of
    atomics (the TPU idiom)."""
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qi = gi % q_blocks  # q-block index within the current group member

    @pl.when(gi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q
    k_start = ki * block_k
    should_compute = (not causal) or positioned or (q_start + block_q - 1 >= k_start)

    @pl.when(should_compute)
    def _compute():
        q = _zero_oob_rows(q_ref[0], q_start, t_len)
        g = _zero_oob_rows(g_ref[0], q_start, t_len)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        p, ds = _bwd_tile(
            q, k_ref[0], v_ref[0], g, lse, delta, sm_scale,
            q_start, k_start, t_len, s_len, causal, block_q, block_k,
            seg_q_ref[0, 0] if segmented else None,
            seg_kv_ref[0, 0] if segmented else None,
            pos_q_ref[0, 0] if positioned else None,
            pos_kv_ref[0, 0] if positioned else None,
        )
        dv_scratch[:] += jax.lax.dot_general(
            p.astype(q.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scratch[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(gi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, seg_q, seg_kv, pos_q, pos_kv, out, lse, g, g_lse, causal,
               sm_scale, block_q, block_k, segmented, positioned, interpret):
    """Fused blockwise backward: dq [B*H, T, D], dk/dv [B*Hkv, S, D].

    ``g_lse`` is the cotangent of the lse output (nonzero when callers
    combine partial attentions by logsumexp — ring CP): its score-gradient
    contribution is ``p * g_lse``, which folds into the existing
    ``ds = p * (dp - delta)`` as ``delta - g_lse``.
    """
    bh, t, d = q.shape
    bhkv, s_len, _ = k.shape
    n_batch = seg_q.shape[0]
    n_heads = bh // n_batch
    n_rep = bh // bhkv
    block_q = min(block_q, t)
    block_k = min(block_k, s_len)
    q_blocks = pl.cdiv(t, block_q)

    # delta_i = g_i . out_i — one cheap fused XLA pass, carried as [BH, 1, T]
    # (same tiling-friendly layout as lse)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = delta[:, None, :]
    lse3 = lse[:, None, :]

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )

    # dq grid: (q heads, q_blocks, kv_blocks) — kv specs map to the group head
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // n_rep, j, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    seg_q_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b // n_heads, 0, i))
    seg_kv_spec = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // n_heads, 0, j))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            t_len=t, s_len=s_len, segmented=segmented, positioned=positioned,
        ),
        name="flash_bwd_dq",
        grid=(bh, q_blocks, pl.cdiv(s_len, block_k)),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec, seg_q_spec, seg_kv_spec,
                  seg_q_spec, seg_kv_spec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, g, lse3, delta, seg_q, seg_kv, pos_q, pos_kv)

    # dk/dv grid: (kv heads, kv_blocks, group*q_blocks) — the serial dim walks
    # every (group member, q block) pair so GQA head-sums happen in-scratch
    hkv = bhkv // n_batch  # kv heads per batch element

    def q_map(b, j, i):  # kv head b, serial step i -> q-head row + q block
        return ((b // hkv) * n_heads + (b % hkv) * n_rep + i // q_blocks, i % q_blocks, 0)

    def row_map(b, j, i):
        return ((b // hkv) * n_heads + (b % hkv) * n_rep + i // q_blocks, 0, i % q_blocks)

    qspec2 = pl.BlockSpec((1, block_q, d), q_map)
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec2 = pl.BlockSpec((1, 1, block_q), row_map)
    seg_q_spec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b // hkv, 0, i % q_blocks))
    seg_kv_spec2 = pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b // hkv, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            t_len=t, s_len=s_len, q_blocks=q_blocks, segmented=segmented, positioned=positioned,
        ),
        name="flash_bwd_dkv",
        grid=(bhkv, pl.cdiv(s_len, block_k), n_rep * q_blocks),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2, seg_q_spec2, seg_kv_spec2,
                  seg_q_spec2, seg_kv_spec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, s_len, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, s_len, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, g, lse3, delta, seg_q, seg_kv, pos_q, pos_kv)
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
# models/llama.py:KV_QUANT_QMAX): symmetric int8 uses the full [-127, 127]
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

    **Quantized pages** (``serving/paged_cache.py`` int8/fp8 pools): pass
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
    the kernel's BlockSpecs map each q head to its group's kv head, and dk/dv
    accumulate the group sum in VMEM scratch.

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
    if block_q is None or block_k is None:
        bq, bk = default_block_sizes(t, s, d)
        block_q = block_q or bq
        block_k = block_k or bk

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

