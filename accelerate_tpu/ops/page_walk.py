"""Reading pages through a block table: everything that attends over the
page-major pools ``[P, page, W]`` of ``ops/paged_cache.py``.  Two walks:

- **the XLA walk**, :func:`paged_masked_attention` (with :func:`block_pages_for`,
  :func:`pad_block_tables`, :func:`causal_mask`, :func:`paged_causal_attention`):
  a block of pages at a time with a running softmax under a caller's mask, plain
  XLA gathers of whole pages.  It serves every masked attention over paged keys
  that is not a kernel of its own: Keye-VL-2's prefill chunks and decode steps
  (through ``ops/sparse_attention.paged_selected_attention``), the PREFILL CHUNKS
  of K-EXAONE's and Qwen3-Next's full causal layers and of JoyAI-Flash's latent
  attention, and it is the oracle the kernel below is tested against;
- **the Pallas decode walk**, ``_page_walk`` (:func:`paged_walk_decode_attention`,
  :func:`latent_decode_attention`): a decode step ``[S, 1]``, described next.

The dense family's head-major pools are read by the paged kernels of
``ops/flash_attention.py`` (``ROADMAP.md`` C5 moves them here when A1 has
decided which survive).

A decode step's attention over ITS OWN pages as one Pallas kernel: each slot
reads its own pages once, up to its own length, and nothing is gathered.  Two
callers, one walk; what a row of a page holds is read off the inputs:

- ``latent_decode`` (:func:`latent_decode_attention`; ``models/joyai_flash.py``'s
  ``[S, 1]`` step): ONE pool.  A token's latent row ``[c ; kr ; zeros]`` is the
  key of every head, whole, and in its first ``r`` values the value.  With
  ``q_h = [qa_h ; qr_h]`` the absorbed query of head ``h`` of the slot's one
  token at position ``t``::

      score_h(s) = q_h . row_s * scale          for s <= t
      u_h        = sum_s softmax_s(score_h) row_s[:r]

- ``paged_walk_decode`` (:func:`paged_walk_decode_attention`;
  ``models/k_exaone.py``'s full-attention layers, ``[S, 1]`` step): TWO pools
  ``[P, page, Hkv * D]``, a token's key heads in a row of one and its value
  heads in a row of the other; ``G = H / Hkv`` query heads meet each KV head's
  ``D`` lanes::

      score_h(s) = q_h . k_s[h // G] / sqrt(D)  for s <= t
      o_h        = sum_s softmax_s(score_h) v_s[h // G]

The mathematics and the precision are those of
:func:`paged_masked_attention` under the causal mask (float32
scores from the pool's dtype, a running maximum and sum in float32, ``p`` cast
to the pool's dtype before it meets the values, a float32 sum divided once at
the end), which stays the oracle this kernel is tested against.  What differs
is what is read: that walk gathers EVERY slot's pages up to the LONGEST live
context into a ``[S, block, row]`` array, writes it and reads it twice; here
each slot reads ITS OWN pages ONCE, up to ITS OWN length.

Grid ``(slots,)``, walked in order.  The block table and the positions ride as
scalar-prefetch operands, the pools stay in HBM (``pl.ANY``) and the kernel
walks a slot's pages itself, a chunk of pages (:func:`chunk_pages`) at a time:
one copy a page and pool (a page is contiguous in its pool) into one of two
VMEM buffers a pool, the next chunk's copies in flight while the current chunk
is scored — and behind a slot's last chunk the NEXT slot's first, so that only
the first slot of a call waits for copies it has just started (the buffer's
parity is carried from slot to slot in SMEM).  The copies are started and
awaited from LOOPS over the chunk's held pages (a buffer's copies share one
DMA semaphore a pool), so a page past the slot's last is neither copied nor
stepped over, and the kernel's body does not grow with the chunk; the places
of those pages in the last chunk's VALUE buffer are zeroed (whatever an
earlier chunk left there would meet ``p = 0``, and ``0 x NaN`` is not 0; a
stale key is masked before it is used).  A slot that sees nothing (``q_pos <
0``) copies nothing of its own and writes zeros.

Timed on a v5e, one layer's call alone, contexts drawn as the cell's traffic
holds them in steady state (``PERF.md`` section 6, PRs 37 and 39):

- ``k-exaone.serve_reason`` (64 slots of 608 .. 15,586 keys, 8 heads on 1 KV
  head, 3,666 pages of 64 x 128 bf16 in each pool, 16 KB a page; PR 39): the
  XLA walk 1.889 ms; the kernel 0.374 ms at 8 pages a chunk, 0.341 at 16,
  **0.318 at 32**, 0.321 at 64, 0.364 at 128: 377 GB/s of whole pages, 45.6%
  of the HBM time of the visible K and V.  7,332 copies a call: the scalar
  core's start and wait a page set the pace, not HBM.  At the published
  group (8 slots, 64 heads on 8 KV heads, 128 KB a page, 16 pages a chunk)
  0.175 ms against the XLA walk's 1.126: 77.9%.
- ``joyai-flash.serve_docs`` (48 slots, 4 heads, 7,077 pages of 64 x 640 bf16,
  80 KB a page): 0.818 ms at 16 pages a chunk with the copies looped (PR 39)
  against 0.815-0.820 with 48 predicated copies unrolled (PR 37's body, the
  same contexts), 0.828-0.833 at 32; the looped body traces and lowers in a
  third of the time (0.26 s against 0.79 s on that machine's host).  PR 37,
  5,915 pages, unrolled: 0.704 ms at 16 (0.764 at 8, 0.721 at 32; 0.762
  without the copies across slots) against the XLA walk's 6.80.

Hence :func:`chunk_pages`: a buffer of 1.25 MiB a pool, no fewer than 16 pages
and no more than 32.  The other scheme — ``BlockSpec``s that hand the pool in
16 times under 16 scalar-prefetched page ids, index maps held at each
operand's last page so that steps past a slot's length fetch nothing — took
1.40 ms at PR 37's shapes (a grid step for every chunk of the table, a copy of
each page inside VMEM) and is not in the package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

# f32 elements one loop step may hold as scores ([B, T, H or J, block]); 64M = 256 MiB
_BLOCK_BUDGET = 1 << 26
_MAX_BLOCK_PAGES = 64

# pages one compute step of the kernel scores: as many as fill _CHUNK_BYTES of one pool's buffer (16 pages of
# 64 rows of 640 bf16 values), no fewer than _CHUNK_PAGES (a step's fixed cost) and no more than
# twice that (the timings are in the module's docstring)
_CHUNK_PAGES = 16
_CHUNK_BYTES = 16 * 64 * 640 * 2


def chunk_pages(page_bytes: int, pages_per_slot: int) -> int:
    """Pages one compute step scores, from a page's bytes in one pool."""
    fill = max(_CHUNK_PAGES, min(_CHUNK_BYTES // page_bytes, 2 * _CHUNK_PAGES))
    return min(fill, pages_per_slot)


# ---------------------------------------------------------------------------
# the XLA walk
# ---------------------------------------------------------------------------


def block_pages_for(batch: int, width: int, heads: int, page_size: int) -> int:
    """Pages a loop step covers: as many as keep one step's float32 scores
    ([batch, width, heads, pages * page_size]) inside the block budget."""
    fit = _BLOCK_BUDGET // max(1, batch * width * heads * page_size)
    return int(max(1, min(_MAX_BLOCK_PAGES, 1 << max(0, int(fit).bit_length() - 1))))


def pad_block_tables(block_tables, block_pages: int):
    """Block tables padded to a whole number of loop steps.  The padding's
    page ids are 0: such keys lie past every sequence's capacity and are
    never visible."""
    pad = -block_tables.shape[1] % block_pages
    return jnp.pad(block_tables, ((0, 0), (0, pad))) if pad else block_tables


def paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len, block_mask,
                           mask_carry=lambda: (), *, scale=None, value_width=None, expand=None):
    """The page walk every masked attention over paged keys shares (callers:
    ``ops/sparse_attention.paged_selected_attention``, Keye's chunks and decode
    steps; :func:`paged_causal_attention`, K-EXAONE's prefill chunks; JoyAI's
    prefill chunks through ``expand``; and, as their oracle, the tests of the
    decode kernels below): ``q``
    [B, T, H, D] against the pages of ``block_tables`` [B, n] (n a whole
    number of loop steps: ``pad_block_tables``), a block of
    ``block_pages_for`` pages at a time with a running softmax, so that no
    ``[T, S]`` array is held whole.  ``block_mask(i, blk, carry)`` ->
    ``(mask [B, T, blk] bool, carry)`` says which keys of block ``i`` (the
    positions ``i * blk + arange(blk)``) each query attends; ``mask_carry()``
    makes its state before the first block.  ``kv_len``: scalar, the longest live
    context (no step walks past it).  A row that attends nothing (dead slot,
    padding) comes back zero.  Returns [B, T, H, Dv].

    What a row of a page may be:

    - ``k_pages`` and ``v_pages`` [P, page, Hkv * D], two pools of one head
      width: a token's key heads in one row, its value heads in the other
      (``Dv = D``);
    - ``v_pages=None``: ONE pool [P, page, Hkv * D] whose row is the key and,
      in its first ``value_width`` values, the value (a latent row ``[c ;
      kr]``: scored whole, summed as ``c``).  The row is summed whole and the
      sum cut to ``Dv = value_width``, so no slice of a gathered block is made;
    - ``expand(rows [B, blk, W]) -> (k [B, blk, H, D], v [B, blk, H,
      value_width])``: the gathered rows of that one pool are up-projected to
      per-head keys and values before they are scored (a latent row expanded
      by ``W_UK`` / ``W_UV`` for a prefill chunk).

    ``scale`` multiplies the scores (default ``1 / sqrt(D)``)."""
    b, t, h, d = q.shape
    _, page, width = k_pages.shape
    hkv = h if expand is not None else width // d
    dv = d if value_width is None else value_width
    g = h // hkv
    n = block_tables.shape[1]
    bp = block_pages_for(b, t, h, page)
    if n % bp:
        raise ValueError(f"block tables of {n} pages are not a whole number of {bp}-page steps")
    if v_pages is not None and (expand is not None or value_width is not None):
        raise ValueError("a value is a second pool, or a part of the key pool's row, not both")
    blk = bp * page
    qg = q.reshape(b, t, hkv, g, d)
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    summed = d if v_pages is None and expand is None else dv       # a whole row is summed, then cut

    def gathered(pages):
        if expand is not None:
            return expand(k_pages[pages].reshape(b, blk, width))
        k_blk = k_pages[pages].reshape(b, blk, hkv, d)
        return k_blk, (k_blk if v_pages is None else v_pages[pages].reshape(b, blk, hkv, d))

    def attend_block(i, carry):
        m, l, acc, state = carry
        pages = lax.dynamic_slice_in_dim(block_tables, i * bp, bp, axis=1)        # [B, bp]
        k_blk, v_blk = gathered(pages)
        s = jnp.einsum("bthgd,bshd->bhgts", qg, k_blk, preferred_element_type=jnp.float32) * scale
        sel, state = block_mask(i, blk, state)
        sel = sel[:, None, None]                                                  # [B,1,1,T,blk]
        m_new = jnp.maximum(m, jnp.max(jnp.where(sel, s, -jnp.inf), axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(sel, jnp.exp(s - safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgts,bshd->bhgtd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha[..., None] + pv, state

    steps = jnp.minimum((kv_len + blk - 1) // blk, n // bp)
    init = (jnp.full((b, hkv, g, t), -jnp.inf, jnp.float32),
            jnp.zeros((b, hkv, g, t), jnp.float32),
            jnp.zeros((b, hkv, g, t, summed), jnp.float32), mask_carry())
    _, l, acc, _ = lax.fori_loop(0, steps, attend_block, init)
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, summed).astype(q.dtype)
    return out if summed == dv else out[..., :dv]


def causal_mask(q_positions):
    """The ``block_mask`` of full causal attention: key ``s`` is seen by the
    query at position ``t`` iff ``s <= t``.  ``q_positions`` [B, T] int32, -1
    for a query that sees nothing (dead slot, padding)."""
    def causal(i, blk, state):
        pos = i * blk + jnp.arange(blk, dtype=jnp.int32)
        return pos[None, None, :] <= q_positions[:, :, None], state

    return causal


@jax.named_scope("global_attend")
def paged_causal_attention(q, k_pages, v_pages, block_tables, q_positions, kv_len):
    """Full causal attention over paged keys: the same walk with the mask
    ``s <= t`` (:func:`causal_mask`).  It gathers EVERY row of ``q``'s batch
    up to ``kv_len`` in whole blocks, which suits one sequence's chunk ``[1,
    C]`` (``models/k_exaone.py``'s prefill); a decode step ``[S, 1]`` over
    ragged contexts is :func:`paged_walk_decode_attention`, whose oracle this
    is."""
    return paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len,
                                  causal_mask(q_positions))


# ---------------------------------------------------------------------------
# the Pallas decode walk
# ---------------------------------------------------------------------------


def _walk_kernel(bt_ref, pos_ref, *refs, scale: float, pages_per_slot: int, chunk: int,
                 q_parts: int, pools: int, kv_heads: int):
    """One slot: its pages ``0 .. q_pos // page`` in chunks of ``chunk`` under
    a running softmax kept in registers.  ``refs``: the query's parts (side by
    side they meet a key head's lanes), the key pool, the value pool if it is
    another, the output; then the scratch: the query whole, two buffers a
    pool, a DMA semaphore a buffer and pool, and ``par_ref[0]``: which of the
    two buffers holds this slot's first chunk (started by the slot before it)."""
    refs = iter(refs)
    take = lambda n: [next(refs) for _ in range(n)]
    q_refs, pool_refs, (o_ref, q_scr), bufs, (sem, par_ref) = \
        take(q_parts), take(pools), take(2), take(pools), take(2)
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    page = pool_refs[0].shape[1]
    heads, dk = q_scr.shape
    dv = o_ref.shape[2]
    group = heads // kv_heads
    pos = pos_ref[slot]
    pages_of = lambda s: jnp.maximum(pos_ref[s] + page, 0) // page
    pages = pages_of(slot)
    chunks = (pages + chunk - 1) // chunk
    nxt = jnp.minimum(slot + 1, slots - 1)
    nxt_pages = jnp.where(slot + 1 < slots, pages_of(nxt), 0)
    rows_of = lambda j: pl.ds(pl.multiple_of(j * page, page), page)

    def start(s, s_pages, c, b):
        """Copies of slot ``s``'s chunk ``c`` into buffer ``b``: its held pages alone."""
        def page_copies(j, _):
            page_id = bt_ref[s * pages_per_slot + c * chunk + j]
            for i in range(pools):
                pltpu.make_async_copy(pool_refs[i].at[page_id], bufs[i].at[b, rows_of(j)],
                                      sem.at[b, i]).start()
            return _

        lax.fori_loop(0, jnp.clip(s_pages - c * chunk, 0, chunk), page_copies, 0)

    def wait(c, b):
        """This slot's chunk ``c`` has landed in buffer ``b``; what it does not hold is zero."""
        held = jnp.minimum(pages - c * chunk, chunk)

        def landed(j, _):       # one page's bytes off the pool's semaphore, whichever page it was
            for i in range(pools):
                pltpu.make_async_copy(pool_refs[i].at[0], bufs[i].at[b, rows_of(j)],
                                      sem.at[b, i]).wait()
            return _

        def zeroed(j, _):
            bufs[-1][b, rows_of(j), :] = jnp.zeros((page, bufs[-1].shape[2]), bufs[-1].dtype)
            return _

        lax.fori_loop(0, held, landed, 0)
        lax.fori_loop(held, chunk, zeroed, 0)

    @pl.when(slot == 0)
    def _():
        par_ref[0] = 0
        start(slot, pages, 0, 0)

    par = par_ref[0]

    @pl.when(chunks == 0)
    def _():
        start(nxt, nxt_pages, 0, par)

    at = 0
    for part in q_refs:
        q_scr[:, at:at + part.shape[2]] = part[0]
        at += part.shape[2]
    if at < dk:
        q_scr[:, at:] = jnp.zeros((heads, dk - at), q_scr.dtype)
    q = q_scr[...]
    heads_of = lambda h: slice(h * group, (h + 1) * group)
    over_heads = lambda parts: parts[0] if kv_heads == 1 else jnp.concatenate(parts, axis=0)

    def attend_chunk(c, carry):
        m, l, acc = carry
        b = (par + c) % 2
        last = c + 1 == chunks
        start(jnp.where(last, nxt, slot), jnp.where(last, nxt_pages, pages),
              jnp.where(last, 0, c + 1), 1 - b)
        wait(c, b)
        s = over_heads([lax.dot_general(q[heads_of(h)], bufs[0][b, :, h * dk:(h + 1) * dk],
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                        for h in range(kv_heads)]) * scale               # [H, chunk * page]
        seen = c * (chunk * page) + lax.broadcasted_iota(jnp.int32, s.shape, 1) <= pos
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p = p.astype(bufs[-1].dtype)
        pv = over_heads([lax.dot_general(p[heads_of(h)], bufs[-1][b, :, h * dv:(h + 1) * dv],
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                         for h in range(kv_heads)])
        return m_new, l, acc * alpha + pv

    init = (jnp.full((heads, 1), -jnp.inf, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, chunks, attend_chunk, init)
    par_ref[0] = (par + chunks) % 2
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_width", "name", "interpret"))
def _page_walk(q_parts, k_pages, v_pages, block_tables, q_positions, *, scale, value_width, name,
               interpret):
    """The walk under a ``jit`` of its own, so that a program of many layers
    traces and lowers the kernel once and not once a layer.  ``q_parts``:
    ``[S, H, w]`` arrays that side by side meet a key head's lanes;
    ``v_pages=None``: the value is the first ``value_width`` values of the key
    pool's row (one KV head).  Returns ``[S, H, value_width]`` a KV head's
    group after another."""
    s_slots, heads = q_parts[0].shape[:2]
    _, page, row = k_pages.shape
    n = block_tables.shape[1]
    pools = (k_pages,) if v_pages is None else (k_pages, v_pages)
    kv_heads = 1 if v_pages is None else row // value_width
    dk = row // kv_heads
    if sum(part.shape[2] for part in q_parts) > dk or heads % kv_heads:
        raise ValueError(f"{heads} queries of {[part.shape[2] for part in q_parts]} values do "
                         f"not meet {kv_heads} key heads of {dk}")
    # one pool: the sum runs over whole lane tiles of the row, cut below
    dv = value_width if v_pages is not None else min(row, -(-value_width // 128) * 128)
    chunk = chunk_pages(page * row * k_pages.dtype.itemsize, n)
    mine = lambda s, bt, pos: (s, 0, 0)
    out = pl.pallas_call(
        functools.partial(_walk_kernel, scale=scale, pages_per_slot=n, chunk=chunk,
                          q_parts=len(q_parts), pools=len(pools), kv_heads=kv_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s_slots,),
            in_specs=[pl.BlockSpec((1, heads, part.shape[2]), mine) for part in q_parts]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, heads, dv), mine),
            scratch_shapes=[pltpu.VMEM((heads, dk), k_pages.dtype)]
            + [pltpu.VMEM((2, chunk * page, pool.shape[2]), pool.dtype) for pool in pools]
            + [pltpu.SemaphoreType.DMA((2, len(pools))), pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s_slots, heads, dv), q_parts[0].dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(block_tables.reshape(-1).astype(jnp.int32), q_positions.astype(jnp.int32), *q_parts, *pools)
    return out if dv == value_width else out[..., :value_width]


def latent_decode_attention(qa, qr, latent_pages, block_tables, q_positions, *, scale: float):
    """One decode step's absorbed attention over paged latent rows.

    qa: ``[S, H, r]`` (``W_UK^T qn``: meets a row's first ``r`` values, which
    are also the value); qr: ``[S, H, dr]`` (rotary applied: meets the next
    ``dr``); latent_pages: ``[P, page, row]`` in the queries' dtype, ``row >=
    r + dr``, the rest of a row zeros; block_tables: ``[S, n]`` int32;
    q_positions: ``[S]`` int32, the token's position (keys ``0 .. position``
    are seen), -1 for a slot that sees nothing.  Returns ``u`` ``[S, H, r]``
    in ``qa``'s dtype; a slot that sees nothing comes back zero.

    Entries of a block table past ``position // page`` are never read, and
    neither are the pages they name."""
    return _page_walk((qa, qr), latent_pages, None, block_tables, q_positions, scale=scale,
                      value_width=qa.shape[2], name="latent_decode", interpret=not _on_tpu())


def paged_walk_decode_attention(q, k_pages, v_pages, block_tables, q_positions):
    """One decode step's full causal attention over paged keys and values.

    q: ``[S, H, D]``; k_pages, v_pages: ``[P, page, Hkv * D]`` in the queries'
    dtype (``H / Hkv`` query heads a KV head, a KV head's group after
    another); block_tables: ``[S, n]`` int32; q_positions: ``[S]`` int32, the
    token's position (keys ``0 .. position`` are seen), -1 for a slot that
    sees nothing.  Scores are scaled by ``1 / sqrt(D)``.  Returns ``[S, H,
    D]`` in ``q``'s dtype; a slot that sees nothing comes back zero.

    Entries of a block table past ``position // page`` are never read, and
    neither are the pages they name."""
    d = q.shape[2]
    return _page_walk((q,), k_pages, v_pages, block_tables, q_positions, scale=1.0 / d ** 0.5,
                      value_width=d, name="paged_walk_decode", interpret=not _on_tpu())
