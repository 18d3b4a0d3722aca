"""Learned sparse attention: an indexer scores every visible key, the
``topk`` largest are selected, and attention runs over the selected keys only
(the DeepSeek-Sparse-Attention scheme; ``models/keye_vl2.py`` is its user).

Per query token ``t`` with indexer queries ``qI_{t,j}`` (``J`` heads), head
weights ``w_{t,j}`` and ONE indexer key ``kI_s`` per token::

    I_{t,s} = sum_j w_{t,j} * relu(qI_{t,j} . kI_s) / sqrt(Di)      for s <= t
    S_t     = the topk keys of largest I_{t,s}   (all of them while t < topk)
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s

One selected set per token, shared by every attention head.  The selection is
applied as the mask ``I_{t,s} >= (topk-th largest of row t)`` over a blockwise
attention that walks the sequence's pages: the same mathematics as a gather of
the selected rows, and no ``[T, S]`` probability array is ever held whole.
The threshold is EXACT, and keys that tie AT the threshold are taken in order
of position until the row has ``topk``: the set a stable sort by descending
score keeps.

The page walk under that mask, :func:`paged_masked_attention`, is shared by
every masked attention over paged keys that is not a kernel of its own: this
family's prefill chunks and decode steps, and the PREFILL CHUNKS of
``models/k_exaone.py``'s full causal layers and of ``models/joyai_flash.py``'s
latent attention (their decode steps ``[S, 1]`` walk each slot's own pages
inside the Pallas kernels of ``ops/latent_attention.py``, which are tested
against this walk).  What a row of a page holds is the caller's to say: a token's key heads in one pool and its value
heads in another, of one head width; or ONE row that is the key and, in its
first values, the value (a latent ``[c ; kr]`` shared by every head, scored
whole at the caller's scale); or a row that is up-projected to per-head keys
and values a gathered block at a time before it is scored.

The threshold is the one Pallas kernel here (``sparse_threshold``,
:func:`kth_largest_key`): a tile of rows of the order-preserving integer image
of the float32 scores is brought into VMEM once and a 32-step bisection on its
bits runs there, the same code for a decode step's handful of rows and a
prefill chunk's thousands (off the TPU it runs in Pallas interpret mode, as
the kernels of ``ops/flash_attention.py`` do).  Everything else is plain XLA:
gathers of whole pages, dynamic slices and ``while`` loops whose trip count
follows the longest live context.  Pages are written one page (prefill) or
one row (decode) at a time with ``dynamic_update_slice`` on the donated pool —
in the layout the reads use, so no relayout copy of a pool surrounds a write.
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

_INT_MIN = np.int32(-2 ** 31)
# f32 elements one loop step may hold as scores ([B, T, H or J, block]); 64M = 256 MiB
_BLOCK_BUDGET = 1 << 26
_MAX_BLOCK_PAGES = 64
# keys (rows x columns) of a row tile's running count in the threshold kernel: 4 vregs of
# int32, and how many times as many keys one counting step folds into it (so a step reads
# 32 vregs, and the loop's carried registers and its branch are paid once for them)
_COUNT_ELEMS = 4 * 1024
_COUNT_FOLD = 8
# bytes the kernel's double-buffered row tile may take of a core's ~16 MiB of VMEM
_TILE_BUDGET_BYTES = 12 * 1024 * 1024


def order_key(x):
    """float32 -> int32 whose signed order is the floats' order (``-0.0``
    counts as ``+0.0``).  Every finite float maps above ``INT32_MIN``, which
    is left to mark a key that is not visible."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


def _threshold_kernel(kv_len_ref, keys_ref, threshold_ref, ties_ref, *, k: int, step: int,
                      width: int):
    """One tile of rows, whole in VMEM: the bisection's 32 counts and the
    count for ``ties_taken`` all read the tile, never HBM.  Candidates are
    held as the keys' unsigned image (``key ^ INT32_MIN``) and compared signed
    against the keys themselves, so no second image of the tile is made.  A
    count walks the columns ``step`` at a time and folds a step's hits by
    halves into a running count ``width`` wide."""
    rows, cols = keys_ref.shape
    steps = jnp.minimum(pl.cdiv(kv_len_ref[0], step), cols // step)

    def count(test):
        """Per row [rows, 1]: the tile's keys that pass ``test``, over the
        column steps below ``kv_len``."""
        def add(i, acc):
            hits = test(keys_ref[:, pl.ds(pl.multiple_of(i * step, step), step)]).astype(jnp.int32)
            while hits.shape[1] > width:
                half = hits.shape[1] // 2
                hits = hits[:, :half] + hits[:, half:]
            return acc + hits
        acc = lax.fori_loop(0, steps, add, jnp.zeros((rows, width), jnp.int32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    def bit_step(i, prefix):
        """The largest v >= 1 with ``count(image >= v) >= k``, else 0: bit ``31 - i`` of it."""
        cand = prefix | lax.shift_left(jnp.int32(1), 31 - i)
        signed = cand ^ _INT_MIN
        return jnp.where(count(lambda block: block >= signed) >= k, cand, prefix)

    found = lax.fori_loop(0, 32, bit_step, jnp.zeros((rows, 1), jnp.int32))
    threshold = jnp.maximum(found ^ _INT_MIN, _INT_MIN + 1)
    threshold_ref[...] = threshold
    ties_ref[...] = k - count(lambda block: block > threshold)


def kth_largest_key(keys, k: int, kv_len=None):
    """Per row of ``keys`` [..., S] (int32, invisible entries ``INT32_MIN``):
    ``(threshold, ties_taken)``.  ``threshold`` is the ``k``-th largest value,
    or ``INT32_MIN + 1`` where fewer than ``k`` entries are visible;
    ``ties_taken`` is how many of the entries EQUAL to it belong to the top
    ``k`` (the first so many by position, as a stable descending sort keeps them).
    ``kv_len`` (scalar, optional): every entry at or past it is ``INT32_MIN``
    in every row, so no count walks there.

    One Pallas kernel (``sparse_threshold``) for any row count: the grid walks
    tiles of 8 to 32 rows, a tile is read from HBM once, and a bisection on
    the bits, most significant first, runs over it in VMEM.  Rows are padded
    to 8 and columns to a whole counting step with ``INT32_MIN`` where the
    shape is not already so (a decode step's [8, 36864] and a prefill chunk's
    [2048, 34816] are)."""
    lead, s = keys.shape[:-1], keys.shape[-1]
    r = int(np.prod(lead))
    rows = pl.cdiv(r, 8) * 8
    tile = next((t for t in (32, 16, 8)
                 if rows % t == 0 and 2 * 4 * t * s <= _TILE_BUDGET_BYTES), None)
    if tile is None:
        raise ValueError(f"8 rows of {s} keys do not fit the threshold kernel's VMEM tile "
                         f"(double-buffered, in {_TILE_BUDGET_BYTES} bytes)")
    width = _COUNT_ELEMS // tile
    step = width * min(_COUNT_FOLD, pl.next_power_of_2(pl.cdiv(s, width)))
    cols = pl.cdiv(s, step) * step
    flat = keys.reshape(r, s)
    if (rows, cols) != (r, s):
        flat = jnp.pad(flat, ((0, rows - r), (0, cols - s)), constant_values=_INT_MIN)
    kv_len = jnp.full((1,), s if kv_len is None else kv_len, jnp.int32)
    out = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    threshold, ties_taken = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k, step=step, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, cols), lambda i, kv_len: (i, 0))],
            out_specs=[pl.BlockSpec((tile, 1), lambda i, kv_len: (i, 0))] * 2),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=not _on_tpu(),
        name="sparse_threshold",
    )(kv_len, flat)
    return threshold[:r, 0].reshape(lead), ties_taken[:r, 0].reshape(lead)


def selected(keys, threshold, ties_taken, ties_before=0):
    """The selection over a block of ``keys`` [..., blk]: above the threshold,
    or tied with it and among the row's first ``ties_taken`` ties
    (``ties_before``: ties in the blocks before this one).  Returns
    ``(mask, ties_before for the next block)``."""
    tie = keys == threshold[..., None]
    rank = ties_before[..., None] + jnp.cumsum(tie, axis=-1, dtype=jnp.int32) \
        if not isinstance(ties_before, int) else jnp.cumsum(tie, axis=-1, dtype=jnp.int32)
    mask = (keys > threshold[..., None]) | (tie & (rank <= ties_taken[..., None]))
    return mask, rank[..., -1]


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


@jax.named_scope("sparse_index")
def index_keys(q_idx, w_idx, index_pages, block_tables, q_positions, topk: int, kv_len):
    """Indexer scores of every (query, key) pair against the paged indexer
    keys, as order keys, and each row's selection threshold.

    q_idx: [B, T, J, Di] float32 (rotary applied); w_idx: [B, T, J] float32;
    index_pages: [P, page, >= Di] (a key in the first Di lanes of its row); block_tables: [B, n] (n a whole number of
    loop steps: ``pad_block_tables``); q_positions: [B, T] int32, -1 for a
    query that sees nothing (dead slot, padding); kv_len: scalar, the longest
    live context (keys at or past it are scored by no step).

    Returns ``(keys [B, T, n * page] int32, threshold [B, T], ties_taken
    [B, T])``: what :func:`selected` reads the row's selection from.
    Scores are float32 from float32 operands at the highest matmul precision:
    the choice of the ``topk`` is discrete, and a bf16 product moves it."""
    b, t, j, di = q_idx.shape
    page = index_pages.shape[1]
    n = block_tables.shape[1]
    bp = block_pages_for(b, t, j, page)
    if n % bp:
        raise ValueError(f"block tables of {n} pages are not a whole number of {bp}-page steps")
    blk = bp * page
    scale = 1.0 / np.sqrt(di)

    def score_block(i, keys):
        pages = lax.dynamic_slice_in_dim(block_tables, i * bp, bp, axis=1)        # [B, bp]
        k_blk = index_pages[pages][..., :di].reshape(b, blk, di).astype(jnp.float32)
        dots = jnp.einsum("btjd,bsd->btjs", q_idx, k_blk, precision=lax.Precision.HIGHEST)
        scores = jnp.sum(w_idx[..., None] * jax.nn.relu(dots), axis=2) * scale    # [B, T, blk]
        pos = i * blk + jnp.arange(blk, dtype=jnp.int32)
        visible = pos[None, None, :] <= q_positions[:, :, None]
        return lax.dynamic_update_slice_in_dim(
            keys, jnp.where(visible, order_key(scores), _INT_MIN), i * blk, axis=2)

    steps = jnp.minimum((kv_len + blk - 1) // blk, n // bp)
    keys = lax.fori_loop(0, steps, score_block, jnp.full((b, t, n * page), _INT_MIN, jnp.int32))
    return (keys, *kth_largest_key(keys, topk, kv_len))


def paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len, block_mask,
                           mask_carry=lambda: (), *, scale=None, value_width=None, expand=None):
    """The page walk every masked attention over paged keys shares (callers:
    :func:`paged_selected_attention`, Keye's chunks and decode steps;
    :func:`paged_causal_attention`, K-EXAONE's prefill chunks; JoyAI's
    prefill chunks through ``expand``; and, as their oracle, the tests of the
    decode kernels of ``ops/latent_attention.py``): ``q``
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


@jax.named_scope("sparse_attend")
def paged_selected_attention(q, k_pages, v_pages, block_tables, keys, threshold, ties_taken,
                             kv_len):
    """Attention of ``q`` [B, T, H, D] over the keys its row selected:
    :func:`paged_masked_attention` with the selection as the mask.  ``keys``
    / ``threshold`` / ``ties_taken`` as :func:`index_keys` returns them.
    Returns [B, T, H, D]."""
    def selection(i, blk, ties):
        return selected(lax.dynamic_slice_in_dim(keys, i * blk, blk, axis=2), threshold,
                        ties_taken, ties)

    return paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len, selection,
                                  lambda: jnp.zeros(q.shape[:2], jnp.int32))


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
    ragged contexts is ``ops/latent_attention.paged_walk_decode_attention``,
    whose oracle this is."""
    return paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len,
                                  causal_mask(q_positions))


def dense_selected_attention(q, k, v, q_idx, w_idx, k_idx, positions, topk: int):
    """The same attention with no cache: one causal sequence per batch row,
    ``[T, T]`` scores held whole (tests and short forwards; ``T`` here is
    thousands at most).  q: [B, T, H, D]; k/v: [B, T, Hkv, D]; q_idx:
    [B, T, J, Di]; w_idx: [B, T, J]; k_idx: [B, T, Di]; positions: [B, T]
    (order of the tokens).  Returns ``(out [B, T, H, D], selected [B, T, T])``."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    with jax.named_scope("sparse_index"):
        dots = jnp.einsum("btjd,bsd->btjs", q_idx.astype(jnp.float32), k_idx.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
        scores = jnp.sum(w_idx[..., None] * jax.nn.relu(dots), axis=2) / np.sqrt(q_idx.shape[-1])
        visible = positions[:, None, :] <= positions[:, :, None]
        keys = jnp.where(visible, order_key(scores), _INT_MIN)
        chosen, _ = selected(keys, *kth_largest_key(keys, topk))
    with jax.named_scope("sparse_attend"):
        qg = q.reshape(b, t, hkv, h // hkv, d)
        s = jnp.einsum("bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v)
    return out.reshape(b, t, h, d), chosen


# ---------------------------------------------------------------------------
# page writes: in place, in the layout the reads use
# ---------------------------------------------------------------------------
#
# A pool is ``[P, page, W]``: page-major, a token's whole row (every KV head,
# the indexer's one key, or a latent row with no head axis at all) contiguous.
# Reads gather whole pages along the leading dim and writes update a row or a
# page of it, so XLA keeps the pool in the layout it arrives in and puts no
# relayout copy around either (the
# head-major ``[Hkv, P, page, D]`` of ``models/llama.py`` is the Pallas
# kernels' tile; under these XLA ops it costs two copies of the pool a write).


def page_writer(block_tables, positions, live, page_size: int):
    """``write(pages, rows [B, T, W])`` of one paged call, for every pool of a
    layer: a decode step ``[B, 1]`` writes one row a slot at its position's
    page and offset, a prefill chunk ``[1, C]`` its first ``sum(live)`` rows a
    page at a time from ``positions[0, 0]`` (a page boundary)."""
    if positions.shape[1] == 1:
        logical = jnp.clip(positions[:, 0] // page_size, 0, block_tables.shape[1] - 1)
        ids = jnp.take_along_axis(block_tables, logical[:, None], axis=1)[:, 0]
        return lambda pages, rows: write_token_rows(
            pages, rows[:, 0], ids, positions[:, 0] % page_size, live[:, 0])
    # one chunk of one sequence: contiguous positions from a page boundary
    length = jnp.sum(live[0].astype(jnp.int32))
    return lambda pages, rows: write_chunk_pages(
        pages, rows[0], block_tables[0], positions[0, 0], length)


def write_token_rows(pages, rows, page_ids, offsets, live):
    """Decode: one row per slot into ``pages`` [P, page, W].  rows: [B, W];
    page_ids/offsets/live: [B].  One scatter over the two leading dims; a
    dead slot's row goes out of bounds and is dropped (its block table may
    name a page that is another slot's by now)."""
    ids = jnp.where(live, page_ids, pages.shape[0])
    return pages.at[ids, offsets].set(rows.astype(pages.dtype), mode="drop")


def write_chunk_pages(pages, rows, page_row, start, length):
    """Prefill: the first ``length`` of ``rows`` [C, W] into the pages
    ``page_row`` [n] names from token ``start`` on, a page at a time.
    ``start`` is a multiple of the page size and ``C`` a whole number of
    pages (the engine's chunks are: ``prefill_chunk % page_size == 0``)."""
    _, page, width = pages.shape
    c = rows.shape[0]
    if c % page:
        raise ValueError(f"a prefill bucket of {c} tokens is not a whole number of {page}-token pages")
    vals = rows.astype(pages.dtype).reshape(c // page, page, width)
    first = start // page

    def write_page(j, pages):
        at = (page_row[first + j], 0, 0)
        old = lax.dynamic_slice(pages, at, (1, page, width))
        new = lax.dynamic_slice_in_dim(vals, j, 1, axis=0)
        keep = (j * page + jnp.arange(page)) < length
        return lax.dynamic_update_slice(pages, jnp.where(keep[None, :, None], new, old), at)

    return lax.fori_loop(0, (length + page - 1) // page, write_page, pages)
