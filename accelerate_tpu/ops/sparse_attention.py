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

The page walk under that mask is ``ops/page_walk.paged_masked_attention``,
shared with every other masked attention over paged keys
(:func:`paged_selected_attention` here is one caller of it); the page writes
are ``ops/paged_cache.page_writer``.  This module keeps what is SPARSE: the
order-preserving image of the scores, the threshold, the selection, the
indexer's scores over paged keys, and the cache-free form.

The threshold is the one Pallas kernel here (``sparse_threshold``,
:func:`kth_largest_key`): a tile of rows of the order-preserving integer image
of the float32 scores is brought into VMEM once and a 32-step bisection on its
bits runs there, the same code for a decode step's handful of rows and a
prefill chunk's thousands (off the TPU it runs in Pallas interpret mode, as
the kernels of ``ops/flash_attention.py`` do).  Everything else is plain XLA:
gathers of whole pages, dynamic slices and ``while`` loops whose trip count
follows the longest live context.
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
from . import page_walk

_INT_MIN = np.int32(-2 ** 31)
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
    bp = page_walk.block_pages_for(b, t, j, page)
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


@jax.named_scope("sparse_attend")
def paged_selected_attention(q, k_pages, v_pages, block_tables, keys, threshold, ties_taken,
                             kv_len):
    """Attention of ``q`` [B, T, H, D] over the keys its row selected:
    ``page_walk.paged_masked_attention`` with the selection as the mask.  ``keys``
    / ``threshold`` / ``ties_taken`` as :func:`index_keys` returns them.
    Returns [B, T, H, D]."""
    def selection(i, blk, ties):
        return selected(lax.dynamic_slice_in_dim(keys, i * blk, blk, axis=2), threshold,
                        ties_taken, ties)

    return page_walk.paged_masked_attention(q, k_pages, v_pages, block_tables, kv_len, selection,
                                            lambda: jnp.zeros(q.shape[:2], jnp.int32))


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
