"""The paged cache: the structure every served family's pools hang from, and
everything that writes it.  The reads through a block table are
``ops/page_walk.py`` (XLA walk and Pallas decode walk) and, for the dense
family's head-major pools, the Pallas kernels of ``ops/flash_attention.py``.

**One structure** (:func:`init_paged_pools`): a dict of pools a layer, whatever
that layer's kind keeps per token, under ONE block table ``[slots, pages a
slot]``, one ``seq_lens`` and one free stack.  The served model's
``init_paged_cache`` (the family protocol of ``serving/__init__.py``) builds the
layers and calls it.  The allocator (:func:`pages_for`, :func:`allocate`,
:func:`release`, :func:`push_pages`) mutates that structure **functionally** —
every operation is ``jnp`` index math on arrays the serving step carries through
``donate_argnums``, so the jitted decode/prefill steps stay donation-clean
(graft-lint GL101/GL201: the pool buffers alias in place, and no Python name
outlives its donation).

**Two layouts of a pool**, and which write goes with each:

- ``[Hkv, P, page, D]``, head-major — the dense family alone
  (``models/llama.py::init_paged_cache``: Llama, Mistral, Yi).  It is the
  tile the paged Pallas kernels of ``ops/flash_attention.py`` read.  Written by
  :func:`paged_write_kv` / :func:`paged_write_kv_quantized` (one scatter over
  ``[B * T]`` rows of every head), read back whole by :func:`paged_gather_kv`,
  and the only layout with quantised pages (``KV_QUANT_*``,
  :func:`dequantize_kv_pages`).  Under these XLA scatters the layout costs two
  relayout copies of the WHOLE pool around every write (``ROADMAP.md`` A1).
- ``[P, page, Hkv * D]``, page-major rows — Keye-VL-2, K-EXAONE, JoyAI-Flash and
  Qwen3-Next (and the ring of ``ops/window_attention.py``, a row a slot): a
  token's whole row (every KV head, an indexer's one key, a latent row with no
  head axis at all) is contiguous.  Written by :func:`page_writer`
  (:func:`write_token_rows` for a decode step, :func:`write_chunk_pages` for a
  prefill chunk) with ``dynamic_update_slice`` / one scatter on the donated
  pool, in the layout the reads use.  ONLY this layout writes without a relayout.

Merging the two is ``ROADMAP.md`` A1 (the dense family's pools take the second
layout) and C5 (which paged kernels survive it); until then both live here,
side by side, so that the change is one inside this module.

Allocator notes (vLLM PagedAttention discipline):

- ``free_stack``/``free_top`` form a stack of free physical page ids.  Pops
  never rewrite the stack (entries above ``free_top`` are dead); pushes
  overwrite dead entries.  Both directions are scatter/gather with computed
  ranks, so a *batch* of slots allocates/releases in one fused op.
- Masked lanes route their scatter index out of bounds and drop
  (``mode="drop"``) — the write-mask convention shared with the model's
  paged attention path.
- Exhaustion is the **scheduler's** job: the host mirrors the free count
  deterministically (same arithmetic on the same trace) and evicts before a
  pop could underflow; :func:`allocate` clamps indices so even a scheduler
  bug corrupts allocation, not memory safety.

What takes a model or a configuration and runs on the host (bytes a page, the
pool's share of HBM) is ``serving/paged_cache.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# the structure and its allocator
# ---------------------------------------------------------------------------


def init_paged_pools(layers: list, num_pages: int, num_slots: int, pages_per_slot: int,
                     **extras) -> dict:
    """The engine's cache pytree around ``layers``: one dict of page pools per
    layer, whatever that layer's kind keeps per token (K and V pages; scales
    of quantized pages; an indexer's key pages).  Every pool of every layer is
    addressed by the ONE block table and fed by the one free stack, so the
    scheduler, eviction and release know nothing of a layer's kind.
    ``extras`` are carried through every program untouched unless a family's
    program body updates them (``tick_counters``)."""
    return {
        "layers": layers,
        "block_tables": jnp.zeros((num_slots, pages_per_slot), jnp.int32),
        "seq_lens": jnp.zeros((num_slots,), jnp.int32),
        "free_stack": jnp.arange(num_pages, dtype=jnp.int32),
        "free_top": jnp.asarray(num_pages, jnp.int32),
        **extras,
    }


def pages_for(tokens, page_size: int):
    """Pages needed to hold ``tokens`` tokens (ceil division; 0 -> 0)."""
    return -(-tokens // page_size)


def allocate(block_tables, free_stack, free_top, slots, logical_pages, need):
    """Pop one page per needing lane and write it into the block table.

    ``slots``/``logical_pages``/``need``: aligned ``[K]`` arrays — lane *i*
    asks for a fresh physical page at ``block_tables[slots[i],
    logical_pages[i]]`` iff ``need[i]``.  Returns ``(block_tables,
    free_top)``; ``free_stack`` itself is untouched (pops only move the
    top).  Lanes with ``need=False`` drop their scatter.
    """
    need = need.astype(bool)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1           # 0-based grab order
    src = jnp.clip(free_top - 1 - rank, 0, free_stack.shape[0] - 1)
    pages = free_stack[src]
    rows = jnp.where(need, slots, block_tables.shape[0])    # OOB -> drop
    block_tables = block_tables.at[rows, logical_pages].set(pages, mode="drop")
    return block_tables, free_top - jnp.sum(need.astype(jnp.int32))


def release(block_tables, seq_lens, free_stack, free_top, release_mask, page_size: int):
    """Push every page owned by the masked slots back onto the free stack.

    A slot owns ``ceil(seq_len / page_size)`` pages (its block-table prefix).
    Returns ``(seq_lens, free_stack, free_top)`` with released slots' lengths
    zeroed — the block-table rows are left stale on purpose: the positional
    liveness mask never reads past ``seq_len``, so the next tenant just
    overwrites them.
    """
    release_mask = release_mask.astype(bool)
    n = block_tables.shape[1]
    owned = release_mask[:, None] & (
        jnp.arange(n)[None, :] < pages_for(seq_lens, page_size)[:, None]
    )
    free_stack, free_top = push_pages(
        free_stack, free_top, block_tables.reshape(-1), owned.reshape(-1)
    )
    seq_lens = jnp.where(release_mask, 0, seq_lens)
    return seq_lens, free_stack, free_top


def push_pages(free_stack, free_top, pages, mask):
    """Push an arbitrary masked set of physical pages back onto the free
    stack — THE free-stack push primitive (:func:`release` and the
    speculative verify pass's rollback both route through it).  A verify
    pass allocates worst-case pages up front (every page-start among its
    ``k + 1`` candidate positions), then returns the ones past the accepted
    frontier through this scatter, all inside the same donated jitted
    program.  ``pages``/``mask``: aligned ``[K]`` arrays; masked-out lanes
    route their scatter out of bounds and drop (the shared write-mask
    convention).  Returns ``(free_stack, free_top)``.

    **Aliasing contract** (prefix caching, docs/serving.md): a page id may
    reach this scatter ONLY while no holder references it.  The callers
    enforce it — the engine's COW release masks each slot's shared-prefix
    pages out (``release`` here pushes a slot's WHOLE block-table prefix,
    so prefix-armed engines route through the keep-aware variant instead),
    and ``PrefixCache.pop_pending`` hard-asserts refcount zero before the
    ``push_free`` dispatch — while ``verify_serving_invariants()`` checks
    the device-side exclusion (referenced ∩ free-stack = ∅) after the
    fact.  Pushing a still-referenced page is the double-free a refcount
    bug causes — two owners of one physical page — pinned by a planted
    test (tests/test_prefix_cache.py).
    """
    mask = mask.astype(bool)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dst = jnp.where(mask, free_top + rank, free_stack.shape[0])  # OOB -> drop
    free_stack = free_stack.at[dst].set(pages, mode="drop")
    return free_stack, free_top + jnp.sum(mask.astype(jnp.int32))


# ---------------------------------------------------------------------------
# head-major pools [Hkv, P, page, D]: the dense family's
# ---------------------------------------------------------------------------


# Quantized KV page dtypes (KIVI-style per-page scales; serving/paged_cache
# kv_page_bytes carries the matching accounting).  Codes are symmetric:
# q = round(v * QMAX / amax), dequant = q * (amax / QMAX); the per-(kv-head,
# page) amax lives in `k_scales`/`v_scales` float32 arrays next to the pages.
KV_QUANT_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
KV_QUANT_QMAX = {"int8": 127.0, "fp8": 448.0}


def resolve_kv_dtype(kv_dtype):
    """Normalize a KV page dtype knob: ``None``/``""``/``"bf16"`` mean
    "model dtype" (dense pages, no scales); ``"int8"``/``"fp8"`` arm the
    quantized page layout."""
    if kv_dtype in (None, "", "bf16"):
        return None
    if kv_dtype not in KV_QUANT_DTYPES:
        raise ValueError(
            f"kv_dtype must be '', 'bf16', 'int8' or 'fp8', got {kv_dtype!r}"
        )
    return kv_dtype


def paged_gather_kv(k_pages, v_pages, block_tables, k_scales=None,
                    v_scales=None, kv_dtype=None, out_dtype=None):
    """Gather a ``[B, S, Hkv, D]`` linear KV view through the block table.

    ``k_pages``/``v_pages``: ``[Hkv, P, page, D]``; ``block_tables``:
    ``[B, n]``.  Returns ``(k, v, kv_positions)`` with ``S = n * page`` and
    ``kv_positions`` the within-sequence token index of every gathered slot
    — ready for :func:`cached_attention`'s positional liveness mask (stale
    pages beyond a slot's ``seq_len`` sit at positions the causal
    comparison never admits).

    With quantized pages, pass the per-page ``k_scales``/``v_scales`` plus
    ``kv_dtype``/``out_dtype``: the gathered codes dequantize in the linear
    view (``codes * amax / QMAX``), so downstream attention is unchanged."""
    hkv, _, page, d = k_pages.shape
    b, n = block_tables.shape

    def lin(pages, scales):
        g = pages[:, block_tables]                      # [Hkv, B, n, page, D]
        if scales is not None:
            qmax = KV_QUANT_QMAX[kv_dtype]
            s = (scales / qmax)[:, block_tables]        # [Hkv, B, n]
            g = (g.astype(jnp.float32) * s[..., None, None]).astype(
                out_dtype or jnp.float32
            )
        return g.transpose(1, 2, 3, 0, 4).reshape(b, n * page, hkv, d)

    kv_positions = jnp.broadcast_to(jnp.arange(n * page, dtype=jnp.int32), (b, n * page))
    return lin(k_pages, k_scales), lin(v_pages, v_scales), kv_positions


@jax.named_scope("paged_write_kv")
def paged_write_kv(pages, values, page_ids, offsets):
    """Scatter per-token K or V rows into the page pool.

    ``pages``: ``[Hkv, P, page, D]``; ``values``: ``[B, T, Hkv, D]``;
    ``page_ids``/``offsets``: ``[B, T]`` int32 (masked tokens carry an
    out-of-bounds page id and drop — the write-mask convention)."""
    hkv, _, _, d = pages.shape
    flat = values.reshape(-1, hkv, d).transpose(1, 0, 2)   # [Hkv, B*T, D]
    return pages.at[:, page_ids.reshape(-1), offsets.reshape(-1)].set(
        flat.astype(pages.dtype), mode="drop"
    )


@jax.named_scope("paged_write_kv")
def paged_write_kv_quantized(pages, scales, values, page_ids, offsets,
                             kv_dtype: str):
    """Quantize-on-write into int8/fp8 pages with per-(kv-head, page) scales.

    Same scatter contract as :func:`paged_write_kv` (OOB page ids drop), with
    the per-page running-amax discipline layered on:

    1. an **offset-0 write opens the page**: its stored amax resets, so a
       recycled page never inherits the previous tenant's range (the reset
       also zeroes the stale codes via the ratio rescale below);
    2. the page amax is the **running max** over every row written so far
       (scatter-max), monotone within a page's lifetime;
    3. when the amax grows, the page's **existing codes rescale in place**
       (``codes * old_amax / new_amax``) so quantization and dequantization
       always share one scale — only the pages touched by this call are
       gathered/rescaled/scattered, never the pool.

    Every duplicate-index scatter writes identical values (all copies see
    the final amax), so the result is order-independent — bitwise
    deterministic run-to-run.  Returns ``(pages, scales)``.
    """
    hkv, num_pages, _, d = pages.shape
    qmax = KV_QUANT_QMAX[kv_dtype]
    page_dtype = KV_QUANT_DTYPES[kv_dtype]
    flat_pages = page_ids.reshape(-1)                       # [N]
    flat_off = offsets.reshape(-1)                          # [N]
    vals = values.reshape(-1, hkv, d).transpose(1, 0, 2).astype(jnp.float32)
    row_amax = jnp.max(jnp.abs(vals), axis=-1)              # [Hkv, N]
    # 1. open fresh pages (at most one offset-0 row per page per call)
    reset_ids = jnp.where(flat_off == 0, flat_pages, num_pages)
    opened = scales.at[:, reset_ids].set(0.0, mode="drop")
    # 2. running max over this call's rows
    new_scales = opened.at[:, flat_pages].max(row_amax, mode="drop")
    # 3. rescale the touched pages' existing codes to the final amax
    safe_pages = jnp.clip(flat_pages, 0, num_pages - 1)
    old_amax = opened[:, safe_pages]                        # [Hkv, N]
    fin_amax = new_scales[:, safe_pages]
    ratio = jnp.where(fin_amax > 0, old_amax / jnp.maximum(fin_amax, 1e-30), 1.0)
    touched = pages[:, safe_pages].astype(jnp.float32)      # [Hkv, N, page, D]
    rescaled = touched * ratio[:, :, None, None]
    if page_dtype == jnp.int8:
        rescaled = jnp.clip(jnp.rint(rescaled), -qmax, qmax)
    pages = pages.at[:, flat_pages].set(
        rescaled.astype(page_dtype), mode="drop"
    )
    # 4. quantize the new rows under the final page amax
    q = vals * (qmax / jnp.maximum(fin_amax, 1e-30))[:, :, None]
    q = jnp.where(fin_amax[:, :, None] > 0, q, 0.0)
    if page_dtype == jnp.int8:
        q = jnp.rint(q)
    q = jnp.clip(q, -qmax, qmax)
    pages = pages.at[:, flat_pages, flat_off].set(q.astype(page_dtype), mode="drop")
    return pages, new_scales


def dequantize_kv_pages(pages, scales, kv_dtype: str, dtype):
    """Full-pool dequantize: ``codes * amax / QMAX`` in ``dtype``.  The
    reference path for parity tests and the wire format's receive side."""
    qmax = KV_QUANT_QMAX[kv_dtype]
    return (pages.astype(jnp.float32)
            * (scales / qmax)[:, :, None, None]).astype(dtype)


# ---------------------------------------------------------------------------
# page-major pools [P, page, W]: page writes in place, in the layout the reads use
# ---------------------------------------------------------------------------
#
# A pool is ``[P, page, W]``: page-major, a token's whole row (every KV head,
# the indexer's one key, or a latent row with no head axis at all) contiguous.
# Reads gather whole pages along the leading dim and writes update a row or a
# page of it, so XLA keeps the pool in the layout it arrives in and puts no
# relayout copy around either (the head-major ``[Hkv, P, page, D]`` above is
# the Pallas kernels' tile; under these XLA ops it costs two copies of the
# pool a write).


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
