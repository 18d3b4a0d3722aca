"""Functional device-side page allocator for the paged KV cache.

The pool itself is built by the served model's ``init_paged_cache`` (the
family protocol of ``serving/__init__.py``) around :func:`init_paged_pools`
(fixed-size pages, per-slot block tables, a free-list stack).  This module is
the allocator arithmetic that mutates that structure **functionally** — every
operation is ``jnp`` index math on arrays the serving step carries through
``donate_argnums``, so the jitted decode/prefill steps stay donation-clean
(graft-lint GL101/GL201: the pool buffers alias in place, and no Python name
outlives its donation).

Design notes (vLLM PagedAttention discipline):

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


def kv_page_bytes(config, page_size: int, dtype_bytes: int = 2,
                  kv_dtype: str = "") -> int:
    """Bytes of ONE physical page across all layers — the unit the
    allocator hands out AND the disaggregated transfer wire unit
    (``serving/transfer.py`` computes its ``transfer.page_bytes`` twin
    through this same function, so the twin stays exact by construction).

    Dense pages: ``2 (K+V) * L * page_size * Hkv * D * dtype_bytes``.
    Quantized pages (``kv_dtype`` "int8"/"fp8"): 1-byte codes plus the
    per-(kv-head, page) float32 scale that is part of the page's content
    (``2 * L * Hkv * 4`` bytes — it travels with the page on the wire and
    feeds the prefix-cache hash)."""
    if kv_dtype in ("int8", "fp8"):
        data = (2 * config.num_hidden_layers * page_size
                * config.num_key_value_heads * config.head_dim)
        scales = 2 * config.num_hidden_layers * config.num_key_value_heads * 4
        return data + scales
    return (2 * config.num_hidden_layers * page_size
            * config.num_key_value_heads * config.head_dim * dtype_bytes)


def kv_pool_accounting(config, num_pages: int, page_size: int,
                       dtype_bytes: int = 2, kv_dtype: str = "") -> dict:
    """Predicted KV-HBM ladder for a pool geometry (CheckFreq-style
    predicted twin; the measured counterpart is the harness's
    ``kv_pool_utilization``).

    bytes/page is per *physical page across all layers* — the unit the
    allocator hands out: ``2 (K+V) * L * page_size * Hkv * D * dtype``
    (:func:`kv_page_bytes`; quantized pools count the 1-byte codes plus
    the per-page scales).  ``capacity_vs_bf16`` reports the quantized
    pool's token-capacity multiple at equal HBM — the ladder headline
    (~1.9-2x for int8/fp8 once ``page_size * D`` amortizes the scales)."""
    per_page = kv_page_bytes(config, page_size, dtype_bytes, kv_dtype)
    total = per_page * num_pages
    gib = lambda b: round(b / 2**30, 4)
    out = {
        "page_size_tokens": page_size,
        "num_pages": num_pages,
        "bytes_per_page": per_page,
        "pool_bytes": total,
        "pool_gib": gib(total),
        "tokens_capacity": num_pages * page_size,
        # the ladder: how much of each chip generation's HBM the pool takes
        "hbm_frac": {
            "v5e_16GiB": round(total / (16 * 2**30), 6),
            "v5p_95GiB": round(total / (95 * 2**30), 6),
            "v6e_32GiB": round(total / (32 * 2**30), 6),
        },
    }
    if kv_dtype in ("int8", "fp8"):
        bf16_page = kv_page_bytes(config, page_size, 2)
        out["kv_dtype"] = kv_dtype
        out["capacity_vs_bf16"] = round(bf16_page / per_page, 4)
        # predicted side of the kv_quant.page_bytes twin — the measured
        # side is the engine's allocated pool arrays (nbytes per page);
        # exact by construction since both route through kv_page_bytes'
        # codes+scales arithmetic
        from ..telemetry import twin_registry

        twin_registry().record_predicted(
            "kv_quant.page_bytes", per_page,
            source="serving/paged_cache.kv_pool_accounting",
        )
    return out


def cache_accounting(model, num_pages: int, page_size: int, num_slots: int,
                     pages_per_slot: int, kv_dtype=None) -> dict:
    """What a family's cache takes, by kind of layer state, read from the
    shapes ``model.init_paged_cache`` builds (nothing is allocated): arrays
    with an axis of ``num_pages`` pages (first, or second behind the kv heads)
    are paged, arrays ``[num_slots, ...]`` are kept per slot outside the
    allocator — the two kinds ``verify_serving_invariants`` admits.

    :func:`kv_pool_accounting` predicts from a configuration and assumes that
    every layer keeps K and V pages of every KV head; this counts what the
    family really builds, so it also holds for a model that keeps some layers'
    state per slot (a window layer's ring; a linear-attention layer's
    recurrent state and conv window, whose bytes are the same at any context),
    holds a share of the KV heads, or keeps more than K and V in its pages."""
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        num_pages, page_size, num_slots, pages_per_slot, kv_dtype=kv_dtype))
    pool = slot_state = paged_layers = 0
    for i, layer in enumerate(cache["layers"]):
        paged_here = 0
        for name, arr in layer.items():
            nbytes = int(np.prod(arr.shape)) * arr.dtype.itemsize
            if num_pages in arr.shape[:2]:
                paged_here += nbytes
            elif arr.shape[0] == num_slots:
                slot_state += nbytes
            else:
                raise ValueError(f"layer {i}: {name} {tuple(arr.shape)} is neither paged nor "
                                 f"slot-addressed")
        pool += paged_here
        paged_layers += paged_here > 0
    return {"page_size_tokens": page_size, "num_pages": num_pages, "paged_layers": paged_layers,
            "bytes_per_page": pool // num_pages, "pool_bytes": pool,
            "slot_state_bytes": slot_state, "tokens_capacity": num_pages * page_size}


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
