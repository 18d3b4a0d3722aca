"""Host-side accounting of the paged cache: what a page and a pool take, read
from a configuration (:func:`kv_page_bytes`, :func:`kv_pool_accounting`) or from
the shapes a served model's ``init_paged_cache`` builds
(:func:`cache_accounting`).  Nothing here runs on the device.

The structure itself, its allocator and every page write are
``ops/paged_cache.py``, below the models; the reads through a block table are
``ops/page_walk.py``.  ``serving/__init__.py`` exports the allocator's public
names (``allocate``, ``pages_for``, ``push_pages``, ``release``) from there.
"""

from __future__ import annotations

import jax
import numpy as np


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
