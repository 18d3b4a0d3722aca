"""Serving core: paged KV cache + continuous batching (ROADMAP item 1).

The production-inference rebuild of the reference's
``inference.py``/``big_modeling.py`` contract — see docs/serving.md:

- ``ops/paged_cache.py`` (below the models) — the pools a model builds
  (``model.init_paged_cache``, below), their functional device-side page
  allocator and every page write; :mod:`.paged_cache` — what a page and a
  pool take, on the host;
- :mod:`.scheduler` — deterministic continuous-batching policy (FIFO
  admission, chunked prefill into shape buckets, youngest-first eviction);
- :mod:`.engine` — the jitted, donation-clean prefill/decode/release
  programs and the host-driven serving loop;
- :mod:`.harness` — seeded traffic replay, serving metrics, and the
  static-batching baseline;
- :mod:`.adapters` — multi-tenant batched LoRA (ROADMAP item 2): the
  fixed-size device adapter pool with hot-swap streaming + LRU behind the
  segment-batched adapter matmul (``ops/lora.py``), and the per-adapter
  fine-tuning trainer with host-resident optimizer state;
- :mod:`.speculate` — speculative multi-token decode (draft-and-verify):
  n-gram/prompt-lookup self-drafting and draft-model providers feeding the
  engine's fixed-shape batched verify program, with the model-free
  predicted acceptance replay (the accept-rate twin);
- :mod:`.overload` — serving resilience (docs/serving.md "Overload &
  deadlines"): the SLO-driven graceful-degradation ladder and the
  :func:`~.overload.verify_serving_invariants` resource-contract checker
  behind per-request deadlines, deterministic cancellation, admission
  control/load shedding, and the :func:`~.harness.chaos_replay` soak;
- :mod:`.prefix_cache` — content-addressed COW prefix reuse (ROADMAP
  item 2's first half): full prompt-prefix pages hash-match against shared
  refcounted physical pages, chunked prefill starts at the hit boundary,
  eviction respects shared refcounts (the AdapterStore LRU rule);
- :mod:`.transfer` — the first disaggregated prefill→decode slice: two
  fixed-shape wire programs stream finished KV pages between engines, with
  the ``dcn``-axis byte-accounting twin (``transfer.page_bytes``);
- :mod:`.router` — the fleet layer (ROADMAP item 1's scale-out step): N
  replicas (fused engines or disaggregated pairs) behind deterministic
  prefix-/adapter-affinity routing with load-aware tie-breaking, fleet-wide
  degradation-ladder escalation, drain/respawn on ``replica_kill``, and
  the :func:`~.router.fleet_replay` / :func:`~.router.fleet_chaos_replay`
  harnesses (docs/serving.md "Fleet serving").

**The family protocol** — what :class:`ServingEngine` asks of the model object
it is handed (``models/llama.py``, ``models/keye_vl2.py``,
``models/k_exaone.py``, ``models/joyai_flash.py`` and ``models/qwen3_next.py``
  are the five families;
the engine imports none):

- ``init_paged_cache(num_pages, page_size, num_slots, pages_per_slot,
  kv_dtype=None)`` (required) — the cache pytree of
  ``ops/paged_cache.init_paged_pools`` around one dict of arrays per
  layer: what a layer keeps per token is the layer's kind's to say.  Two
  kinds exist.  A *paged* layer's arrays are ``[num_pages, ...]`` and the
  ONE block table addresses all of them (K and V pages, scales, an
  indexer's keys; or ONE pool of latent rows ``[num_pages, page, width]``
  with no kv-head axis, a row serving as every head's key and value).  A
  *slot-addressed* layer's arrays are ``[num_slots,
  ...]``, addressed by slot id and outside the allocator: its bytes do not
  grow with the context, and it must stay correct when a slot is handed on,
  evicted or re-admitted WITHOUT being cleared (the engine clears nothing).
  Two disciplines do so, and both are the model's.  A RING (a window
  layer's last ``window`` rows) is read only inside the owner's window, so a
  stale row is never seen.  A CUMULATIVE state (a linear-attention layer's
  recurrent state and conv window, ``models/qwen3_next.py``) is a sum over
  the whole past that nothing read later can mask: the model starts it from
  zero wherever a call's first live position is 0 and from the slot's stored
  state otherwise, and a lane whose ``cache_write_mask`` is off, or a padded
  position of a prefill bucket, leaves it as it was.  Eviction re-admits from
  position 0, so recompute rebuilds either; what would need a snapshot of a
  cumulative state (a prefix-cache hit, a speculative rollback, a page
  transfer) the family refuses by name;
- a paged ``__call__(ids, positions=, cache=, cache_write_mask=)`` that
  returns ``(logits, layers)`` or ``(logits, layers, counters)``.  Each
  layer's view in ``cache`` is the layer's arrays plus ``block_tables``
  (the block-table rows of the call's ``B`` sequences) and ``slots`` (their
  slot ids ``[B]``); the model returns the arrays, updated, per layer;
- ``tick_counters`` (optional) — ``((name, length), ...)`` of the int32
  vector such a call returns third; the engine sums it into ``metrics``,
  fetched with a decode tick's tokens;
- ``serving_refuses`` (optional) — ``{feature: what}`` for the engine
  features the family cannot be served with (``adapters``, ``kv_dtype``,
  ``speculate``, ``prefix_cache``, ``hold_finished``): asking for one raises
  at construction;
- ``prefill_writes_whole_pages`` (optional) — the prefill buckets must be
  whole pages;

What a family's cache takes, by kind of layer state, is read from the shapes
``init_paged_cache`` builds: :func:`.paged_cache.cache_accounting`.
"""

from .adapters import (
    AdapterPoolFullError,
    AdapterStore,
    LoraTrainer,
    adapter_pool_accounting,
    predicted_adapter_hit_rate,
)
from .engine import ServingEngine
from .harness import (
    chaos_replay,
    predicted_pool_utilization,
    predicted_prefix_hit_rate,
    replay,
    static_batching_report,
    synthesize_trace,
)
from .overload import DegradationLadder, verify_serving_invariants
from .prefix_cache import (
    PrefixCache,
    block_hashes,
    prefix_cache_accounting,
    unbounded_prefix_hit_rate,
)
from ..ops.paged_cache import allocate, pages_for, push_pages, release
from .paged_cache import cache_accounting, kv_pool_accounting
from .router import FleetRouter, fleet_chaos_replay, fleet_replay
from .scheduler import ContinuousBatchingScheduler, Request, SlotState
from .speculate import (
    DraftModelDraft,
    NgramDraft,
    Speculator,
    make_draft_provider,
    predicted_acceptance,
    speculative_page_need,
)
from .transfer import (
    DisaggregatedPair,
    PagedKVTransport,
    page_bytes,
    transfer_accounting,
)

__all__ = [
    "ServingEngine",
    "ContinuousBatchingScheduler",
    "Request",
    "SlotState",
    "AdapterStore",
    "AdapterPoolFullError",
    "LoraTrainer",
    "adapter_pool_accounting",
    "predicted_adapter_hit_rate",
    "allocate",
    "release",
    "push_pages",
    "pages_for",
    "cache_accounting",
    "kv_pool_accounting",
    "NgramDraft",
    "DraftModelDraft",
    "Speculator",
    "make_draft_provider",
    "predicted_acceptance",
    "speculative_page_need",
    "synthesize_trace",
    "replay",
    "chaos_replay",
    "static_batching_report",
    "predicted_pool_utilization",
    "DegradationLadder",
    "verify_serving_invariants",
    "PrefixCache",
    "block_hashes",
    "predicted_prefix_hit_rate",
    "unbounded_prefix_hit_rate",
    "prefix_cache_accounting",
    "PagedKVTransport",
    "DisaggregatedPair",
    "transfer_accounting",
    "page_bytes",
    "FleetRouter",
    "fleet_replay",
    "fleet_chaos_replay",
]
