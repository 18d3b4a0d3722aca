"""The serving engine: paged-KV decode + continuous batching over one model.

This is the production-scale rebuild of the reference's
``inference.py``/``big_modeling.py`` contract (PAPER.md L5): where
:func:`~accelerate_tpu.generation.generate` runs one fixed batch start-to-
finish, the engine keeps a fixed set of **decode slots** and a fixed-size
**page pool** busy under live traffic — requests are admitted, chunk-
prefilled, decoded and retired *per step*, so a finished short request's
slot and pages immediately serve the next arrival instead of padding out
the longest sequence in the batch.

Execution contract:

- every device step is one of THREE jitted programs with **fixed shapes**
  (one decode shape, one prefill shape per bucket, one release shape) — no
  recompiles mid-traffic;
- the cache pytree is **donated** through every step: pools update in place
  (graft-lint GL101/GL201-clean — ``audit_decode_step`` checks on demand);
- the decode loop is host-driven (tokens must surface per step for EOS/
  stop handling anyway — the same shape as ``generate_streamed``'s loop);
- sampling reuses :func:`~accelerate_tpu.generation.sample_logits`, so
  greedy serving emits tokens identical to ``generate()`` (pinned by
  tests/test_serving.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.compiled_audit import PARTS as _WARMUP_PARTS
from ..analysis.compiled_audit import install_global_compile_counter
from ..generation import GenerationConfig, sample_logits
from ..resilience import faults as _faults
from ..telemetry import HostLedger, RequestTracer
from ..utils.dataclasses import ServingPlugin, TelemetryPlugin
from .overload import DegradationLadder
from ..ops.paged_cache import allocate, pages_for, push_pages, release
from .prefix_cache import PrefixCache
from .scheduler import ContinuousBatchingScheduler, Request, SlotState
from .speculate import Speculator, make_draft_provider, speculative_page_need


# latency samples kept for the harness's percentiles (the newest; the
# whole-run sums are in ``ServingEngine.metrics``)
_SAMPLE_WINDOW = 4096

def _layer_view(layer, block_tables, slots):
    """One layer's model-facing cache dict: every array the layer's kind keeps
    (K and V pages; ``k_scales`` / ``v_scales`` of quantized pools, which the
    model detects to route quantize-on-write / dequant-on-read; an indexer's
    ``index_pages``; a slot-addressed kind's per-slot state) plus how a call
    addresses them: the block-table rows of the call's sequences, and their
    slot ids ``[B]`` for state that is kept per slot and not in pages."""
    return {**layer, "block_tables": block_tables, "slots": slots}


def _layer_keep(layer):
    """The engine-side carry of one layer returned by the model (drop the
    per-step addressing, keep the state)."""
    return {k: v for k, v in layer.items() if k not in ("block_tables", "slots")}


def _with_counters(cache, new_cache, aux, tokens=None):
    """A family whose paged call returns a counter vector (``tick_counters``
    on the model) keeps it pending in the cache; a program that hands tokens
    to the host (decode) appends the pending sum to them — one transfer — and
    clears it.  No ``aux``: the program is unchanged."""
    if not aux:
        return new_cache, tokens
    pending = cache["tick_counters"] + aux[0]
    if tokens is None:
        return {**new_cache, "tick_counters": pending}, None
    return ({**new_cache, "tick_counters": jnp.zeros_like(pending)},
            jnp.concatenate([tokens, pending]))


def _engine_step_fns(model, gen_config, page_size: int, lora: bool = False,
                     lora_kernel_mode: str = "auto"):
    """The raw (un-jitted) device-program bodies.  :func:`_engine_fns`
    wraps them in the process-shared jit cache for serving;
    :func:`fresh_engine_jits` wraps them fresh for the deploy preflight,
    whose executable-level stats must come from a real compile.

    With ``lora=True`` (multi-tenant mode) decode/prefill additionally take
    the adapter pool (the ``lora`` variable collection — read-only here;
    the AdapterStore's donated insert program owns its mutation) and the
    per-slot adapter ids.  The ids are **normal array arguments**: any
    tenant mix reuses the same compiled program (the fixed-shape contract
    ``strict_compiles`` enforces).  ``lora_kernel_mode`` is applied as a
    SCOPED override around every trace (and keys the program cache), so
    two engines with different kernel knobs never share a traced program
    and engine construction never retargets the process-global mode."""
    if lora:
        from ..ops.lora import lora_kernel

        raw_apply = model.apply

        def apply(*args, **kwargs):
            with lora_kernel(lora_kernel_mode):
                return raw_apply(*args, **kwargs)
    else:
        apply = model.apply

    def decode_step(params, lora_pool, cache, tokens, active, adapter_slots, rng):
        # one token for every slot at once; dead slots write nowhere and
        # their sampled token is ignored by the host
        seq_lens = cache["seq_lens"]
        pos = seq_lens
        n_slots = tokens.shape[0]
        need = active & (pos % page_size == 0)
        slot_ids = jnp.arange(n_slots, dtype=jnp.int32)
        block_tables, free_top = allocate(
            cache["block_tables"], cache["free_stack"], cache["free_top"],
            slot_ids, pos // page_size, need,
        )
        layer_caches = [_layer_view(l, block_tables, slot_ids) for l in cache["layers"]]
        variables = {**params, "lora": lora_pool} if lora else params
        kwargs = {"adapter_ids": adapter_slots} if lora else {}
        logits, new_layers, *aux = apply(
            variables, tokens[:, None], positions=pos[:, None],
            cache=layer_caches, cache_write_mask=active[:, None], **kwargs,
        )
        next_tok = sample_logits(logits[:, 0], rng, gen_config)
        new_cache = {
            **cache,
            "layers": [_layer_keep(l) for l in new_layers],
            "block_tables": block_tables,
            "seq_lens": seq_lens + active.astype(jnp.int32),
            "free_top": free_top,
        }
        return _with_counters(cache, new_cache, aux, next_tok)

    def prefill_step(params, lora_pool, cache, slot, chunk_ids, start, chunk_len,
                     adapter_slot):
        # one bucket-padded chunk of one sequence's prompt; returns the
        # logits of the chunk's last REAL token (the decode-loop seed once
        # the prompt completes)
        width = chunk_ids.shape[0]
        positions = start + jnp.arange(width, dtype=jnp.int32)
        wmask = jnp.arange(width) < chunk_len
        need = wmask & (positions % page_size == 0)
        block_tables, free_top = allocate(
            cache["block_tables"], cache["free_stack"], cache["free_top"],
            jnp.full((width,), slot, jnp.int32), positions // page_size, need,
        )
        row = jax.lax.dynamic_slice_in_dim(block_tables, slot, 1, axis=0)
        layer_caches = [_layer_view(l, row, jnp.reshape(slot, (1,))) for l in cache["layers"]]
        variables = {**params, "lora": lora_pool} if lora else params
        kwargs = {"adapter_ids": jnp.reshape(adapter_slot, (1,))} if lora else {}
        logits, new_layers, *aux = apply(
            variables, chunk_ids[None], positions=positions[None],
            cache=layer_caches, cache_write_mask=wmask[None], **kwargs,
        )
        last = jnp.take(logits[0], chunk_len - 1, axis=0)
        new_cache = {
            **cache,
            "layers": [_layer_keep(l) for l in new_layers],
            "block_tables": block_tables,
            "seq_lens": cache["seq_lens"].at[slot].set(start + chunk_len),
            "free_top": free_top,
        }
        return _with_counters(cache, new_cache, aux)[0], last

    def verify_step(params, lora_pool, cache, tokens, spec_len, active,
                    adapter_slots, rng):
        # speculative draft-and-verify: ONE fixed-shape pass of width
        # w = bucket + 1 per active slot — lane 0 is the slot's last sampled
        # token (the plain decode input), lanes 1..spec_len its draft
        # proposals.  The pass (1) pops worst-case fresh pages for every
        # page-start among its candidate positions (multi-token paged
        # append: up to ceil(w/page)+1 block-table scatters per slot),
        # (2) writes K/V for the live lanes and computes the greedy target
        # token per lane through the same ragged paged attention the decode
        # step uses, (3) accepts the longest greedy-matching draft prefix,
        # and (4) rolls the pages past the accepted frontier back onto the
        # functional free-list — all inside the one donated jitted program.
        # Accepted tokens are BITWISE what sequential decode would emit.
        seq_lens = cache["seq_lens"]
        n, w = tokens.shape
        lane = jnp.arange(w, dtype=jnp.int32)
        positions = seq_lens[:, None] + lane[None, :]
        live = active[:, None] & (lane[None, :] <= spec_len[:, None])
        logical = positions // page_size
        need = live & (positions % page_size == 0)
        slot_ids = jnp.arange(n, dtype=jnp.int32)
        block_tables, free_top = allocate(
            cache["block_tables"], cache["free_stack"], cache["free_top"],
            jnp.repeat(slot_ids, w),
            logical.reshape(-1), need.reshape(-1),
        )
        layer_caches = [_layer_view(l, block_tables, slot_ids) for l in cache["layers"]]
        variables = {**params, "lora": lora_pool} if lora else params
        kwargs = {"adapter_ids": adapter_slots} if lora else {}
        logits, new_layers = apply(
            variables, tokens, positions=positions,
            cache=layer_caches, cache_write_mask=live, **kwargs,
        )
        # the exact sampling path decode uses (greedy: argmax over fp32) —
        # the token-parity pin is this shared code path, not a reimplementation
        greedy = sample_logits(
            logits.reshape(n * w, logits.shape[-1]), rng, gen_config
        ).reshape(n, w)
        # longest greedy-matching prefix: draft j accepted iff it equals the
        # target's token after consuming drafts 1..j-1 (greedy[:, j-1])
        match = (tokens[:, 1:] == greedy[:, :-1]) & \
            (lane[None, 1:] <= spec_len[:, None])
        m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        new_seq_lens = seq_lens + jnp.where(active, m + 1, 0)
        # rollback: pages grabbed for positions past the accepted frontier
        # return to the stack (their stale K/V is unreadable — the next pass
        # rewrites any position before the positional mask can admit it)
        give_back = need & (positions >= new_seq_lens[:, None])
        pages = jnp.take_along_axis(
            block_tables, jnp.clip(logical, 0, block_tables.shape[1] - 1),
            axis=1,
        )
        free_stack, free_top = push_pages(
            cache["free_stack"], free_top, pages.reshape(-1),
            give_back.reshape(-1),
        )
        new_cache = {
            "layers": [_layer_keep(l) for l in new_layers],
            "block_tables": block_tables,
            "seq_lens": new_seq_lens,
            "free_stack": free_stack,
            "free_top": free_top,
        }
        return new_cache, greedy, m

    def release_step(cache, mask):
        seq_lens, free_stack, free_top = release(
            cache["block_tables"], cache["seq_lens"], cache["free_stack"],
            cache["free_top"], mask, page_size,
        )
        return {**cache, "seq_lens": seq_lens, "free_stack": free_stack,
                "free_top": free_top}

    def sample_first(last, rng):
        return sample_logits(last[None], rng, gen_config)[0]

    if lora:
        return decode_step, prefill_step, release_step, sample_first, verify_step

    # single-tenant mode keeps the original program arity (the preflight
    # and every existing caller compile these signatures)
    def decode_legacy(params, cache, tokens, active, rng):
        return decode_step(params, None, cache, tokens, active, None, rng)

    def prefill_legacy(params, cache, slot, chunk_ids, start, chunk_len):
        return prefill_step(params, None, cache, slot, chunk_ids, start,
                            chunk_len, None)

    def verify_legacy(params, cache, tokens, spec_len, active, rng):
        return verify_step(params, None, cache, tokens, spec_len, active,
                           None, rng)

    return decode_legacy, prefill_legacy, release_step, sample_first, verify_legacy


def fresh_engine_jits(model, gen_config, page_size: int, lora: bool = False,
                      lora_kernel_mode: str = "auto"):
    """FRESH jit wrappers over the engine program bodies — deliberately
    outside the shared :func:`_engine_fns` cache.  The deploy preflight
    compiles through these: a wrapper another engine already drove may hold
    an executable deserialized from the persistent compilation cache, and
    deserialized executables LOSE their buffer-donation alias table
    (``memory_analysis().alias_size_in_bytes`` reads 0), which would turn
    every healthy donation into a GL301 false positive.

    Returns ``(decode, prefill, release, sample_first, verify)`` — one
    jitted ``verify`` covers the whole speculative bucket ladder (width is
    a trace-time shape, exactly like the prefill buckets)."""
    decode_step, prefill_step, release_step, sample_first, verify_step = \
        _engine_step_fns(model, gen_config, page_size, lora, lora_kernel_mode)
    cache_arg = 2 if lora else 1
    return (
        jax.jit(decode_step, donate_argnums=(cache_arg,)),
        jax.jit(prefill_step, donate_argnums=(cache_arg,)),
        jax.jit(release_step, donate_argnums=(0,)),
        jax.jit(sample_first),
        jax.jit(verify_step, donate_argnums=(cache_arg,)),
    )


def _prefix_step_fns(page_size: int):
    """The prefix-cache device programs (model-free — pure allocator
    arithmetic on the cache pytree, keyed by page geometry only):

    - ``adopt_step`` writes an admission's shared page ids into the slot's
      block-table row prefix and pins ``seq_lens[slot]`` at the hit
      boundary (the region chunked prefill will skip; no free-stack touch —
      shared pages were never free);
    - ``release_cow_step`` is the keep-aware COW release: per released slot
      it pushes ONLY the pages past ``keep_counts[slot]`` (the slot's
      shared prefix stays off the stack — the host refcounts decide when an
      aliased page actually frees);
    - ``push_free_step`` pushes an explicit masked id set (refcount-zero
      deaths + LRU reclaims the host queued) — the device half of
      ``PrefixCache.pop_pending``'s double-free guard.
    """

    def adopt_step(cache, slot, page_ids, n_shared):
        npp = cache["block_tables"].shape[1]
        keep = jnp.arange(npp, dtype=jnp.int32) < n_shared
        row = jax.lax.dynamic_slice_in_dim(cache["block_tables"], slot, 1)[0]
        row = jnp.where(keep, page_ids, row)
        block_tables = jax.lax.dynamic_update_slice_in_dim(
            cache["block_tables"], row[None], slot, 0
        )
        # (``**cache``: a family's extras, e.g. pending ``tick_counters``, ride along)
        return {
            **cache,
            "block_tables": block_tables,
            "seq_lens": cache["seq_lens"].at[slot].set(n_shared * page_size),
        }

    def release_cow_step(cache, mask, keep_counts):
        mask = mask.astype(bool)
        n = cache["block_tables"].shape[1]
        logical = jnp.arange(n, dtype=jnp.int32)[None, :]
        owned = mask[:, None] & (logical >= keep_counts[:, None]) & (
            logical < pages_for(cache["seq_lens"], page_size)[:, None]
        )
        free_stack, free_top = push_pages(
            cache["free_stack"], cache["free_top"],
            cache["block_tables"].reshape(-1), owned.reshape(-1),
        )
        return {
            **cache,
            "seq_lens": jnp.where(mask, 0, cache["seq_lens"]),
            "free_stack": free_stack,
            "free_top": free_top,
        }

    def push_free_step(cache, page_ids, mask):
        free_stack, free_top = push_pages(
            cache["free_stack"], cache["free_top"], page_ids, mask
        )
        return {**cache, "free_stack": free_stack, "free_top": free_top}

    return adopt_step, release_cow_step, push_free_step


@lru_cache(maxsize=8)
def _prefix_fns(page_size: int):
    """Jitted (donated) prefix-cache programs, shared per page geometry —
    each compiles exactly once per process per slot-count shape."""
    adopt_step, release_cow_step, push_free_step = _prefix_step_fns(page_size)
    return (
        jax.jit(adopt_step, donate_argnums=(0,)),
        jax.jit(release_cow_step, donate_argnums=(0,)),
        jax.jit(push_free_step, donate_argnums=(0,)),
    )


@lru_cache(maxsize=8)
def _engine_fns(model, gen_config, page_size: int, lora: bool = False,
                lora_kernel_mode: str = "auto"):
    """The jitted device programs, shared across engines of the same
    (model, config, page geometry, lora kernel) — jax.jit caches per input
    shape, so bucket widths and slot counts each compile exactly once per
    process."""
    return fresh_engine_jits(model, gen_config, page_size, lora, lora_kernel_mode)


class ServingEngine:
    """Continuous-batching serving over one model + param tree.

    >>> engine = ServingEngine(model, params, plugin, generation_config)
    >>> engine.add_request(Request(uid=0, prompt=(1, 2, 3), max_new_tokens=8))
    >>> while not engine.idle():
    ...     engine.step()
    >>> engine.results[0]  # generated token ids

    ``run(trace)`` replays a list of :class:`~.scheduler.Request` with
    virtual-time arrivals (the traffic-replay harness's entry point).
    """

    def __init__(self, model, params, plugin: Optional[ServingPlugin] = None,
                 generation_config: Optional[GenerationConfig] = None, rng=None,
                 adapters=None, telemetry: Optional[TelemetryPlugin] = None,
                 draft_model=None, draft_params=None,
                 hold_finished: bool = False):
        self.plugin = plugin or ServingPlugin()
        self.gen_config = generation_config or GenerationConfig()
        if getattr(getattr(model, "config", None), "scan_layers", False):
            from ..generation import _unrolled_view

            model, params = _unrolled_view(model, params)
        cfg = model.config
        kernel = self.plugin.decode_kernel
        if kernel == "auto":
            kernel = "flash" if jax.default_backend() == "tpu" else "native"
        # (a family whose paged programs have one implementation has no such field)
        if getattr(cfg, "attn_implementation", kernel) != kernel and kernel in ("native", "flash"):
            cfg = dataclasses.replace(cfg, attn_implementation=kernel)
            model = model.clone(config=cfg) if hasattr(model, "clone") else type(model)(cfg)
        self.model = model
        self.params = params
        # multi-tenant mode: the AdapterStore's pool rides every decode/
        # prefill program as a read-only extra arg, and per-slot adapter
        # ids route each row through its tenant's adapter (ops/lora.py);
        # the plugin's kernel mode scopes the program traces (it never
        # touches the process-global ambient mode)
        self.adapters = adapters
        p = self.plugin
        self._refuse_unsupported(adapters, hold_finished)
        # what a layer stores per token is the layer's kind's to say: the
        # model builds its pools (the family protocol, serving/__init__.py)
        # around the one block table and free stack of init_paged_pools
        self.cache = model.init_paged_cache(
            p.num_pages, p.page_size, p.num_slots, p.pages_per_slot, kv_dtype=p.kv_dtype)
        # counters the family's programs hand back with a decode tick's tokens
        self._tick_counters = tuple(getattr(model, "tick_counters", ()))
        if p.kv_dtype in ("int8", "fp8"):
            # measured side of the kv_quant.page_bytes twin: the pool
            # arrays as actually allocated (codes + per-page scales),
            # counted per physical page — the predicted side is
            # kv_pool_accounting's kv_page_bytes arithmetic
            pool_nbytes = sum(
                int(arr.nbytes) for layer in self.cache["layers"]
                for arr in layer.values()
            )
            from ..telemetry import twin_registry

            twin_registry().record_measured(
                "kv_quant.page_bytes", pool_nbytes / p.num_pages,
                source="serving/engine.ServingEngine",
            )
        # speculative multi-token decode (serving/speculate.py): a draft
        # provider proposes k tokens per slot and the verify program accepts
        # the longest greedy-matching prefix — greedy only, because the
        # acceptance rule IS the token-parity pin (a sampled verify would
        # need rejection sampling, a different contract)
        self._spec: Optional[Speculator] = None
        if p.speculate != "off":
            if self.gen_config.do_sample:
                raise ValueError(
                    "speculative decode supports greedy decoding only "
                    "(do_sample=True breaks the greedy-prefix acceptance "
                    "pin) — disable ServingPlugin.speculate or sampling"
                )
            provider = make_draft_provider(
                p.speculate, draft_model=draft_model, draft_params=draft_params,
                window=p.speculate_draft_window,
            )
            self._spec = Speculator(provider, p.speculate_k, p.speculate_buckets)
        # content-addressed prefix reuse (serving/prefix_cache.py): COW
        # shared pages with host-side refcounts; the three extra device
        # programs (adopt / keep-aware COW release / push-free) are pure
        # allocator arithmetic keyed by page geometry
        self.prefix: Optional[PrefixCache] = None
        if p.prefix_cache == "on":
            self.prefix = PrefixCache(p.page_size, kv_dtype=p.kv_dtype)
            self._adopt, self._release_cow, self._push_free = _prefix_fns(
                p.page_size
            )
        self.sched = ContinuousBatchingScheduler(
            p.num_slots, p.num_pages, p.page_size, p.pages_per_slot,
            p.prefill_chunk, p.prefill_buckets,
            adapters=adapters,
            max_bypass_age=(adapters.plugin.max_bypass_age
                            if adapters is not None else 16),
            speculate_k=p.speculate_k if self._spec is not None else 0,
            max_queue=p.max_queue, kv_shed_watermark=p.kv_shed_watermark,
            default_deadline_ticks=p.default_deadline_ticks,
            prefix=self.prefix,
        )
        # overload control (serving/overload.py): the degradation ladder is
        # always armed (escalation is explicit — an SLO trip, a deadline
        # storm, or an operator call; every stage reuses warmed programs so
        # strict_compiles holds through the full ladder), and cancellation
        # requests queue here until the next tick boundary processes them
        self.despeculated = False
        self.ladder = DegradationLadder(self)
        self.slo = None                      # optional attached SLOMonitor
        self._pending_cancels: list[int] = []
        (self._decode, self._prefill, self._release, self._sample,
         self._verify) = _engine_fns(
            self.model, self.gen_config, p.page_size, adapters is not None,
            adapters.plugin.kernel if adapters is not None else "auto",
        )
        self._base_rng = rng if rng is not None else jax.random.PRNGKey(0)
        # request-level trace spans (telemetry/spans.py): host-side only —
        # zero added device syncs, no new compiled programs, tokens bitwise
        # identical on or off (pinned by tests + the dryrun telemetry leg).
        # A single attribute check per hook when off.
        self.telemetry = telemetry or TelemetryPlugin()
        self._clock = time.perf_counter   # the scheduler's stamps share it
        self.trace: Optional[RequestTracer] = None
        # recompile guard: compile events are counted process-wide (the
        # jax.monitoring backend-compile stream) and reported as a delta
        # from engine construction — after warmup() this must stay flat
        # (the fixed-shape contract: a mid-traffic compile is a bug)
        self._compile_counter = install_global_compile_counter()
        self._compile_baseline = self._compile_counter.count
        self.warmed_up = False
        self.steps = 0
        self.interrupted = False
        # disaggregation (serving/transfer.py): a prefill-role engine holds
        # finished slots — pages intact — until the transport streams them
        # to the decode engine and calls release_held()
        self.hold_finished = hold_finished
        self.held: list[int] = []
        self._undelivered: list[Request] = []
        self.results: dict[int, list[int]] = {}
        self._arrival_wall: dict[int, float] = {}
        self._last_token_wall: dict[int, float] = {}
        self._ttft_seen: set[int] = set()
        self._dispatch_seen: set[int] = set()
        self.metrics = {
            "decode_steps": 0, "prefill_steps": 0, "idle_steps": 0,
            "scheduled_decode_slots": 0, "useful_decode_tokens": 0,
            "prefill_scheduled_tokens": 0, "prefill_useful_tokens": 0,
            "evictions": 0, "page_step_sum": 0, "peak_used_pages": 0,
            "prompt_tokens": 0, "generated_tokens": 0,
            # speculative decode (zeros-clean when speculation is off):
            # verify passes, drafted/accepted lanes, per-lane pass count +
            # emitted tokens (the tokens_per_step twin's numerator and
            # denominator), and pages rolled back off rejected drafts
            "verify_steps": 0, "draft_tokens": 0, "accepted_draft_tokens": 0,
            "decode_lane_passes": 0, "decode_emitted_tokens": 0,
            "speculative_rollbacks": 0,
            # disaggregation (zeros unless a PagedKVTransport streams KV
            # pages out of / into this engine — serving/transfer.py)
            "page_transfers": 0, "page_transfer_pages": 0,
            "page_transfer_bytes": 0,
            # always-on latency counters, from the stamps add_request and
            # the first dispatch / first token take (seconds on the
            # engine's clock, once per request): submit -> first prefill
            # dispatch, and submit -> first token on the host
            "queue_wait_s_sum": 0.0, "queue_wait_n": 0,
            "ttft_s_sum": 0.0, "ttft_n": 0,
            # the family's device-side counters (Keye-VL2: expert_tokens [E],
            # moe_experts_hit_sum, moe_ticks, sparse_selected_sum,
            # sparse_visible_sum), summed on the host as they arrive
            **{name: (0 if size == 1 else np.zeros((size,), np.int64))
               for name, size in self._tick_counters},
        }
        # the host ledger (telemetry/host_ledger.py), always on: every phase
        # of step() clocked per tick kind, the caller's time between ticks,
        # the collector's pauses, and the stall log — flat keys in
        # ``metrics``; the slow ticks themselves, newest 64, in ``stalls``.
        # warmup() leaves a row a program in ``warmup_report`` and the sums
        # ``warmup_*`` in ``metrics``
        self._ledger = HostLedger(self.metrics, self._clock)
        self.stalls = self._ledger.stalls
        self.warmup_report: list[dict] = []
        if self.telemetry.trace_requests:
            self.enable_tracing()
        self.ttft_s: deque[float] = deque(maxlen=_SAMPLE_WINDOW)
        # TTFT in VIRTUAL engine ticks (arrival -> first token), the
        # deterministic twin of the wall-clock ttft_s samples: the prefix
        # cache's with/without-reuse comparison pins on these (wall clocks
        # flake on CPU; tick counts replay identically)
        self.ttft_ticks: list[int] = []
        self.token_gaps_s: deque[float] = deque(maxlen=_SAMPLE_WINDOW)

    # -- telemetry -----------------------------------------------------------

    def enable_tracing(self, clock=None, capacity: Optional[int] = None) -> RequestTracer:
        """Arm request-level trace spans (idempotent unless ``clock`` or
        ``capacity`` is passed, which installs a fresh tracer).  ``clock``
        injects a deterministic timestamp source
        (:class:`~accelerate_tpu.telemetry.VirtualClock`) for tests; the
        default is wall ``perf_counter``.  Host-side only — arming this
        changes no token and compiles no program."""
        if self.trace is None or clock is not None or capacity is not None:
            self.trace = RequestTracer(
                capacity=capacity or self.telemetry.ring_capacity, clock=clock,
            )
            # one clock for the spans, the stamps they are built from and
            # the host ledger
            self._clock = self.sched.clock = self.trace.recorder.clock
            self._ledger.set_clock(self._clock, self.trace.recorder)
        return self.trace

    def disable_tracing(self) -> None:
        self.trace = None
        self._clock = self.sched.clock = time.perf_counter
        self._ledger.set_clock(self._clock)

    # -- request lifecycle ---------------------------------------------------

    def add_request(self, request: Request) -> None:
        now = self._clock()
        self.sched.submit(request, now)
        self._arrival_wall[request.uid] = now
        self._ledger.note_add_request(now, self._clock())

    def cancel(self, uid: int) -> None:
        """Request cancellation of ``uid`` at whatever lifecycle stage it is
        in (queued, mid-prefill-chunk, decoding, or mid-speculative-verify).
        Processed at the next tick boundary — the engine's device programs
        are atomic per tick, so the boundary is the only place every
        resource (KV pages, adapter refcount, slot, speculative state) can
        be released consistently.  Idempotent; unknown/finished uids are
        dropped silently.  A cancel pending at a preemption drain is still
        owed: :meth:`remaining_requests` hands the request back exactly
        once."""
        if uid not in self._pending_cancels:
            self._pending_cancels.append(uid)

    def adopt_prefilled(self, request: Request, first_token: int) -> int:
        """Decode-role half of the disaggregated handoff
        (serving/transfer.py): occupy a free slot for a request whose
        prompt was prefilled on ANOTHER engine, whose first token is
        already sampled, and whose KV pages the transport's ``recv``
        program is about to scatter into this pool.  The host mirror books
        ``pages_for(prompt_len)`` pages (the recv program pops exactly
        those); decode proceeds through the ordinary tick loop from the
        first generated token on.  Returns the slot id."""
        sched = self.sched
        if not sched.free_slots:
            raise RuntimeError("adopt_prefilled: no free decode slot")
        n_pages = int(pages_for(request.prompt_len, self.plugin.page_size))
        if n_pages > sched.free_pages:
            raise RuntimeError(
                f"adopt_prefilled: request {request.uid} needs {n_pages} "
                f"pages, pool has {sched.free_pages} free"
            )
        slot = sched.free_slots.pop(0)
        st = SlotState(request, sched._admit_counter,
                       prefilled=request.prompt_len)
        st.tokens = [int(first_token)]
        if self.adapters is not None and request.adapter_id:
            # adapter routing across the split: the decode role pins the
            # tenant's adapter in ITS pool (the prefill role's pin released
            # with the held slot) — the normal finish path unpins, so the
            # refcount contract balances per engine
            adapter_slot, swapped = self.adapters.pin(request.adapter_id)
            st.adapter_slot = adapter_slot
            if swapped:
                sched.events.append(("swap", request.adapter_id, adapter_slot))
        sched.slots[slot] = st
        sched._admit_counter += 1
        sched.free_pages -= n_pages
        sched.events.append(("admit", request.uid, slot))
        # the prefill engine delivered the first token — TTFT is its story
        now = self._clock()
        self._arrival_wall[request.uid] = now
        self._last_token_wall[request.uid] = now
        self._ttft_seen.add(request.uid)
        self._dispatch_seen.add(request.uid)
        return slot

    def release_held(self, slot: int) -> None:
        """Prefill-role half of the handoff: retire a held finished slot
        once its pages have been streamed out (device release first, then
        the host mirror — the ordering every retirement path uses)."""
        self.held.remove(slot)
        self._release_slots([slot])
        self.sched.finish(slot)
        self._drain_prefix_frees()

    def attach_slo(self, monitor) -> "DegradationLadder":
        """Feed per-token latency and TTFT samples into ``monitor`` as they
        are measured and wire its trip/recover callbacks to the degradation
        ladder (trip → escalate one stage, recover → relax one).  Returns
        the ladder for inspection."""
        self.slo = monitor
        self.ladder.attach(monitor)
        return self.ladder

    def idle(self) -> bool:
        return self.sched.idle()

    def unfinished_requests(self) -> list[Request]:
        """Everything not yet finished — in admission order then queue order
        (prompt intact, generated tokens discarded: the recompute-on-resume
        contract a preemption drain relies on)."""
        in_flight = [
            self.sched.slots[s].request
            for s in sorted(self.sched.slots, key=lambda s: self.sched.slots[s].admit_seq)
        ]
        return in_flight + list(self.sched.waiting)

    def remaining_requests(self) -> list[Request]:
        """After a drain: everything still owed — in-flight + queued + trace
        arrivals the replay never delivered — **deduplicated by uid** and
        excluding deliberately retired requests (shed / cancelled).  A
        request whose :meth:`cancel` is still pending (the drain interrupted
        before the tick boundary could process it) has NOT been retired and
        is handed back exactly once; a processed cancel never comes back."""
        retired = self.sched.retired_uids
        out, seen = [], set()
        for r in self.unfinished_requests() + list(self._undelivered):
            if r.uid in retired or r.uid in self.results or r.uid in seen:
                continue
            seen.add(r.uid)
            out.append(r)
        return out

    # -- program dispatch (single-tenant vs multi-tenant arity) --------------

    def _run_decode(self, tokens, active, adapter_slots, rng):
        self._drain_prefix_frees()
        if self.adapters is None:
            return self._decode(self.params, self.cache, tokens, active, rng)
        return self._decode(self.params, self.adapters.pool, self.cache,
                            tokens, active, adapter_slots, rng)

    def _run_prefill(self, slot, chunk_ids, start, chunk_len, adapter_slot):
        self._drain_prefix_frees()
        if self.adapters is None:
            return self._prefill(self.params, self.cache, slot, chunk_ids,
                                 start, chunk_len)
        return self._prefill(self.params, self.adapters.pool, self.cache,
                             slot, chunk_ids, start, chunk_len, adapter_slot)

    def _run_verify(self, tokens, spec_len, active, adapter_slots, rng):
        self._drain_prefix_frees()
        if self.adapters is None:
            return self._verify(self.params, self.cache, tokens, spec_len,
                                active, rng)
        return self._verify(self.params, self.adapters.pool, self.cache,
                            tokens, spec_len, active, adapter_slots, rng)

    # -- the engine tick -----------------------------------------------------

    def warmup(self) -> int:
        """Compile every device program before taking traffic: one no-op
        pass through decode, release, and each bucket's prefill (plus the
        first-token sampler), using the engine's real cache and params so
        every shape/dtype matches live traffic exactly.  No-op means no
        slot state changes: decode runs with zero active slots, prefill
        writes a zero-length chunk into an idle slot, release releases an
        empty mask — tokens are never recorded and ``steps`` does not
        advance.  Returns the number of backend compile events the warmup
        cost (0 when the persistent compilation cache was already warm).

        Where the time went is left behind: :attr:`warmup_report` has a row a
        program (the labels of :meth:`warmup_programs`: ``wall_s`` and of it
        ``trace_s``, ``lower_s``, ``backend_s`` (compiling), ``cache_load_s``
        (reading the persistent cache), ``execute_s`` (the rest), and
        ``cache``: ``hit``, ``miss`` or ``none``), ``metrics`` the sums
        ``warmup_trace_s`` ... ``warmup_cache_misses`` and the call's
        ``warmup_wall_s``; with a tracer armed each program is also a
        ``warmup:<label>`` span on the ``warmup`` track.

        Call before traffic (the replay harness does); after it,
        :attr:`compile_events` staying flat IS the no-mid-traffic-recompile
        contract.
        """
        if self.sched.slots:
            raise RuntimeError("warmup() must run before any traffic is admitted")
        before = self._compile_counter.count
        parts0, t_call = self._compile_counter.parts(), self._clock()
        rows: dict[str, dict] = {}
        warming = partial(self._warming, rows)
        n = self.plugin.num_slots

        def decode_pass():
            cache, _ = self._run_decode(
                jnp.asarray(np.zeros((n,), np.int32)),
                jnp.asarray(np.zeros((n,), bool)),
                jnp.asarray(np.zeros((n,), np.int32)), rng,
            )
            self.cache = cache

        with warming("decode"):
            rng = jax.random.fold_in(self._base_rng, 0)  # warms the fold_in program
            decode_pass()
        last = None
        for bucket in self.plugin.prefill_buckets:
            with warming(f"prefill[{bucket}]"):
                cache, last = self._run_prefill(
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(np.zeros((bucket,), np.int32)),
                    jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                )
                self.cache = cache
        with warming("sample_first"):
            if last is not None:
                self._sample(last, rng)
        if self._spec is not None:
            # every verify bucket is a production program: one no-op pass
            # per width (zero active slots, zero spec depth), plus the draft
            # provider's own program (the draft-model windowed forward; the
            # n-gram provider compiles nothing)
            for bucket in self.plugin.speculate_buckets:
                with warming(f"verify[{bucket}]"):
                    cache, _, _ = self._run_verify(
                        jnp.asarray(np.zeros((n, bucket + 1), np.int32)),
                        jnp.asarray(np.zeros((n,), np.int32)),
                        jnp.asarray(np.zeros((n,), bool)),
                        jnp.asarray(np.zeros((n,), np.int32)), rng,
                    )
                    self.cache = cache
            with warming("draft_provider"):
                self._spec.provider.warmup(n, self.plugin.speculate_k)
        if self.prefix is not None:
            # the three prefix programs are production programs: a first
            # hit / COW release / refcount-death push mid-traffic must hit
            # a warm cache (no-op passes: zero shared pages, empty masks)
            pps = self.plugin.pages_per_slot
            with warming("prefix_adopt"):
                self.cache = self._adopt(
                    self.cache, jnp.asarray(0, jnp.int32),
                    jnp.asarray(np.zeros((pps,), np.int32)),
                    jnp.asarray(0, jnp.int32),
                )
            with warming("prefix_release_cow"):
                self.cache = self._release_cow(
                    self.cache, jnp.asarray(np.zeros((n,), bool)),
                    jnp.asarray(np.zeros((n,), np.int32)),
                )
            with warming("prefix_push_free"):
                self.cache = self._push_free(
                    self.cache, jnp.asarray(np.zeros((pps,), np.int32)),
                    jnp.asarray(np.zeros((pps,), bool)),
                )
        else:
            with warming("release"):
                self.cache = self._release(
                    self.cache, jnp.asarray(np.zeros((n,), bool))
                )
        # Decode compiled FIRST, against the fresh host-built cache — but
        # every program OUTPUT carries the steady-state placement GSPMD
        # chose (under a mesh-sharded param tree the KV pools come back
        # tp-sharded, not replicated).  One more no-op decode warms the
        # program against THAT layout, so the first post-warmup decode —
        # plain serving under sharded params, or the ladder's despeculate
        # stage re-entering decode after verify — can never recompile
        # mid-traffic.
        with warming("decode"):
            decode_pass()
        if self.adapters is not None:
            # the pool-insert scatter is a fixed-shape production program
            # too: a first hot-swap mid-traffic must hit a warm cache
            with warming("adapter_insert"):
                self.adapters.warmup_insert()
        # the no-op passes are still running on the device: the engine is
        # warm when the last of them has ended
        jax.block_until_ready(self.cache)
        self.warmup_report = list(rows.values())
        m = self.metrics
        m["warmup_wall_s"] = self._clock() - t_call
        for key, a, b in zip(_WARMUP_PARTS, parts0, self._compile_counter.parts()):
            m[f"warmup_{key}"] = b - a
        self.warmed_up = True
        return self._compile_counter.count - before

    @contextlib.contextmanager
    def _warming(self, rows: dict, label: str):
        """Bracket one of :meth:`warmup`'s programs: its wall time on the
        engine's clock and the compile counter's deltas, added to the row of
        ``label`` (decode runs twice: one row).  ``execute_s`` is the rest of
        the wall: staging, dispatch, and whatever of an earlier pass the call
        waited for."""
        counter = self._compile_counter
        parts0, t0 = counter.parts(), self._clock()
        try:
            yield
        finally:
            t1 = self._clock()
            row = rows.setdefault(label, {"label": label, "wall_s": 0.0,
                                          **dict.fromkeys(_WARMUP_PARTS, 0)})
            row["wall_s"] += t1 - t0
            for key, a, b in zip(_WARMUP_PARTS, parts0, counter.parts()):
                row[key] += b - a
            row["execute_s"] = row["wall_s"] - sum(
                row[k] for k in _WARMUP_PARTS if k.endswith("_s"))
            # a program jax still held in memory reads neither
            row["cache"] = ("miss" if row["cache_misses"] else
                            "hit" if row["cache_hits"] else "none")
            if self.trace is not None:
                self.trace.recorder.complete(f"warmup:{label}", "warmup", t0, t1,
                                             cat="warmup", cache=row["cache"])

    def warmup_programs(self) -> frozenset:
        """The static set of program labels :meth:`warmup` compiles for
        this engine's plugin — the same ``warmup_plan`` derivation the
        GL404 pair audit checks dispatch coverage against
        (``analysis/distributed_audit.py``), exposed on the engine so the
        runtime warmup and the preflight gate read one source of truth."""
        from ..analysis.distributed_audit import warmup_plan

        return warmup_plan(self.plugin, adapters=self.adapters is not None)

    @property
    def compile_events(self) -> int:
        """Real XLA backend compiles observed since this engine was built
        (process-wide jax.monitoring stream, reported as a delta).  After
        :meth:`warmup` this must not grow — every program is fixed-shape."""
        return self._compile_counter.count - self._compile_baseline

    def step(self) -> dict:
        """One scheduler decision + at most one device program.

        The tick is partitioned into sibling phases that cover it from entry
        to return — ``control``, ``schedule``, ``plan``, ``stage:*``,
        ``dispatch:*``, ``host_sync``, ``commit``, ``trace``
        (``RequestTracer``'s docstring says what each holds).  The host ledger
        clocks every one of them, tracer or no tracer (``metrics``'
        ``host_s.<kind>.<phase>``; a tick far over its class's median is a row
        of :attr:`stalls`); with tracing on (:attr:`trace`) the same brackets
        are also spans on the ``engine`` track, beside the per-request
        lifecycle spans derived from the scheduler's event log.  All
        host-side: the device programs are identical."""
        led = self._ledger
        step = self.steps
        led.begin_tick()
        event = self._tick(led.phase, step)
        row = led.end_tick(event["type"], event.get("bucket", 0), step,
                           busy=not self.sched.idle())
        if row is not None:
            row["waiting"] = len(self.sched.waiting)
            row["live"] = len(self.sched.slots)
        return event

    def _tick(self, phase, step: int) -> dict:
        """``step()``'s body, between the ledger's two tick boundaries."""
        tr = self.trace
        with phase("control", step=step):
            for ev in _faults.fault_point("serve_step"):
                if ev.kind == "preempt":
                    # drain: stop taking work, hand every in-flight request
                    # back (the serving analog of the trainer's SIGTERM-at-
                    # step-boundary stop; resilience/preemption.py discipline)
                    self.interrupted = True
                    self._drain_prefix_frees()
                    return {"type": "preempted", "step": self.steps}
                if ev.kind == "cancel":
                    # cancellation storm: the oldest live request cancels —
                    # deterministic, so the event-log pin covers the storm
                    self._inject_cancel_oldest()
                elif ev.kind == "deadline":
                    # deadline storm: every live request expires NOW, and the
                    # overload signal escalates the degradation ladder one
                    # stage
                    self.sched.force_expire_all()
                    self.ladder.escalate()
                elif ev.kind == "prefix":
                    # cache-invalidation storm: every index hold drops — live
                    # slots keep their shared refcounts (their pages free
                    # later through the normal release path), future
                    # admissions miss.  Tokens stay bitwise: a flush only
                    # changes WHERE K/V gets computed, never what it holds.
                    if self.prefix is not None:
                        freed = self.prefix.flush()
                        self.sched.free_pages += freed
                        self.sched.events.append(("prefix_flush", freed))
            self.sched.tick = self.steps
            self._process_control()
        with phase("schedule", step=step):
            admitted = self.sched.admit()
            if self.prefix is not None:
                # push refcount-death / LRU-reclaim pages BEFORE any
                # allocating dispatch (the host mirror counted them at
                # decision time), then write each adopted prefix into its
                # slot's block-table row
                self._drain_prefix_frees()
                for s in admitted:
                    st = self.sched.slots[s]
                    if st.shared_pages:
                        pps = self.plugin.pages_per_slot
                        ids = np.zeros((pps,), np.int32)
                        ids[:len(st.shared_pages)] = st.shared_pages
                        self.cache = self._adopt(
                            self.cache, jnp.asarray(s, jnp.int32),
                            jnp.asarray(ids),
                            jnp.asarray(len(st.shared_pages), jnp.int32),
                        )
            action = self.sched.next_action()
        window = None
        event: dict = {"type": action[0], "step": self.steps}
        if action[0] == "prefill":
            window = self._prefill_tick(action, phase, event)
        elif action[0] == "decode" and self._spec is not None \
                and not self.despeculated:
            event["type"] = "verify"
            window = self._verify_tick(action[1], phase, event)
            if self.interrupted:  # preempt-mid-verify fault: nothing ran
                self._drain_prefix_frees()
                return {"type": "preempted", "step": self.steps}
        elif action[0] == "decode":
            window = self._decode_tick(action[1], phase, event)
        else:
            self.metrics["idle_steps"] += 1
        with phase("commit", step=step):
            used = self.sched.used_pages
            self.metrics["page_step_sum"] += used
            self.metrics["peak_used_pages"] = max(
                self.metrics["peak_used_pages"], used)
            # the tick boundary owes the device every refcount-death push
            # the host counted this tick (mirror exact at every boundary —
            # the refcounted invariant checker runs between ticks)
            self._drain_prefix_frees()
        if tr is not None:
            # lifecycle spans off the scheduler's deterministic event log
            # (submit/admit/swap/bypass/prefill/evict/finish this tick); the
            # tracer's own cost shows as the tick's last span
            with phase("trace", step=step):
                tr.consume_scheduler_events(
                    self.sched.events, step, window=window,
                    stamps=self.sched.stamps)
        self.steps += 1
        return event

    def _prefill_tick(self, action, phase, event):
        """One bucket-padded prefill chunk (and, when it completes the
        prompt, the first token's sampling).  Returns the tracing window —
        ``stage`` start to the last sync's end — or None."""
        _, slot, start, chunk, bucket = action
        step = self.steps
        with phase("plan", step=step):
            survived, evicted = self.sched.plan_prefill_evictions(slot, chunk)
            self._release_evicted(evicted)
            if survived:
                st = self.sched.slots[slot]
                ids = np.zeros((bucket,), np.int32)
                ids[:chunk] = st.request.prompt[start:start + chunk]
        if not survived:
            event["cancelled"] = True
            return None
        m = self.metrics
        with phase("stage:prefill", step=step) as opened:
            uid = st.request.uid
            if uid not in self._dispatch_seen:
                # once per request, like the TTFT sample: how long it waited
                # for its first chunk to be handed to the device
                self._dispatch_seen.add(uid)
                m["queue_wait_s_sum"] += self._clock() - self._arrival_wall[uid]
                m["queue_wait_n"] += 1
            args = (
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(ids), jnp.asarray(start, jnp.int32),
                jnp.asarray(chunk, jnp.int32),
                jnp.asarray(st.adapter_slot, jnp.int32),
            )
        with phase("dispatch:prefill", step=step, slot=slot, chunk=chunk,
                   bucket=bucket) as closed:
            cache, last = self._run_prefill(*args)
        with phase("commit", step=step):
            self.cache = cache
            self.sched.note_prefill(slot, chunk)
            m["prefill_steps"] += 1
            m["prefill_scheduled_tokens"] += bucket
            m["prefill_useful_tokens"] += chunk
            m["prompt_tokens"] += chunk
            event.update(slot=slot, chunk=chunk, bucket=bucket)
        if self.prefix is not None and st.prefill_done:
            # the completed prompt's NEW full pages register in the content
            # index (one small block-row fetch; the engine syncs a token
            # this tick anyway)
            with phase("host_sync", step=step) as closed:
                self._insert_prefix(slot, st)
        if st.prefill_done:
            # the prompt's last-token logits seed the decode loop — the
            # first generated token, exactly like generate()
            with phase("stage:sample", step=step):
                rng = self._step_rng()
            with phase("dispatch:sample", step=step):
                tok_dev = self._sample(last, rng)
            with phase("host_sync", step=step) as closed:
                tok = int(tok_dev)
            with phase("commit", step=step):
                m["generated_tokens"] += 1
                self._record_token(slot, tok)
        return opened.t0, closed.t1

    def _decode_tick(self, slots, phase, event):
        """One decode step for every decoding slot.  Returns the tracing
        window (``stage`` start to ``host_sync`` end) or None."""
        step = self.steps
        with phase("plan", step=step):
            active_slots, evicted = self.sched.plan_evictions(slots)
            self._release_evicted(evicted)
            if active_slots:
                needing = self.sched.decode_page_need(active_slots)
                n = self.plugin.num_slots
                tokens = np.zeros((n,), np.int32)
                active = np.zeros((n,), bool)
                adapter_slots = np.zeros((n,), np.int32)
                for s in active_slots:
                    tokens[s] = self.sched.slots[s].tokens[-1]
                    active[s] = True
                    adapter_slots[s] = self.sched.slots[s].adapter_slot
        if not active_slots:
            event["cancelled"] = True
            return None
        with phase("stage:decode", step=step) as opened:
            args = (jnp.asarray(tokens), jnp.asarray(active),
                    jnp.asarray(adapter_slots), self._step_rng())
        with phase("dispatch:decode", step=step, slots=list(active_slots)):
            cache, next_tok = self._run_decode(*args)
        with phase("host_sync", step=step) as closed:
            next_np = np.asarray(next_tok)
        with phase("commit", step=step):
            self.cache = cache
            self._note_tick_counters(next_np[self.plugin.num_slots:])
            self.sched.note_decode(needing, active_slots)
            done_slots = []
            for s in active_slots:
                if self._record_token(s, int(next_np[s]), release=False):
                    done_slots.append(s)
            if done_slots and not self.hold_finished:
                self._release_slots(done_slots)
                self._finish_decode_slots(done_slots)
            m = self.metrics
            m["decode_steps"] += 1
            m["scheduled_decode_slots"] += n
            m["useful_decode_tokens"] += len(active_slots)
            m["generated_tokens"] += len(active_slots)
            m["decode_lane_passes"] += len(active_slots)
            m["decode_emitted_tokens"] += len(active_slots)
            event.update(slots=tuple(active_slots))
        return opened.t0, closed.t1

    def run(self, trace: list[Request], max_steps: int = 200_000) -> dict[int, list[int]]:
        """Replay ``trace`` (arrivals keyed on virtual step time) to
        completion — or to the first injected preemption."""
        pending = sorted(trace, key=lambda r: (r.arrival_step, r.uid))
        i = 0
        while True:
            while i < len(pending) and pending[i].arrival_step <= self.steps:
                self.add_request(pending[i])
                i += 1
            if self.interrupted or (self.idle() and i >= len(pending)):
                break
            self.step()
            if self.steps >= max_steps:
                raise RuntimeError(f"serving replay exceeded {max_steps} steps")
        # arrivals that never reached the engine before a drain still count
        # as unfinished work for the resume path
        self._undelivered = pending[i:]
        return self.results

    # -- internals -----------------------------------------------------------

    def _process_control(self) -> None:
        """The tick-boundary control pass: apply pending cancellations, then
        retire in-flight requests whose deadline has passed.  Runs BEFORE
        admission, so a cancelled/expired request's pages and slot are
        available to this very tick's admissions."""
        sched = self.sched
        for uid in list(self._pending_cancels):
            if self._apply_cancel(uid, reason="cancel"):
                self._pending_cancels.remove(uid)
            elif uid in self.results or uid in sched.retired_uids:
                # raced a finish/shed: nothing left to cancel
                self._pending_cancels.remove(uid)
            # else: not yet arrived — the cancel stays pending
        for slot in sorted(sched.slots):
            # a held finished slot already delivered its tokens — pages stay
            # parked for the KV transfer; a deadline cannot revoke them
            if sched.slots[slot].finished:
                continue
            if sched.request_expired(sched.slots[slot].request):
                self._cancel_slot(slot, reason="deadline")

    def _apply_cancel(self, uid: int, reason: str) -> bool:
        """Cancel ``uid`` at whatever stage it is in right now.  Returns
        True when a live request was retired."""
        sched = self.sched
        for slot, st in sched.slots.items():
            if st.request.uid == uid and not st.finished:
                # (a held finished slot is already in results — the caller's
                # raced-a-finish branch drops the stale cancel)
                self._cancel_slot(slot, reason=reason)
                return True
        return sched.cancel_queued(uid, reason=reason)

    def _cancel_slot(self, slot: int, reason: str) -> None:
        """Retire an admitted request: device pages back to the functional
        free-list first (the same release program finish/evict drive), then
        the scheduler's mirrored host-side release — the exact ordering that
        keeps ``verify_serving_invariants`` green at every boundary."""
        uid = self.sched.slots[slot].request.uid
        self._release_slots([slot])
        self.sched.cancel_slot(slot, reason=reason)
        self._arrival_wall.pop(uid, None)
        self._last_token_wall.pop(uid, None)
        self._ttft_seen.discard(uid)
        self._dispatch_seen.discard(uid)

    def _inject_cancel_oldest(self) -> None:
        """The cancellation-storm fault payload: cancel the oldest live
        request — oldest-admitted in-flight first, else the head of the
        waiting line.  Deterministic by construction."""
        sched = self.sched
        live = [s for s in sched.slots if not sched.slots[s].finished]
        if live:
            slot = min(live, key=lambda s: sched.slots[s].admit_seq)
            self._cancel_slot(slot, reason="cancel")
        elif sched.waiting:
            sched.cancel_queued(sched.waiting[0].uid, reason="cancel")

    def _verify_tick(self, candidate_slots, phase, event):
        """One speculative draft-and-verify pass (the decode action with
        speculation armed).  Draft first (the proposals size the page
        reservation), evict for the WORST-CASE page demand, dispatch the
        bucket-padded verify program, then settle the host mirror off the
        device-accepted lengths.  Returns the tracing window (or None).
        The phases are the decode tick's: drafting and eviction planning
        are ``plan``.

        The ``verify_step`` fault site fires FIRST — a ``preempt`` armed
        there drains the engine mid-verify with nothing dispatched and no
        state touched, so the drain/resume contract (and every invariant)
        holds at the finest-grained boundary speculation has."""
        step = self.steps
        with phase("plan", step=step):
            for ev in _faults.fault_point("verify_step"):
                if ev.kind == "preempt":
                    self.interrupted = True
                    event["preempted"] = True
                    return None
            sp = self._spec
            sched = self.sched
            cand = list(candidate_slots)
            n = self.plugin.num_slots
            # the draft batch is padded to the FULL slot width like every
            # other engine program: a draft-model provider jits per batch
            # shape, and a shape that tracked the live candidate count would
            # recompile mid-traffic the first time occupancy changed
            # (strict_compiles).  Contexts carry only the provider's trailing
            # window — rebuilding the full prompt+generated history per pass
            # would be quadratic in stream length — and the assembly counts
            # as draft time (it exists only to feed the drafting layer).
            t_ctx = time.perf_counter()
            win = max(2, getattr(sp.provider, "window", 512))
            contexts = [[1]] * n
            remaining = [1] * n  # dummy rows clamp to depth 0
            tenant_ids = [0] * n
            for s in cand:
                st = sched.slots[s]
                toks = st.tokens
                if len(toks) >= win:
                    contexts[s] = toks[-win:]
                else:
                    contexts[s] = list(st.request.prompt[len(toks) - win:]) + toks
                remaining[s] = st.request.max_new_tokens - len(toks)
                tenant_ids[s] = st.request.adapter_id
            sp.draft_time_s += time.perf_counter() - t_ctx
            drafts, spec_lens = sp.draft(contexts, remaining, tenant_ids)
            spec_by_slot = {s: int(spec_lens[s]) for s in cand}
            active_slots, evicted = sched.plan_speculative_evictions(
                cand, spec_by_slot
            )
            self._release_evicted(evicted)
            if not active_slots:
                event["cancelled"] = True
                return None
            worst_need = sched.verify_page_need(active_slots, spec_by_slot)
            bucket = sp.bucket_for(max(spec_by_slot[s] for s in active_slots))
            w = bucket + 1
            tokens = np.zeros((n, w), np.int32)
            spec_arr = np.zeros((n,), np.int32)
            active = np.zeros((n,), bool)
            adapter_slots = np.zeros((n,), np.int32)
            for s in active_slots:
                st = sched.slots[s]
                d = spec_by_slot[s]
                tokens[s, 0] = st.tokens[-1]
                if d:
                    tokens[s, 1:1 + d] = drafts[s, :d]
                spec_arr[s] = d
                active[s] = True
                adapter_slots[s] = st.adapter_slot
        with phase("stage:verify", step=step) as opened:
            args = (jnp.asarray(tokens), jnp.asarray(spec_arr),
                    jnp.asarray(active), jnp.asarray(adapter_slots),
                    self._step_rng())
        with phase("dispatch:verify", step=step, slots=list(active_slots),
                   bucket=bucket):
            cache, greedy, m_dev = self._run_verify(*args)
        with phase("host_sync", step=step) as closed:
            greedy_np = np.asarray(greedy)
            m_np = np.asarray(m_dev)
        with phase("commit", step=step):
            self.cache = cache
            self._settle_verify(active_slots, spec_by_slot, worst_need,
                                greedy_np, m_np, bucket, event)
        return opened.t0, closed.t1

    def _settle_verify(self, active_slots, spec_by_slot, worst_need,
                       greedy_np, m_np, bucket, event) -> None:
        """The host side of a verify pass once its tokens are here: the
        page mirror, the accepted tokens, the finished slots, the counters."""
        sched = self.sched
        n = self.plugin.num_slots
        w = bucket + 1
        accepted = {s: int(m_np[s]) for s in active_slots}
        m = self.metrics
        # rollback accounting against the PRE-pass kv lengths (note_verify
        # advances them)
        for s in active_slots:
            kept = speculative_page_need(
                sched.slots[s].kv_tokens, accepted[s], self.plugin.page_size
            )
            m["speculative_rollbacks"] += worst_need[s] - kept
        sched.note_verify(accepted)
        done_slots = []
        recorded = 0
        delivered_drafts = 0
        for s in active_slots:
            r = 0
            for tok in greedy_np[s, :accepted[s] + 1]:
                r += 1
                if self._record_token(s, int(tok), release=False):
                    # EOS (or max_new) inside the accepted window retires
                    # the sequence; the remainder of the window is
                    # discarded exactly as sequential decode never would
                    # have produced it
                    done_slots.append(s)
                    break
            recorded += r
            # accepted drafts DELIVERED (each pass emits m+1 for m accepted
            # drafts; an EOS truncation discards the tail, and discarded
            # drafts must not inflate the measured accept-rate twin — the
            # predicted replay caps at the stream end the same way)
            delivered_drafts += r - 1
        if done_slots and not self.hold_finished:
            self._release_slots(done_slots)
            self._finish_decode_slots(done_slots)
        m["verify_steps"] += 1
        m["scheduled_decode_slots"] += n * w
        m["useful_decode_tokens"] += recorded
        m["generated_tokens"] += recorded
        m["decode_lane_passes"] += len(active_slots)
        m["decode_emitted_tokens"] += recorded
        m["draft_tokens"] += sum(spec_by_slot[s] for s in active_slots)
        m["accepted_draft_tokens"] += delivered_drafts
        event.update(slots=tuple(active_slots), bucket=bucket,
                     accepted=tuple(accepted[s] for s in active_slots))

    def _step_rng(self):
        return jax.random.fold_in(self._base_rng, self.steps)

    def _refuse_unsupported(self, adapters, hold_finished) -> None:
        """A family names the engine features it cannot be served with
        (``serving_refuses`` on the model): asking for one is an error here,
        never a silent run without part of the family's state."""
        p = self.plugin
        asked = {
            "adapters": adapters is not None,
            "kv_dtype": p.kv_dtype in ("int8", "fp8"),
            "speculate": p.speculate != "off",
            "prefix_cache": p.prefix_cache == "on",
            "hold_finished": hold_finished,
        }
        for feature, what in getattr(self.model, "serving_refuses", {}).items():
            if asked[feature]:
                raise NotImplementedError(
                    f"{type(self.model).__name__} cannot be served with {what}")
        if getattr(self.model, "prefill_writes_whole_pages", False):
            widths = (p.prefill_chunk,) + tuple(p.prefill_buckets or ())
            if any(w % p.page_size for w in widths):
                raise ValueError(
                    f"{type(self.model).__name__} writes a prefill chunk a page at a time: "
                    f"prefill_chunk and every bucket {widths} must be multiples of "
                    f"page_size={p.page_size}")

    def _note_tick_counters(self, values) -> None:
        """Add the counter vector that came with a decode tick's tokens to
        ``metrics`` (nothing for a family without ``tick_counters``)."""
        at = 0
        for name, size in self._tick_counters:
            if size == 1:
                self.metrics[name] += int(values[at])
            else:
                self.metrics[name] += values[at:at + size]
            at += size

    def _record_token(self, slot: int, tok: int, release: bool = True) -> bool:
        """Append a sampled token; retire the sequence on EOS/max_new.
        Returns True when the sequence finished (caller releases if it opted
        out of the immediate release)."""
        st = self.sched.slots[slot]
        now = self._clock()
        uid = st.request.uid
        if not st.tokens:
            # once per request: an evicted-and-readmitted sequence must not
            # re-sample its TTFT (the first life already delivered a token)
            if uid not in self._ttft_seen:
                self._ttft_seen.add(uid)
                ttft = now - self._arrival_wall[uid]
                self.ttft_s.append(ttft)
                self.metrics["ttft_s_sum"] += ttft
                self.metrics["ttft_n"] += 1
                self.ttft_ticks.append(self.steps - st.request.arrival_step)
                if self.slo is not None:
                    self.slo.observe("ttft_s", ttft)
        elif uid in self._last_token_wall:
            self.token_gaps_s.append(now - self._last_token_wall[uid])
            if self.slo is not None:
                self.slo.observe("token_latency_s", self.token_gaps_s[-1])
        self._last_token_wall[uid] = now
        st.tokens.append(tok)
        if not st.prefill_done:
            raise AssertionError("token recorded before prefill completed")
        eos = self.gen_config.eos_token_id
        finished = (eos is not None and tok == eos) or \
            len(st.tokens) >= st.request.max_new_tokens
        if finished:
            self.results[uid] = list(st.tokens)
            # retire the per-request wall clocks: the serving loop is
            # long-lived, so live-request bookkeeping must not grow with
            # total requests served
            self._arrival_wall.pop(uid, None)
            self._last_token_wall.pop(uid, None)
            self._ttft_seen.discard(uid)
            self._dispatch_seen.discard(uid)
            if self.hold_finished:
                # prefill-role engine: the KV pages stay resident until the
                # transport streams them to the decode engine
                st.finished = True
                self.held.append(slot)
            elif release:
                self._release_slots([slot])
                self.sched.finish(slot)
            return True
        return False

    def _release_slots(self, slots: list[int]) -> None:
        mask = np.zeros((self.plugin.num_slots,), bool)
        mask[slots] = True
        if self.prefix is None:
            self.cache = self._release(self.cache, jnp.asarray(mask))
            return
        # COW release: the device pushes ONLY the pages past each slot's
        # shared prefix — an aliased page never reaches the free stack from
        # here (the host refcounts in _release_slot_pages decide when it
        # actually frees, through the push_free program)
        keep = np.zeros((self.plugin.num_slots,), np.int32)
        for s in slots:
            st = self.sched.slots.get(s)
            if st is not None:
                keep[s] = len(st.shared_pages)
            else:
                # evict() popped the state already; it parked the keep count
                keep[s] = self.sched.evicted_keep.pop(s, 0)
        self.cache = self._release_cow(self.cache, jnp.asarray(mask),
                                       jnp.asarray(keep))

    def _drain_prefix_frees(self) -> None:
        """Push every refcount-death / LRU-reclaim page the host queued onto
        the device free stack (fixed-width batches of ``pages_per_slot`` —
        one warmed program shape).  ``pop_pending`` hard-asserts none of
        them still holds a reference (the double-free guard)."""
        if self.prefix is None or not self.prefix.pending_free:
            return
        pages = self.prefix.pop_pending()
        width = self.plugin.pages_per_slot
        for i in range(0, len(pages), width):
            chunk = pages[i:i + width]
            ids = np.zeros((width,), np.int32)
            mask = np.zeros((width,), bool)
            ids[:len(chunk)] = chunk
            mask[:len(chunk)] = True
            self.cache = self._push_free(self.cache, jnp.asarray(ids),
                                         jnp.asarray(mask))

    def _insert_prefix(self, slot: int, st) -> None:
        """Register a completed prefill's NEW full pages in the content
        index.  The physical ids come from one small block-row fetch (the
        device popped them; the host mirror only tracks counts) — the
        slot's shared set stays a contiguous row prefix, so the COW release
        keep-count arithmetic holds."""
        hashes = self.prefix.block_hashes(st.request.prompt,
                                          st.request.adapter_id)
        k = len(st.shared_pages)
        if len(hashes) <= k:
            return
        row = np.asarray(self.cache["block_tables"])[slot, :len(hashes)]
        inserted = self.prefix.insert_owned(hashes[k:],
                                            [int(p) for p in row[k:]])
        st.shared_pages.extend(inserted)

    def _release_evicted(self, evicted: list[int]) -> None:
        if evicted:
            self._release_slots(evicted)
            self.metrics["evictions"] += len(evicted)
            # the evicted sequences' generated tokens were revoked: their
            # inter-token clock must not bridge across the readmission
            for req in self.sched.waiting:
                self._last_token_wall.pop(req.uid, None)

    def _finish_decode_slots(self, slots: list[int]) -> None:
        for s in slots:
            self.sched.finish(s)

    # -- introspection --------------------------------------------------------

    def audit_decode_step(self, **audit_kwargs):
        """graft-lint jaxpr audit of the decode step (trace-only — the
        donated pool buffers stay intact).  The pool update must come back
        clean: donation fully consumed (no GL101), no in-trace transfers,
        no donated-name reuse (the AST sweep covers GL201 separately).
        In multi-tenant mode the audited program includes the adapter pool
        and id routing — the contract is identical."""
        from ..analysis import audit_jitted

        n = self.plugin.num_slots
        if self.adapters is None:
            return audit_jitted(
                self._decode, self.params, self.cache,
                jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.bool_),
                self._base_rng, **audit_kwargs,
            )
        return audit_jitted(
            self._decode, self.params, self.adapters.pool, self.cache,
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            self._base_rng, **audit_kwargs,
        )

    @property
    def speculator(self) -> Optional["Speculator"]:
        """The engine's speculative-decode state (None when off)."""
        return self._spec

    @property
    def speculate_mode(self) -> str:
        return self.plugin.speculate if self._spec is not None else "off"

    def audit_verify_step(self, **audit_kwargs):
        """graft-lint jaxpr audit of the speculative verify step at the
        largest bucket width — the allocate + multi-token append +
        page-rollback pytree must alias the donated cache exactly like the
        decode step (no GL101 wasted donation, no in-trace transfers)."""
        from ..analysis import audit_jitted

        if self._spec is None:
            raise RuntimeError("speculation is off: no verify program to audit")
        n = self.plugin.num_slots
        w = self._spec.buckets[-1] + 1
        sds = jax.ShapeDtypeStruct
        if self.adapters is None:
            return audit_jitted(
                self._verify, self.params, self.cache,
                sds((n, w), jnp.int32), sds((n,), jnp.int32),
                sds((n,), jnp.bool_), self._base_rng, **audit_kwargs,
            )
        return audit_jitted(
            self._verify, self.params, self.adapters.pool, self.cache,
            sds((n, w), jnp.int32), sds((n,), jnp.int32),
            sds((n,), jnp.bool_), sds((n,), jnp.int32),
            self._base_rng, **audit_kwargs,
        )

    def free_page_mirror_in_sync(self) -> bool:
        """Test hook: the host scheduler's free-page mirror equals the
        device allocator's ``free_top`` (one scalar fetch).  The full
        resource contract — page conservation, slot accounting, adapter
        refcount balance — is the reusable
        :func:`~.overload.verify_serving_invariants` checker this grew
        into; chaos tests and ``replay(..., verify_invariants=True)`` run
        that one."""
        return int(self.cache["free_top"]) == self.sched.free_pages
