"""Disaggregated prefill→decode: streaming finished KV pages between
engines (DistServe discipline — the first slice).

Once KV pages are a transferable, refcounted resource (the prefix cache's
contract), prefill and decode stop having to share an engine: a **prefill
gang** turns prompts into KV pages + a first token at full chunked-prefill
throughput, and a **decode gang** consumes those pages at decode batch
shapes — neither workload pads out the other's step.  This module lands
the in-process two-engine slice of that split:

- :class:`PagedKVTransport` — two fixed-shape jitted programs move one
  finished slot's pages between pools: ``send`` gathers the slot's
  block-table row into a contiguous wire payload (``[L, pps, Hkv, page,
  D]`` per K/V — the exact bytes a DCN stream would carry), ``recv`` pops
  fresh pages from the destination free stack, scatters the payload into
  them and installs block-table row + ``seq_len``.  Bytes are accounted
  against the ``dcn``-axis model (:func:`transfer_accounting`, the
  ``dcn_comm_accounting`` pattern) as the ``transfer.page_bytes`` twin.
- :class:`DisaggregatedPair` — the host loop over a prefill-role engine
  (``hold_finished=True``: finished slots keep their pages until streamed)
  and a decode-role engine.  Greedy tokens are BITWISE identical to the
  same trace through one engine (pinned by tests/test_prefix_cache.py):
  the payload bytes ARE the K/V, so the decode side attends over exactly
  what a local prefill would have written.

Multi-host streaming is live in the 2-process fabric leg
(``test_utils/scripts/fleet_fabric.py``, launched over jax.distributed by
the dryrun's ``_fleet_leg``): the SAME wire payload crosses a real process
boundary over the ``dcn`` plumbing, gated by the same shared
``wire_schema`` derivation, with independent per-role pool geometry and
the byte twin exact.  N pairs compose into a fleet behind the
deterministic affinity router in :mod:`.router`.
"""

from __future__ import annotations

import dataclasses as _dc
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .engine import ServingEngine
from ..ops.paged_cache import allocate, pages_for
from .paged_cache import kv_page_bytes
from .scheduler import Request


def page_bytes(config, page_size: int, dtype_bytes: int = 2,
               kv_dtype: str = "") -> int:
    """Wire bytes of ONE physical page across all layers — the unit the
    transfer twin counts in (``kv_pool_accounting``'s bytes/page; one
    shared formula, :func:`~.paged_cache.kv_page_bytes`, so predicted and
    measured twins can only agree exactly).  Quantized pools
    (``kv_dtype`` "int8"/"fp8") ship 1-byte codes plus the per-(kv-head,
    page) scales — the scales are page content and travel on the wire."""
    return kv_page_bytes(config, page_size, dtype_bytes, kv_dtype)


def transfer_accounting(config, trace, page_size: int, dtype_bytes: int = 2,
                        dcn_gbps: float = 25.0, kv_dtype: str = "") -> dict:
    """Predicted ``dcn``-axis byte model for a disaggregated replay of
    ``trace`` (the ``dcn_comm_accounting`` pattern): every request ships
    ``pages_for(prompt_len)`` live pages exactly once, prefill→decode.
    The measured twin (``transfer.page_bytes``) comes from the transport's
    executed transfers — the two agree exactly unless a request never made
    it to the handoff (shed, cancelled, drained).  ``dcn_gbps`` turns the
    bytes into a stream-time envelope per the reference DCN link rate.
    Pass the pool's ``kv_dtype`` for quantized pages — the wire unit is
    roughly halved (codes + scales instead of bf16)."""
    per_page = page_bytes(config, page_size, dtype_bytes, kv_dtype)
    pages = sum(int(pages_for(r.prompt_len, page_size)) for r in trace)
    total = pages * per_page
    from ..telemetry import twin_registry

    twin_registry().record_predicted(
        "transfer.page_bytes", total,
        source="serving/transfer.transfer_accounting",
    )
    return {
        "requests": len(trace),
        "pages_predicted": pages,
        "bytes_per_page": per_page,
        "page_transfer_bytes": total,
        "dcn_gbps_ref": dcn_gbps,
        "stream_s_pred": round(total / (dcn_gbps * 1e9), 6) if total else 0.0,
    }


def _transfer_step_fns():
    def send_step(cache, slot):
        # one slot's pages, gathered contiguous through its block-table row
        # — the wire payload a DCN stream would carry (dead pages ride as
        # padding; the byte twin counts live pages only).  Quantized pools
        # also ship the per-(kv-head, page) scales: they are page content
        # (the codes are meaningless without them), so they ride the same
        # payload — the byte twin counts them via kv_page_bytes.
        row = jax.lax.dynamic_slice_in_dim(cache["block_tables"], slot, 1)[0]
        payload = {
            "k": jnp.stack([l["k_pages"][:, row] for l in cache["layers"]]),
            "v": jnp.stack([l["v_pages"][:, row] for l in cache["layers"]]),
        }  # [L, Hkv, pps, page, D] each
        if "k_scales" in cache["layers"][0]:
            payload["k_scales"] = jnp.stack(
                [l["k_scales"][:, row] for l in cache["layers"]])
            payload["v_scales"] = jnp.stack(
                [l["v_scales"][:, row] for l in cache["layers"]])
            # [L, Hkv, pps] each
        return payload

    def recv_step(cache, slot, payload, n_pages, seq_len):
        # pop n_pages fresh pages, install the block-table row, scatter the
        # payload into the popped pages — one donated fixed-shape program
        pps = cache["block_tables"].shape[1]
        lane = jnp.arange(pps, dtype=jnp.int32)
        need = lane < n_pages
        block_tables, free_top = allocate(
            cache["block_tables"], cache["free_stack"], cache["free_top"],
            jnp.full((pps,), slot, jnp.int32), lane, need,
        )
        row = jax.lax.dynamic_slice_in_dim(block_tables, slot, 1)[0]
        num_pages = cache["layers"][0]["k_pages"].shape[1]
        dst = jnp.where(need, row, num_pages)  # OOB -> drop (write-mask rule)
        quantized = "k_scales" in payload
        new_layers = []
        for i, l in enumerate(cache["layers"]):
            layer = {
                "k_pages": l["k_pages"].at[:, dst].set(payload["k"][i],
                                                       mode="drop"),
                "v_pages": l["v_pages"].at[:, dst].set(payload["v"][i],
                                                       mode="drop"),
            }
            if quantized:
                layer["k_scales"] = l["k_scales"].at[:, dst].set(
                    payload["k_scales"][i], mode="drop")
                layer["v_scales"] = l["v_scales"].at[:, dst].set(
                    payload["v_scales"][i], mode="drop")
            new_layers.append(layer)
        return {
            "layers": new_layers,
            "block_tables": block_tables,
            "seq_lens": cache["seq_lens"].at[slot].set(seq_len),
            "free_stack": cache["free_stack"],
            "free_top": free_top,
        }

    return send_step, recv_step


@lru_cache(maxsize=8)
def _transfer_fns(_geom_key):
    send_step, recv_step = _transfer_step_fns()
    return (
        jax.jit(send_step),                      # read-only gather
        jax.jit(recv_step, donate_argnums=(0,)),  # destination pool donates
    )


class PagedKVTransport:
    """Streams one finished slot's KV pages from a prefill-role engine to a
    decode-role engine (in-process: same devices, a gather + scatter; the
    payload shape is the multi-host wire format).  Byte accounting records
    the measured side of the ``transfer.page_bytes`` twin and appends
    ``("page_transfer", uid, n_pages, bytes)`` to the destination
    scheduler's determinism log (the ``page_transfer`` span)."""

    def __init__(self, src: ServingEngine, dst: ServingEngine):
        # one schema derivation for gate and runtime: the GL403 preflight
        # (analysis/distributed_audit.audit_wire_schema) and this runtime
        # rejection read the SAME wire_schema() dict, so they cannot drift
        # — a pair the gate passed constructs, a pair it failed raises here
        from ..analysis.distributed_audit import check_wire_schemas, wire_schema

        ps = src.plugin
        schema_src = wire_schema(src.model.config, ps)
        schema_dst = wire_schema(dst.model.config, dst.plugin)
        check_wire_schemas(schema_src, schema_dst)
        self.src, self.dst = src, dst
        self.schema = schema_src
        self._send, self._recv = _transfer_fns(
            (ps.page_size, ps.pages_per_slot, schema_src["kv_dtype"])
        )
        self._page_bytes = schema_src["page_bytes"]
        self.transfers = 0
        self.pages_moved = 0
        self.bytes_moved = 0
        from ..telemetry import twin_registry

        # the static-vs-runtime wire-unit twin: pair_preflight records the
        # predicted side from the schema alone; this is the measured side
        # off the constructed transport
        twin_registry().record_measured(
            "distributed.wire_bytes_per_page", self._page_bytes,
            source="serving/transfer.PagedKVTransport",
        )

    def warmup(self) -> None:
        """Compile both wire programs before traffic (no-op passes: the
        send gathers slot 0, the recv installs zero pages)."""
        payload = self._send(self.src.cache, jnp.asarray(0, jnp.int32))
        self.dst.cache = self._recv(
            self.dst.cache, jnp.asarray(0, jnp.int32), payload,
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        )

    def transfer(self, src_slot: int, request: Request, first_token: int) -> int:
        """Move one held finished slot: gather on the prefill engine, adopt
        a decode slot, scatter + install on the decode engine, then release
        the source pages (COW-aware — a prefix-shared page on the prefill
        side frees only at refcount zero).  Returns the decode slot."""
        src, dst = self.src, self.dst
        n_pages = int(pages_for(request.prompt_len, src.plugin.page_size))
        payload = self._send(src.cache, jnp.asarray(src_slot, jnp.int32))
        dst_slot = dst.adopt_prefilled(request, first_token)
        dst.cache = self._recv(
            dst.cache, jnp.asarray(dst_slot, jnp.int32), payload,
            jnp.asarray(n_pages, jnp.int32),
            jnp.asarray(request.prompt_len, jnp.int32),
        )
        src.release_held(src_slot)
        moved = n_pages * self._page_bytes
        self.transfers += 1
        self.pages_moved += n_pages
        self.bytes_moved += moved
        for eng in (src, dst):
            eng.metrics["page_transfers"] += 1
            eng.metrics["page_transfer_pages"] += n_pages
            eng.metrics["page_transfer_bytes"] += moved
        dst.sched.events.append(
            ("page_transfer", request.uid, n_pages, moved)
        )
        from ..telemetry import twin_registry

        twin_registry().record_measured(
            "transfer.page_bytes", self.bytes_moved,
            source="serving/transfer.PagedKVTransport",
        )
        return dst_slot


class DisaggregatedPair:
    """The disaggregated prefill→decode deployment shape: one prefill-role
    engine (requests clamped to ``max_new_tokens=1`` — the prompt plus the
    first sampled token), one decode-role engine, and the transport
    streaming finished KV pages between them.

    ``run(trace)`` replays a request trace to completion and returns the
    same ``{uid: tokens}`` dict a single engine's ``run`` would — BITWISE
    identical greedy tokens (the acceptance pin): the first token comes
    off the prefill engine's last-chunk logits exactly as a fused engine
    would sample it, and the decode engine attends over the transferred
    bytes verbatim.  Speculation composes on the decode side
    (``plugin.speculate`` arms the decode engine's verify ladder; the
    prefill role is forced plain — its requests never decode), and
    multi-tenant adapters ride the split with one :class:`AdapterStore`
    per role (``adapters``/``prefill_adapters``, published identical
    weights: each engine's pool refcounts balance independently, and the
    decode role re-pins the tenant at :meth:`~.engine.ServingEngine.
    adopt_prefilled`).

    The incremental API (:meth:`submit` / :meth:`tick` / :meth:`busy`)
    exposes the same host loop one step at a time — the fleet router
    (``serving/router.py``) drives N pairs this way, interleaved, and
    :meth:`remaining_requests` extends the single-engine drain/survivors
    contract across the pair.
    """

    def __init__(self, model, params, plugin=None, generation_config=None,
                 rng=None, prefill_plugin=None, adapters=None,
                 prefill_adapters=None):
        from ..utils.dataclasses import ServingPlugin

        plugin = plugin or ServingPlugin()
        if (adapters is None) != (prefill_adapters is None):
            raise ValueError(
                "adapter traffic crosses the split: pass BOTH role stores "
                "(adapters= for the decode engine, prefill_adapters= for "
                "the prefill engine, published identical weights) or "
                "neither — one engine computing LoRA prompts the other "
                "cannot apply breaks token parity"
            )
        # per-tick deadlines belong to the fused engine's admission story
        # (each half runs its own virtual clock) — disarm the DEFAULT too,
        # not just the per-request field: submit() re-stamps
        # default_deadline_ticks onto any request carrying 0, which would
        # silently defeat run()'s deadline_ticks=0 opt-out
        plugin = _dc.replace(plugin, default_deadline_ticks=0)
        # the prefill role never decodes past the first token, so its
        # verify ladder would warm dead programs — force it plain and let
        # speculation live where the tokens do (the decode role)
        prefill_plugin = _dc.replace(prefill_plugin or plugin,
                                     default_deadline_ticks=0,
                                     speculate="off")
        self.prefill_engine = ServingEngine(
            model, params, prefill_plugin, generation_config,
            rng=rng, hold_finished=True, adapters=prefill_adapters,
        )
        self.decode_engine = ServingEngine(
            model, params, plugin, generation_config, rng=rng,
            adapters=adapters,
        )
        self.transport = PagedKVTransport(self.prefill_engine,
                                          self.decode_engine)
        self._pending: list[Request] = []
        self._i = 0
        self._originals: dict[int, Request] = {}
        self._done: dict[int, list[int]] = {}

    def preflight(self) -> tuple[list, dict]:
        """Run the GL4xx pair audit (wire schema, handoff schedule, traced
        wire programs, per-role warmup coverage) over this pair's configs.

        Trace-only — zero backend compiles — so it is safe to call before
        :meth:`warmup`; the dryrun's ``_distributed_audit_leg`` and
        ``preflight --serve --disaggregate`` both route through here."""
        from ..analysis.distributed_audit import pair_preflight

        return pair_preflight(
            self.prefill_engine.model.config,
            self.prefill_engine.plugin,
            self.decode_engine.plugin,
            adapters=self.decode_engine.adapters is not None,
        )

    def warmup(self) -> int:
        c0 = self.prefill_engine._compile_counter.count
        self.prefill_engine.warmup()
        c1 = self.prefill_engine._compile_counter.count
        self.decode_engine.warmup()
        c2 = self.prefill_engine._compile_counter.count
        self.transport.warmup()
        c3 = self.prefill_engine._compile_counter.count
        # per-role warmup cost off the process-wide counter (the fleet
        # bench's compiles_warmup-per-role rows; replicas sharing a jit
        # cache or a prewarm pack show up here as near-zero roles)
        self.compiles_warmup_by_role = {
            "prefill": c1 - c0, "decode": c2 - c1, "wire": c3 - c2,
        }
        # post-warmup compile baselines: run() must stay compile-free from
        # here (the strict_compiles contract extends across the pair — the
        # wire programs are production programs too)
        self._compile_base = (self.prefill_engine.compile_events,
                              self.decode_engine.compile_events)
        return c3 - c0

    # -- the incremental host loop (the fleet router's drive surface) --------

    def submit(self, request: Request) -> None:
        """Queue one request with the pair (virtual arrival honored against
        the prefill engine's clock).  ``run`` is ``submit`` for the whole
        trace plus ``tick`` until :meth:`busy` clears."""
        import bisect

        key = (request.arrival_step, request.uid)
        lo = self._i + bisect.bisect_right(
            [(r.arrival_step, r.uid) for r in self._pending[self._i:]], key
        )
        self._pending.insert(lo, request)
        self._originals[request.uid] = request

    def tick(self) -> bool:
        """One host-loop decision: deliver due arrivals, stream every held
        finished prefill the decode side can seat, then step exactly one
        engine.  Returns ``False`` when there is nothing left to do."""
        P, D = self.prefill_engine, self.decode_engine
        eos = P.gen_config.eos_token_id
        while self._i < len(self._pending) and \
                self._pending[self._i].arrival_step <= P.steps:
            P.add_request(_dc.replace(self._pending[self._i],
                                      max_new_tokens=1, deadline_ticks=0))
            self._i += 1
        # stream every held finished prefill the decode side can seat
        while P.held and self._dst_capacity():
            slot = P.held[0]
            uid = P.sched.slots[slot].request.uid
            tok = P.results[uid][0]
            if self._originals[uid].max_new_tokens == 1 or \
                    (eos is not None and tok == eos):
                # the first token already finished the request: nothing
                # to decode, nothing to stream
                P.release_held(slot)
                self._done[uid] = [tok]
                continue
            # the decode engine runs on its own virtual clock: per-tick
            # deadlines belong to the fused engine's admission story and
            # stay a documented follow-up for the split
            self.transport.transfer(
                slot, _dc.replace(self._originals[uid], deadline_ticks=0),
                P.results[uid][0],
            )
        if P.held and not self._dst_capacity() and not D.idle():
            # a finished prefill is waiting on decode capacity: drain
            # decode FIRST (prefill idling ahead of a blocked handoff
            # must never starve the decode engine of ticks)
            D.step()
        elif self._p_busy():
            P.step()
        elif not D.idle():
            D.step()
        elif self._i < len(self._pending):
            P.step()  # idle tick — advances the virtual arrival clock
        elif P.held:
            raise RuntimeError(
                "disaggregated handoff wedged: held prefill slots with "
                "an idle decode engine that cannot seat them — "
                "mismatched pool geometry?"
            )  # pragma: no cover - geometry validated at construction
        else:
            return False
        return True

    def busy(self) -> bool:
        """Work anywhere in the pair: undelivered arrivals, a busy prefill
        engine, a held handoff, or a non-idle decode engine."""
        return (self._i < len(self._pending) or self._p_busy()
                or bool(self.prefill_engine.held)
                or not self.decode_engine.idle())

    def run(self, trace: list[Request], max_steps: int = 200_000) -> dict[int, list[int]]:
        for r in sorted(trace, key=lambda r: (r.arrival_step, r.uid)):
            self.submit(r)
        steps = 0
        while self.tick():
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"disaggregated replay exceeded {max_steps} steps"
                )
        # the prefill engine recorded 1-token results; the decode engine
        # owns the full streams (first token included); one-token requests
        # finished at the handoff boundary
        return self.results

    @property
    def results(self) -> dict[int, list[int]]:
        return {**self.decode_engine.results, **self._done}

    @property
    def interrupted(self) -> bool:
        return self.prefill_engine.interrupted or self.decode_engine.interrupted

    def remaining_requests(self) -> list[Request]:
        """The pair-wide drain/survivors contract (the single-engine
        :meth:`~.engine.ServingEngine.remaining_requests` extended across
        the split): every submitted ORIGINAL not yet completed and not
        deliberately retired on either engine — undelivered arrivals,
        prefilling, held at the handoff, or decoding — exactly once, in
        submission order.  The fleet router re-routes these when a replica
        drains."""
        retired = (self.prefill_engine.sched.retired_uids
                   | self.decode_engine.sched.retired_uids)
        results = self.results
        return [
            r for r in self._pending
            if r.uid not in results and r.uid not in retired
        ]

    def _p_busy(self) -> bool:
        P = self.prefill_engine
        return bool(P.sched.waiting) or any(
            not st.finished for st in P.sched.slots.values()
        )

    def _dst_capacity(self) -> bool:
        P, D = self.prefill_engine, self.decode_engine
        if not P.held or not D.sched.free_slots:
            return False
        req = P.sched.slots[P.held[0]].request
        uid = req.uid
        # speculative decode books the worst-case first verify pass at
        # admission (scheduler.admission_page_need) — the handoff seat must
        # reserve the same headroom or the first verify wedges the pool
        depth = 0
        if D.sched.speculate_k:
            depth = 1 + min(D.sched.speculate_k,
                            self._originals[uid].max_new_tokens - 1)
        need = pages_for(req.prompt_len + depth, D.plugin.page_size)
        if need > D.sched.free_pages:
            return False
        # adapter routing across the split: the decode role must be able to
        # pin the tenant before the transfer seats the slot
        if D.adapters is not None and req.adapter_id:
            return D.adapters.can_pin(req.adapter_id)
        return True

    def report(self) -> dict:
        t = self.transport
        base = getattr(self, "_compile_base", (0, 0))
        return {
            "page_transfers": t.transfers,
            "page_transfer_pages": t.pages_moved,
            "page_transfer_bytes": t.bytes_moved,
            "prefill_steps": self.prefill_engine.steps,
            "decode_steps": self.decode_engine.steps,
            # post-warmup compile events per engine — zero is the contract
            "compiles_prefill": self.prefill_engine.compile_events - base[0],
            "compiles_decode": self.decode_engine.compile_events - base[1],
        }


__all__ = [
    "PagedKVTransport", "DisaggregatedPair", "transfer_accounting",
    "page_bytes",
]
