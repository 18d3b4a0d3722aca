"""Overload control: the SLO-driven degradation ladder + serving invariants.

Serving millions of users is an exercise in *graceful* failure: when p99
per-token latency, TTFT or occupancy breaches its SLO, the engine must shed
capacity pressure in a deterministic order that costs the least quality
first — and it must do so **using only programs that are already warmed**,
so the recompile guard (``strict_compiles``) holds through every stage of
the degradation.  The four stages, in escalation order:

1. **despeculate** — speculative verify passes stop; decode falls back to
   the plain single-token program (warmed in :meth:`ServingEngine.warmup`
   whether or not speculation is on).  Speculation is a throughput
   optimization paid in worst-case page reservations; under pressure those
   reservations are the first thing to go.
2. **shrink_prefill** — prefill chunks clamp to the SMALLEST warmed bucket:
   long prompts stop monopolizing engine ticks, so in-flight decodes see
   latency relief.  Every chunk still pads to a warmed bucket width.
3. **tighten_admission** — admission keeps a free-page reserve
   (``ladder_reserve_frac`` of the pool) while the pool is contended, so
   in-flight sequences stop being evicted to make room for new admissions
   (eviction = recompute-on-readmit = every generated token revoked — the
   worst latency outcome there is).
4. **shed** — the waiting line clamps to ``num_slots`` and sheds by the
   deterministic policy (oldest-beyond-deadline first, then the newcomer).

:func:`verify_serving_invariants` is the resource-contract checker the
cancellation/chaos machinery is pinned against: the host free-page mirror
equals the device allocator, every physical page is either free or owned by
exactly one live sequence (zero leaks, zero double-ownership), device
sequence lengths match the host bookkeeping, slot accounting is exact, and
adapter refcounts balance the in-flight census.  Tests run it after every
chaos scenario; ``replay(..., verify_invariants=True)`` runs it opt-in.
"""

from __future__ import annotations

from collections import Counter
from typing import Optional

import numpy as np

from ..ops.paged_cache import pages_for


class DegradationLadder:
    """Deterministic graceful-degradation state machine for one engine.

    Escalation is one stage per :meth:`escalate` call (an SLO trip, a
    deadline-storm fault, or an operator action); :meth:`relax` steps back
    down one stage, restoring that stage's knob.  Every transition appends
    ``("ladder", stage)`` to the scheduler's deterministic event log, so
    the determinism pin covers ladder engagement like every other decision.

    Wire an :class:`~accelerate_tpu.telemetry.SLOMonitor` with
    :meth:`attach`: trips escalate, recoveries relax.  All four stages use
    only already-warmed programs — ``strict_compiles`` holds end-to-end
    (pinned by tests and the multichip dryrun ``_overload_leg``).
    """

    STAGES = ("normal", "despeculate", "shrink_prefill", "tighten_admission",
              "shed")

    def __init__(self, engine, *, reserve_frac: Optional[float] = None):
        self.engine = engine
        self.level = 0
        self.engagements = 0
        frac = (reserve_frac if reserve_frac is not None
                else engine.plugin.ladder_reserve_frac)
        self._reserve_pages = max(1, int(engine.plugin.num_pages * frac))
        self._saved_prefill_chunk = engine.plugin.prefill_chunk

    @property
    def stage(self) -> str:
        return self.STAGES[self.level]

    def escalate(self, metric=None, quantile=None, value=None) -> str:
        """Move one stage up (no-op at the top).  The optional arguments
        match the :class:`SLOMonitor` trip-callback signature so the
        monitor can drive the ladder directly."""
        if self.level >= len(self.STAGES) - 1:
            return self.stage
        self.level += 1
        self.engagements += 1
        self._apply(self.level)
        self.engine.sched.events.append(("ladder", self.stage))
        return self.stage

    def relax(self, metric=None, quantile=None, value=None) -> str:
        """Step one stage down, restoring that stage's knob (no-op at
        normal)."""
        if self.level == 0:
            return self.stage
        self._unapply(self.level)
        self.level -= 1
        self.engine.sched.events.append(("ladder", self.stage))
        return self.stage

    def _apply(self, level: int) -> None:
        eng, sched = self.engine, self.engine.sched
        if level == 1:
            eng.despeculated = True
        elif level == 2:
            sched.prefill_chunk = min(eng.plugin.prefill_buckets)
        elif level == 3:
            sched.admission_reserve_pages = self._reserve_pages
        elif level == 4:
            sched.shed_armed = True

    def _unapply(self, level: int) -> None:
        eng, sched = self.engine, self.engine.sched
        if level == 1:
            eng.despeculated = False
        elif level == 2:
            sched.prefill_chunk = self._saved_prefill_chunk
        elif level == 3:
            sched.admission_reserve_pages = 0
        elif level == 4:
            sched.shed_armed = False

    def attach(self, monitor) -> None:
        """Wire an :class:`~accelerate_tpu.telemetry.SLOMonitor`: trips
        escalate one stage, recoveries relax one.  Callbacks the monitor
        already carries (operator alerting) keep firing — the ladder chains
        in front of them, never replaces them."""
        prev_trip, prev_recover = monitor.on_trip, monitor.on_recover

        def trip(metric, quantile, value):
            self.escalate(metric, quantile, value)
            if prev_trip is not None:
                prev_trip(metric, quantile, value)

        def recover(metric, quantile, value):
            self.relax(metric, quantile, value)
            if prev_recover is not None:
                prev_recover(metric, quantile, value)

        monitor.on_trip = trip
        monitor.on_recover = recover

    def report(self) -> dict:
        return {"stage": self.stage, "level": self.level,
                "engagements": self.engagements}


def verify_serving_invariants(engine) -> list[str]:
    """The serving resource contract, checked exactly (the reusable
    extension of ``ServingEngine.free_page_mirror_in_sync``).  Returns a
    list of violations — empty means every invariant holds:

    - host free-page mirror == device ``free_top``;
    - host page conservation: free + Σ ``pages_for(kv_tokens)`` over
      occupied slots == ``num_pages``;
    - device page conservation: the live free-stack entries are unique, and
      together with every live sequence's block-table prefix they cover the
      physical pages exactly once (zero leaked pages, zero double-owners);
      with prefix caching armed this becomes the REFCOUNTED contract
      (:func:`_verify_refcounted`): shared pages count once however many
      rows alias them, refcounts balance the index + slot holds exactly,
      the host shared-prefix mirror matches the device block-table rows,
      and no referenced page ever sits on the free stack (the double-free
      exclusion);
    - device ``seq_lens`` match the host ``kv_tokens`` per occupied slot and
      read 0 for free slots;
    - every layer's state is paged (a ``num_pages`` axis, first or behind
      the kv heads) or slot-addressed
      (``[num_slots, ...]``: a window layer's ring, which needs no clearing
      and no conservation — a row is read only inside its owner's window;
      or a linear-attention layer's recurrent state and conv window, which
      the model itself starts from zero at position 0 and masked lanes
      leave alone);
    - slot accounting: ``free_slots`` ∪ occupied == all slots, disjoint;
    - adapter refcounts balance the in-flight census per tenant.

    One host sync (the cache fetch) — a test/replay-time checker, never
    called from the hot path.
    """
    problems: list[str] = []
    sched = engine.sched
    cache = engine.cache
    page = sched.page_size
    prefix = getattr(engine, "prefix", None)
    free_top = int(cache["free_top"])
    if free_top != sched.free_pages:
        problems.append(
            f"free-page mirror diverged: device free_top={free_top} vs "
            f"host free_pages={sched.free_pages}"
        )
    stack = np.asarray(cache["free_stack"])[:max(free_top, 0)].tolist()
    if len(set(stack)) != len(stack):
        problems.append("free stack holds duplicate physical pages")
    seq_lens = np.asarray(cache["seq_lens"])
    block_tables = np.asarray(cache["block_tables"])
    if prefix is None:
        held = sum(int(pages_for(st.kv_tokens, page))
                   for st in sched.slots.values())
        if sched.free_pages + held != sched.num_pages:
            problems.append(
                f"host page conservation broken: free={sched.free_pages} + "
                f"held={held} != num_pages={sched.num_pages}"
            )
        owned: list[int] = []
        for slot in range(seq_lens.shape[0]):
            n = int(pages_for(int(seq_lens[slot]), page))
            owned.extend(int(p) for p in block_tables[slot, :n])
        if sorted(owned + stack) != list(range(sched.num_pages)):
            leaked = set(range(sched.num_pages)) - set(owned) - set(stack)
            doubled = [p for p, c in Counter(owned + stack).items() if c > 1]
            problems.append(
                f"device page conservation broken: leaked={sorted(leaked)} "
                f"double-owned={sorted(doubled)}"
            )
    else:
        problems.extend(_verify_refcounted(engine, stack, seq_lens,
                                           block_tables))
    # every array of every layer is one of the two kinds of state: paged
    # (a page axis first, or second behind the kv heads: addressed through the
    # block table) or slot-addressed ([num_slots, ...]: a window layer's ring, a recurrent state)
    for i, layer in enumerate(cache["layers"]):
        for name, arr in layer.items():
            if sched.num_pages not in arr.shape[:2] and arr.shape[0] != sched.num_slots:
                problems.append(
                    f"layer {i}: {name} {tuple(arr.shape)} is neither paged (an axis of "
                    f"{sched.num_pages} pages) nor slot-addressed ([{sched.num_slots}, ..])"
                )
    for slot, st in sched.slots.items():
        if int(seq_lens[slot]) != st.kv_tokens:
            problems.append(
                f"slot {slot}: device seq_len={int(seq_lens[slot])} vs host "
                f"kv_tokens={st.kv_tokens}"
            )
    for slot in range(sched.num_slots):
        if slot not in sched.slots and int(seq_lens[slot]) != 0:
            problems.append(
                f"free slot {slot} still carries device seq_len="
                f"{int(seq_lens[slot])}"
            )
    if sorted(sched.free_slots + list(sched.slots)) != list(range(sched.num_slots)):
        problems.append(
            f"slot accounting broken: free={sched.free_slots} "
            f"occupied={sorted(sched.slots)}"
        )
    if engine.adapters is not None:
        in_flight = Counter(
            st.request.adapter_id for st in sched.slots.values()
            if st.request.adapter_id
        )
        for tid in set(in_flight) | set(engine.adapters.refcount):
            want, got = in_flight.get(tid, 0), engine.adapters.refcount.get(tid, 0)
            if want != got:
                problems.append(
                    f"adapter {tid}: refcount={got} vs {want} in-flight holds"
                )
    return problems


def _verify_refcounted(engine, stack, seq_lens, block_tables) -> list[str]:
    """The refcounted page-conservation contract (prefix caching armed):

    - **mirror exact**: each occupied slot's host ``shared_pages`` list
      equals its device block-table row prefix (the COW release keep-count
      arithmetic depends on it);
    - **refcounts exact**: ``refcount[p] == (index holds p) + (slots
      listing p)`` — no phantom or missing holds;
    - **no referenced page on the free stack** — THE double-free a refcount
      bug causes (the host-side twin is ``PrefixCache.pop_pending``'s
      assertion);
    - **conservation**: free stack ∪ refcounted shared pages ∪ per-slot
      private pages covers the pool exactly once (zero leaks, zero double
      owners — a shared page counts ONCE however many rows alias it);
    - **drained**: no page stuck in ``pending_free`` across a tick boundary.
    """
    problems: list[str] = []
    sched = engine.sched
    prefix = engine.prefix
    page = sched.page_size
    slot_holds: Counter = Counter()
    private: list[int] = []
    for slot, st in sched.slots.items():
        k = len(st.shared_pages)
        total = int(pages_for(st.kv_tokens, page))
        row = [int(p) for p in block_tables[slot, :total]]
        if row[:k] != [int(p) for p in st.shared_pages]:
            problems.append(
                f"slot {slot}: shared-prefix mirror diverged — host "
                f"{st.shared_pages} vs device row {row[:k]}"
            )
        slot_holds.update(int(p) for p in st.shared_pages)
        private.extend(row[k:])
    index_pages = set(prefix.index.values())
    for p in set(slot_holds) | index_pages | set(prefix.refcount):
        want = slot_holds.get(p, 0) + (1 if p in index_pages else 0)
        got = prefix.refcount.get(p, 0)
        if want != got:
            problems.append(
                f"page {p}: refcount={got} vs {want} holds "
                f"(index={p in index_pages}, slots={slot_holds.get(p, 0)})"
            )
    shared = set(prefix.refcount)
    referenced_on_stack = shared & set(stack)
    if referenced_on_stack:
        problems.append(
            f"referenced pages on the free stack (double-free): "
            f"{sorted(referenced_on_stack)}"
        )
    if prefix.pending_free:
        problems.append(
            f"pending_free not drained across the tick boundary: "
            f"{prefix.pending_free}"
        )
    dup_private = [p for p, c in Counter(private).items() if c > 1]
    if dup_private:
        problems.append(f"private pages double-owned: {sorted(dup_private)}")
    cover = sorted(list(shared) + private + stack)
    if cover != list(range(sched.num_pages)):
        counts = Counter(list(shared) + private + stack)
        leaked = set(range(sched.num_pages)) - set(counts)
        doubled = [p for p, c in counts.items() if c > 1]
        problems.append(
            f"refcounted page conservation broken: free={len(stack)} + "
            f"shared={len(shared)} + private={len(private)} vs "
            f"pool={sched.num_pages}; leaked={sorted(leaked)} "
            f"double-class={sorted(doubled)}"
        )
    return problems


__all__ = ["DegradationLadder", "verify_serving_invariants"]
