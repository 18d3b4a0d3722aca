"""Speculative multi-token decode: draft providers + acceptance arithmetic.

Decode emits one token per verify pass in the base engine; speculative
decoding (Leviathan et al.-style draft-and-verify) proposes ``k`` candidate
tokens per slot and runs ONE fixed-shape batched verify pass of width
``k + 1`` through the paged cache, accepting the longest greedy-matching
prefix.  Greedily accepted tokens are BITWISE identical to what sequential
single-token decode would have produced — the existing ``generate()``
token-parity pin extends rather than weakens (tests/test_speculate.py).

Two draft providers:

- :class:`NgramDraft` — prompt-lookup / n-gram self-drafting.  Pure
  host-side and model-free: the slot's context (prompt + emitted tokens) is
  searched for the most recent earlier occurrence of its own trailing
  n-gram, and the tokens that followed that occurrence become the proposal.
  Zero extra device programs, zero extra weights; the draft cost is host
  string-matching (measured into ``draft_overhead_frac``).
- :class:`DraftModelDraft` — a small draft model proposes ``k`` tokens
  greedily from a fixed context window through ONE jitted fixed-shape
  forward (no draft KV cache to keep in sync with eviction/rollback), so
  ``strict_compiles`` still holds after :meth:`DraftModelDraft.warmup`.

Rejected drafts cost nothing but the verify lane they rode in: the verify
program rolls speculatively-consumed pages back onto the functional
free-list (``ops/paged_cache.push_pages``) and the host mirror stays exact via
per-slot accepted-length bookkeeping (``scheduler.note_verify``).

:func:`predicted_acceptance` is the CheckFreq-style predicted twin: a
model-free replay of the draft-and-verify arithmetic over the MEASURED
token streams (greedy target tokens ARE the final stream, so per-pass
acceptance is computable from the streams + the drafting algorithm alone).
The prediction error vs the measured twin is the eviction/recompute
traffic the replay cannot know about.
"""

from __future__ import annotations

import time
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np


class NgramDraft:
    """Prompt-lookup self-drafting (host-side, no extra model).

    For each slot, the trailing ``n``-gram of the context (``max_ngram``
    down to ``min_ngram``) is searched for its most recent earlier
    occurrence; the up-to-``k`` tokens that followed that occurrence are the
    proposal.  Deterministic: same context -> same drafts, always (the
    scheduler-determinism contract extends through drafting).  ``window``
    bounds the backward search so drafting stays O(window) per slot on
    arbitrarily long contexts.
    """

    name = "ngram"
    programs = 0  # host-side: no compiled draft program

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 window: int = 512):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"({min_ngram}, {max_ngram})"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.window = window

    def propose_one(self, context: Sequence[int], k: int) -> list:
        ctx = list(context)[-self.window:]
        n_ctx = len(ctx)
        for n in range(min(self.max_ngram, n_ctx - 1), self.min_ngram - 1, -1):
            tail = ctx[n_ctx - n:]
            best: list = []
            # most recent match with a FULL k-token continuation wins;
            # otherwise the longest continuation seen (a trailing cycle's
            # matches near the end are cut short by the context boundary)
            for i in range(n_ctx - n - 1, -1, -1):
                if ctx[i:i + n] == tail:
                    cont = ctx[i + n:i + n + k]
                    if len(cont) == k:
                        return cont
                    if len(cont) > len(best):
                        best = cont
            if best:
                return best
        return []

    def propose(self, contexts: list, k: int,
                adapter_ids=None) -> tuple[np.ndarray, np.ndarray]:
        """Batched proposal: ``(drafts [n, k] int32, draft_lens [n])``.
        Slots with no n-gram hit draft nothing (their verify lane
        degenerates to plain single-token decode).  ``adapter_ids`` is
        accepted for interface parity with the draft-model provider — an
        n-gram over the slot's own context is already tenant-specific."""
        n = len(contexts)
        drafts = np.zeros((n, k), np.int32)
        lens = np.zeros((n,), np.int32)
        for i, ctx in enumerate(contexts):
            prop = self.propose_one(ctx, k)
            lens[i] = len(prop)
            drafts[i, :len(prop)] = prop
        return drafts, lens

    def warmup(self, n_slots: int, k: int) -> None:
        """Host-side provider: nothing to compile."""


@lru_cache(maxsize=8)
def _draft_fns(model, window: int):
    """The jitted draft forward, shared across engines of the same (draft
    model, window): ``[n, window]`` right-padded ids + per-slot lengths ->
    the greedy next token per slot.  ONE fixed-shape program — the draft
    loop calls it ``k`` times per verify pass, never recompiling
    (``strict_compiles`` holds after warmup)."""
    import jax
    import jax.numpy as jnp

    def next_token(params, ids, lens):
        positions = jnp.broadcast_to(jnp.arange(window), ids.shape)
        logits = model.apply(params, ids, positions=positions)
        last = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1
        )[:, 0]
        return jnp.argmax(last.astype(jnp.float32), axis=-1).astype(jnp.int32)

    return jax.jit(next_token)


class DraftModelDraft:
    """Draft-model provider: a small model proposes ``k`` greedy tokens.

    Stateless by design: each draft token re-forwards the slot's trailing
    ``window`` tokens through one jitted fixed-shape program (a draft KV
    cache would have to mirror every eviction/rollback of the target cache;
    a windowed forward of a model this small costs less than that
    bookkeeping).  The window slides when full, so contexts of any length
    draft at fixed shape.
    """

    name = "draft"
    programs = 1  # the windowed next-token forward

    def __init__(self, model, params, window: int = 32):
        if window < 2:
            raise ValueError(f"draft window must be >= 2, got {window}")
        self.model = model
        self.params = params
        self.window = window
        self._next = _draft_fns(model, window)

    def propose(self, contexts: list, k: int,
                adapter_ids=None) -> tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp

        n = len(contexts)
        w = self.window
        ids = np.zeros((n, w), np.int32)
        lens = np.zeros((n,), np.int32)
        for i, ctx in enumerate(contexts):
            tail = list(ctx)[-w:]
            ids[i, :len(tail)] = tail
            lens[i] = max(1, len(tail))
        drafts = np.zeros((n, k), np.int32)
        for j in range(k):
            tok = np.asarray(self._next(self.params, jnp.asarray(ids),
                                        jnp.asarray(lens)))
            drafts[:, j] = tok
            # slide: append the drafted token, dropping the oldest when full
            full = lens >= w
            ids[full] = np.roll(ids[full], -1, axis=1)
            ids[np.arange(n), np.where(full, w - 1, lens)] = tok
            lens = np.minimum(lens + 1, w)
        return drafts, np.full((n,), k, np.int32)

    def warmup(self, n_slots: int, k: int) -> None:
        """Compile the draft forward before traffic (one program)."""
        self.propose([[1]] * max(1, n_slots), max(1, k))


def make_draft_provider(mode: str, *, draft_model=None, draft_params=None,
                        window: int = 32, max_ngram: int = 3):
    """Resolve a ``ServingPlugin.speculate`` mode to a provider instance."""
    if mode == "ngram":
        return NgramDraft(max_ngram=max_ngram)
    if mode == "draft":
        if draft_model is None or draft_params is None:
            raise ValueError(
                "speculate='draft' needs draft_model and draft_params "
                "(pass them to ServingEngine / generate_paged)"
            )
        return DraftModelDraft(draft_model, draft_params, window=window)
    raise ValueError(f"unknown speculate mode {mode!r} (ngram | draft)")


class Speculator:
    """Host-side drafting state for one engine: the provider, the depth
    ``k``, the verify bucket ladder, and the draft-time accounting the
    ``draft_overhead_frac`` bench field reads."""

    def __init__(self, provider, k: int, buckets: tuple):
        if k < 1:
            raise ValueError(f"speculate_k must be >= 1, got {k}")
        self.provider = provider
        self.k = k
        self.buckets = tuple(sorted(buckets))
        if not self.buckets or self.buckets[-1] < k:
            raise ValueError(
                f"speculate_buckets {buckets} must include a bucket >= k={k}"
            )
        self.draft_time_s = 0.0

    def bucket_for(self, depth: int) -> int:
        for b in self.buckets:
            if b >= depth:
                return b
        return self.buckets[-1]

    def draft(self, contexts: list, remaining: list,
              adapter_ids=None) -> tuple[np.ndarray, np.ndarray]:
        """Propose drafts for the active slots and clamp per-slot depth:
        ``spec_len[i] = min(draft_len, k, remaining-1)`` — a slot one token
        from ``max_new_tokens`` verifies at depth 0 (plain decode in lane
        0), so speculation can never overrun a request's token budget (or,
        transitively, its submit-guarded page capacity)."""
        t0 = time.perf_counter()
        drafts, lens = self.provider.propose(contexts, self.k, adapter_ids)
        self.draft_time_s += time.perf_counter() - t0
        spec_len = np.minimum(
            lens.astype(np.int64),
            np.maximum(np.asarray(remaining, np.int64) - 1, 0),
        ).astype(np.int32)
        return drafts, spec_len


def predicted_acceptance(trace, results: dict, provider, k: int) -> dict:
    """The predicted twin: replay draft-and-verify arithmetic over the
    measured token streams (no model, no device).  For each request, walk
    its final stream: at ``e`` emitted tokens the engine would verify with
    drafts proposed from ``prompt + stream[:e]`` at depth
    ``min(k, max_new - e - 1, draft_len)``; the greedy targets ARE the
    stream, so the accepted prefix length is exact.  Returns
    ``accept_rate`` (accepted drafts / drafted tokens) and
    ``tokens_per_step`` (verify-emitted tokens per verify pass) — the
    measured twins' error vs this is the eviction/recompute re-decode
    traffic the replay cannot see."""
    drafted = accepted = passes = emitted = 0
    window = getattr(provider, "window", None)
    for req in trace:
        stream = results.get(req.uid)
        if not stream:
            continue
        prompt = list(req.prompt)
        e = 1  # the first token is sampled off the prefill logits
        while e < len(stream):
            depth = max(min(k, req.max_new_tokens - e - 1), 0)
            m = 0
            if depth > 0:
                # propose at full k, then clamp — exactly the engine's
                # Speculator.draft order (the provider may pick a different
                # match site for a different k).  Context carries only the
                # provider's trailing window, like the engine's verify tick
                # (a full prompt+stream rebuild per pass is quadratic)
                ctx = prompt + stream[:e] if window is None else \
                    (stream[e - window:e] if e >= window
                     else prompt[e - window:] + stream[:e])
                draft, dl = provider.propose([ctx], k)
                depth = min(depth, int(dl[0]))
                while m < depth and e + m < len(stream) \
                        and int(draft[0, m]) == stream[e + m]:
                    m += 1
            out = min(m + 1, len(stream) - e)
            drafted += depth
            accepted += m
            emitted += out
            passes += 1
            e += out
    return {
        "accept_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "tokens_per_step": round(emitted / passes, 4) if passes else 0.0,
        "drafted": drafted,
        "accepted": accepted,
        "verify_passes": passes,
    }


def speculative_page_need(kv_tokens: int, depth: int, page_size: int) -> int:
    """Worst-case fresh pages one slot's verify pass can consume: page
    starts among the written positions ``[kv, kv + depth]``."""
    from ..ops.paged_cache import pages_for

    return int(pages_for(kv_tokens + depth + 1, page_size)
               - pages_for(kv_tokens, page_size))


__all__ = [
    "NgramDraft",
    "DraftModelDraft",
    "Speculator",
    "make_draft_provider",
    "predicted_acceptance",
    "speculative_page_need",
]
