"""Window arithmetic: from a log of ticks (or steps) on the host clock to the
end-to-end numbers.  Pure functions over plain records, so the tier-1 tests
drive them with synthetic logs.

A tick record is a dict: ``end`` (seconds, relative to the window's opening,
taken after the tick's tokens were on the host), ``start``, ``kind``
(``prefill`` | ``decode`` | ``idle``), ``prompt_tokens`` (prompt tokens whose
prefill chunk finished in it), ``emitted`` (uids that got one token each, at
``end``) and ``active`` (slots that decoded).  A tick belongs to the window
when its END does: tokens are counted where they reached the host."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def in_window(ticks, seconds: float):
    return [t for t in ticks if 0.0 < t["end"] <= seconds]


def tokens_per_s(ticks, seconds: float) -> float:
    """Prompt tokens prefilled plus tokens sampled inside the window, per tick,
    over the window — not by finished requests.  (A 512-token chunk whose tick
    straddles the close moves the rate by 512 / seconds: PERF.md section 2.)"""
    inside = in_window(ticks, seconds)
    return sum(t["prompt_tokens"] + len(t["emitted"]) for t in inside) / seconds


def first_token_times(ticks) -> dict:
    first = {}
    for t in ticks:
        for uid in t["emitted"]:
            first.setdefault(uid, t["end"])
    return first


def ttft_ms(ticks, due: dict, seconds: float, give_up_s: float):
    """Per request DUE inside the window: first-token time minus due time, ms.
    One without a first token by ``give_up_s`` (relative to the opening) has
    failed; it stays in the sample at the time it had waited by then.
    Returns (values by uid, failed uids)."""
    first = first_token_times(ticks)
    values, failed = {}, []
    for uid, d in due.items():
        if not 0.0 <= d < seconds:
            continue
        t = first.get(uid)
        if t is None or t > give_up_s:
            failed.append(uid)
            t = give_up_s
        values[uid] = (t - d) * 1e3
    return values, failed


def tpot_ms(ticks, seconds: float, min_tokens: int = 8) -> dict:
    """Per request with at least ``min_tokens`` tokens INSIDE the window:
    (last - first in-window token time) / (in-window tokens - 1), ms.  Needs
    no drain and no finished request."""
    times: dict = {}
    for t in in_window(ticks, seconds):
        for uid in t["emitted"]:
            times.setdefault(uid, []).append(t["end"])
    return {uid: (ts[-1] - ts[0]) / (len(ts) - 1) * 1e3
            for uid, ts in times.items() if len(ts) >= min_tokens}


def backlog(ticks, due: dict, at_s: float) -> int:
    """Requests due by ``at_s`` whose first token had not come by then."""
    first = first_token_times(ticks)
    return sum(1 for uid, d in due.items()
               if d <= at_s and first.get(uid, float("inf")) > at_s)


def steps_tokens_per_s(step_ends, tokens_per_step: int) -> float:
    """Training: ALL the window's steps over all of its time.  The window opens
    at a step boundary and closes at the end of the step in flight when
    ``--seconds`` ran out, so no whole-step count jumps on a hair's difference."""
    return len(step_ends) * tokens_per_step / step_ends[-1]
