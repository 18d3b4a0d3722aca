"""The one traffic generator: a traffic file's parameters -> the requests (or
the token batches) a run replays.

Serving traffic is a REPLAYED TRACE: due times and lengths are drawn once from
the file's ``trace_seed`` and are identical in every run, whatever ``--seed``;
``--seed`` makes the token ids (and the weights) only.  Gaps are unit-rate
draws divided by ``rate_rps``, so a rate sweep replays the same sequence
faster or slower.  Why: PERF.md section 2 (a run that draws its own arrivals
offers different work each time, and the metric reads the draw)."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class TraceRequest:
    uid: int
    due_s: float          # relative to the window's opening; negative = ramp
    prompt_len: int
    output_len: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def build_trace(traffic: dict, rate_rps: float | None = None) -> list[TraceRequest]:
    """The requests of a serving traffic file, in order.  ``serve_open``:
    exponential gaps at ``rate_rps`` starting ``ramp_s`` before the window.
    ``serve_closed``: a plain list (``due_s`` 0; the client keeps the queue
    topped up in list order)."""
    rng = np.random.default_rng(traffic["trace_seed"])
    n = traffic["num_requests"]
    prompts = _lengths(rng, traffic["prompt_len"], n)
    outputs = _lengths(rng, traffic["output_len"], n)
    if traffic["kind"] == "serve_open":
        rate = rate_rps if rate_rps is not None else traffic["rate_rps"]
        due = np.cumsum(rng.exponential(1.0, n)) / rate - traffic["ramp_s"]
    elif traffic["kind"] == "serve_closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"{traffic['kind']!r} is not serving traffic")
    return [TraceRequest(i, float(due[i]), int(prompts[i]), int(outputs[i])) for i in range(n)]


def prompt_tokens(seed: int, uid: int, length: int, vocab: int) -> tuple:
    """Token ids of one prompt: from ``--seed`` and the request's uid."""
    rng = np.random.default_rng([seed, uid])
    return tuple(int(t) for t in rng.integers(1, vocab, length))


def train_batches(seed: int, n: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """``n`` distinct token batches [n, batch, seq]; every row differs."""
    return np.random.default_rng([seed, 7]).integers(
        1, vocab, (n, batch, seq)).astype(np.int32)
