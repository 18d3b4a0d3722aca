"""Shared arithmetic of the per-layer readers (``perfbench/metrics/*.py``).
Every function takes the run's record and returns a number, or None when the
run holds nothing to read (the harness then leaves the metric out).

The record: ``trace`` (``trace_reduce`` output, traced runs only), ``ticks``
(serving), ``step_ends`` (training), ``end_to_end``, ``peaks``,
``host_window_s`` (the part of the window before the profiler started: host
clock readings keep clear of the profiler's own cost)."""

from __future__ import annotations

import statistics

from perfbench import window as W


def _programs(run, needle):
    trace = run.get("trace")
    if not trace:
        return []
    return [p for n, p in trace["programs"].items() if needle in n]


def program_median_ms(run, needle):
    durs = [d for p in _programs(run, needle) for d in p["durations_s"]]
    return statistics.median(durs) * 1e3 if durs else None


def main_program_median_ms(run):
    """The program that took most device time (training: the step)."""
    trace = run.get("trace")
    if not trace or not trace["programs"]:
        return None
    top = max(trace["programs"].values(), key=lambda p: sum(p["durations_s"]))
    return statistics.median(top["durations_s"]) * 1e3


def prefill_large_bucket_ms(run):
    """Prefill program runs and traced prefill ticks come in the same order;
    keep the runs whose tick used the larger bucket."""
    progs = _programs(run, "prefill")
    ticks = [t for t in run.get("ticks", []) if t["traced"] and t["kind"] == "prefill" and t["bucket"]]
    runs = sorted((s, d) for p in progs for s, d in zip(p["starts_s"], p["durations_s"]))
    if not runs or len(runs) != len(ticks):
        return None
    top = max(t["bucket"] for t in ticks)
    kept = [d for (_, d), t in zip(runs, ticks) if t["bucket"] == top]
    return statistics.median(kept) * 1e3 if kept else None


def class_ms_per_program_run(run, cls, needles=("decode", "prefill")):
    progs = [p for n in needles for p in _programs(run, n)]
    runs = sum(p["runs"] for p in progs)
    return sum(p["class_s"].get(cls, 0.0) for p in progs) / runs * 1e3 if runs else None


def idle_share_pct(run):
    trace = run.get("trace")
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0 if trace else None


def host_ticks(run):
    return W.in_window(run.get("ticks", []), run.get("host_window_s", 0.0))


def tick_wall_ms(run):
    ticks = [t["end"] - t["start"] for t in host_ticks(run) if t["kind"] != "idle"]
    return statistics.median(ticks) * 1e3 if ticks else None


def occupancy_pct(run):
    dec = [t["active"] for t in host_ticks(run) if t["kind"] == "decode"]
    return sum(dec) / len(dec) / run["num_slots"] * 100.0 if dec else None


def kernel_time_s(run, program_needle):
    trace = run.get("trace")
    if not trace:
        return []
    return [x for k, v in trace["kernel_calls_s"].items() if program_needle in k.split(":")[0]
            for x in v]


def main_program_runs(run):
    trace = run.get("trace")
    top = max(trace["programs"].values(), key=lambda p: sum(p["durations_s"]))
    return top["runs"]


def flash_kernel_seconds(run, which):
    """Device seconds of the flash forward (``fwd``) or backward (``bwd``: dq
    and dk/dv) kernel calls in the traced window, by the kernel's label."""
    trace = run.get("trace")
    if not trace:
        return []
    return [x for key, calls in trace["kernel_calls_s"].items()
            if key.rsplit(":", 1)[-1] == which for x in calls]
