"""The one reduction from a ``jax.profiler`` trace to device busy/idle,
per-program and per-op-class device time, and the attribution of idle gaps to
what the host was doing.

A COPY of what is sound in ``benchmarks/serve_trace.py::reduce`` and
``accelerate_tpu/utils/xplane.py::classify_op`` (the yardstick may not move
when the program does), extended to several devices, nested ops, collectives
and gap attribution.  Checked in tier-1 against the small recorded trace in
``perfbench/testdata/``.

Input: the ``*.trace.json.gz`` the profiler writes beside its ``xplane.pb``.
Each TPU is a process ``/device:TPU:<n>`` whose threads ``XLA Modules`` (one
event per program run) and ``XLA Ops`` (one per HLO op, ``args.long_name`` the
HLO text) lie on the trace's common microsecond timeline (``ts``, ``dur``).
The traced window is the host's ``perfbench_window`` annotation.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict

WINDOW_EVENT = "perfbench_window"
_SUFFIX_RE = re.compile(r"\.[0-9]+(\.remat)?$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def _lhs_base(name: str) -> str:
    """``%convolution_add_fusion.82 = ...`` -> ``convolution_add_fusion``."""
    lhs = name.split(" = ")[0].lstrip("%").strip()
    return _SUFFIX_RE.sub("", lhs)


def classify_op(name: str) -> str:
    """One HLO event name -> op class (heuristics tuned on v5e traces of this
    package: Pallas kernels are custom-calls that keep the model scope name;
    unnamed ``fusion.N`` output fusions are the matmul-rooted ones)."""
    low = _lhs_base(name).lower()
    full = name.lower()
    if " custom-call(" in full or low.startswith("custom-call"):
        marks = ("self_attn", "flash", "mha", "attention", "paged")
        return "attention_kernel" if any(m in full for m in marks) else "pallas_other"
    if any(k in low for k in COLLECTIVES):
        return "collective"
    if low.startswith(("copy", "send", "recv", "infeed", "outfeed")):
        return "copy"
    if low.startswith("while"):
        return "while_loops"
    if "dynamic-update" in low or "dynamic-slice" in low or low.startswith(("scatter", "gather")):
        return "dynamic_slice"
    if low.startswith(("convolution", "dot", "einsum", "fusion")):
        return "matmul"
    if "fusion" in low:
        return "elementwise_fusion"
    if low.startswith("convert"):
        return "convert"
    return "other"


def op_shape(long_name: str) -> str:
    """``%copy.3 = bf16[8,1024,64,128]{3,2,1,0} copy(...)`` -> ``bf16_8_1024_64_128_``."""
    rhs = long_name.split(" = ", 1)[-1]
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    return re.sub(r"[^A-Za-z0-9]", "_", m.group(1)) if m else ""


def program_name(event_name: str) -> str:
    """``jit_decode_legacy(1234)`` (device line) or ``PjitFunction(decode_legacy)``
    (the host's view, CPU rehearsal) -> ``decode_legacy``."""
    if event_name.startswith("PjitFunction("):
        return event_name[len("PjitFunction("):].rstrip(")")
    name = re.sub(r"\(.*", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def union_length(intervals, lo=None, hi=None) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if end is None or s > end:
            total, end = total + (e - s), e
        elif e > end:
            total, end = total + (e - end), e
    return total


def gaps_of(intervals, lo, hi):
    """The idle intervals inside [lo, hi] left by ``intervals``."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def self_times(ops):
    """ops sorted by start: (start, dur, payload).  An op that encloses later
    ones (a ``while``) keeps only the time its children do not cover."""
    out, stack = [], []
    for s, d, p in ops:
        while stack and s >= stack[-1][0] + stack[-1][1]:
            stack.pop()
        if stack:
            stack[-1][2][0] -= d
        item = (s, d, [d], p)
        out.append(item)
        stack.append(item)
    return [(s, max(self_d[0], 0.0), p) for s, _, self_d, p in out]


def _load(trace_dir) -> list:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    with gzip.open(max(paths, key=os.path.getmtime)) as f:
        return json.load(f)["traceEvents"]


def reduce_dir(trace_dir, spans=(), anchor=None, fallback_host=False) -> dict:
    return reduce_events(_load(trace_dir), spans, anchor, fallback_host)


def reduce_events(events, spans=(), anchor=None, fallback_host=False) -> dict:
    """``spans``: host spans ``(name, start_s, end_s)`` on the clock ``anchor``
    was read from (taken as the ``perfbench_window`` annotation opened).
    Times in the result are seconds."""
    meta = lambda key: {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
                        if e.get("ph") == "M" and e.get("name") == key}
    procs = {pid: n for (pid, _), n in meta("process_name").items()}
    threads = meta("thread_name")
    device_pids = sorted(p for p, n in procs.items() if n.startswith("/device:TPU:"))
    xs = [e for e in events if e.get("ph") == "X"]
    win = next((e for e in xs if e["name"] == WINDOW_EVENT), None)
    if win is None:
        raise ValueError(f"the trace holds no {WINDOW_EVENT!r} annotation")
    lo, hi = win["ts"], win["ts"] + win["dur"]

    per_device = []
    if device_pids:
        for pid in device_pids:
            line = lambda name: sorted(
                ((e["ts"], e["dur"], e) for e in xs
                 if e["pid"] == pid and threads.get((pid, e["tid"])) == name),
                key=lambda t: t[0])
            per_device.append((line("XLA Modules"), line("XLA Ops")))
    elif fallback_host:   # the CPU rehearsal has no device process: programs as the host ran them
        mods = sorted(((e["ts"], e["dur"], e) for e in xs if e["name"].startswith("PjitFunction(")),
                      key=lambda t: t[0])
        per_device.append((mods, []))
    else:
        raise ValueError("the trace holds no /device:TPU:* process")

    busy, programs = [], defaultdict(lambda: {"runs": [], "starts": [], "classes": defaultdict(float)})
    op_time, class_time, kernel_calls = defaultdict(float), defaultdict(float), defaultdict(list)
    exposed_collective = []
    for d, (mods, ops) in enumerate(per_device):
        mods = [(s, dur, e) for s, dur, e in mods if s >= lo and s + dur <= hi]
        ops = [(s, dur, e) for s, dur, e in ops if s >= lo and s + dur <= hi]
        basis = ops if ops else mods
        busy.append(union_length([(s, s + dur) for s, dur, _ in basis], lo, hi))
        i, ops_self = 0, self_times(ops)
        coll = 0.0
        for s, dur, e in mods:
            name = program_name(e["name"])
            if d == 0:
                programs[name]["runs"].append(dur)
                programs[name]["starts"].append(s)
            while i < len(ops_self) and ops_self[i][0] < s + dur:
                os_, od, oe = ops_self[i]
                i += 1
                if os_ < s:
                    continue
                long_name = oe.get("args", {}).get("long_name", oe["name"])
                cls = classify_op(long_name)
                if cls == "collective":
                    coll += od
                if d == 0:
                    programs[name]["classes"][cls] += od
                    class_time[cls] += od
                    op_time[f"{cls}:{name}:{op_shape(long_name)}"] += od
                    if cls == "attention_kernel":
                        # the backward kernels sit under jax's transpose(jvp(...)) scope
                        way = "bwd" if "transpose(" in oe.get("args", {}).get("tf_op", "") else "fwd"
                        kernel_calls[(name, op_shape(long_name), way)].append(od)
        exposed_collective.append(coll)

    # idle gaps of the first device, booked to the innermost host span that covers them
    first = per_device[0][1] or per_device[0][0]
    gaps = gaps_of([(s, s + dur) for s, dur, _ in first if s >= lo and s + dur <= hi], lo, hi)
    booked = defaultdict(float)
    if anchor is not None:
        to_ts = lambda t: lo + (t - anchor) * 1e6
        by_name = defaultdict(list)
        for name, s, e in spans:
            by_name[name.split(":")[0]].append((to_ts(s), to_ts(e)))
        outer = by_name.pop("step", [])
        for g0, g1 in gaps:
            inner_total = 0.0
            for name, ivs in by_name.items():
                got = union_length(ivs, g0, g1)
                booked[name] += got
                inner_total += got
            in_step = union_length(outer, g0, g1)
            booked["step"] += max(in_step - inner_total, 0.0)
            booked["outside_step"] += max((g1 - g0) - max(in_step, inner_total), 0.0)
    else:
        booked["unattributed"] = sum(g1 - g0 for g0, g1 in gaps)

    us = 1e-6
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    if not top:   # no op line (rehearsal): programs stand in
        top = sorted(((f"program:{n}:", sum(r["runs"])) for n, r in programs.items()),
                     key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * us,
        "busy_s": sum(busy) / len(busy) * us,
        "devices": len(per_device),
        "programs": {n: {"runs": len(r["runs"]), "durations_s": [x * us for x in r["runs"]],
                         "starts_s": [x * us for x in r["starts"]],
                         "class_s": {c: t * us for c, t in r["classes"].items()}}
                     for n, r in programs.items()},
        "class_s": {c: t * us for c, t in class_time.items()},
        "kernel_calls_s": {f"{n}:{shape}:{way}": [x * us for x in v]
                           for (n, shape, way), v in kernel_calls.items()},
        "exposed_collective_s": sum(exposed_collective) / len(exposed_collective) * us,
        "top_ops": [[n, t * us] for n, t in top[:10]],
        "idle_gaps": [[n, t * us] for n, t in sorted(booked.items(), key=lambda kv: -kv[1])
                      if t > 0][:10],
    }
