"""What the program itself writes into a ``jax.profiler`` trace, read back:
the engine's phase spans (``telemetry/spans.py`` opens a ``TraceAnnotation``
for each, so they lie on the timeline of the device's lines and need no clock
anchor), the ``jax.named_scope``s inside the programs and the Pallas kernels'
names (both arrive in an op's ``tf_op``).  ``trace_reduce`` stays the one
reduction of busy/idle, programs and op classes; this module only adds what
can be told by NAME.  A trace of a program that writes none of it (the parent
of the PR that added this file) parses to empty lists and every reader
returns None.

``python3 perfbench/program_trace.py [trace_dir]`` prints, for the newest trace
under ``trace_dir`` (default ``.perfbench_trace``): the tick partition with the
causality margins, the ten largest ops with the layer their names put them to,
and the share of device time that no name claims.
"""

from __future__ import annotations

import json
import re
import statistics
import sys
from collections import defaultdict
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from perfbench.trace_reduce import (WINDOW_EVENT, _load, classify_op, op_shape, program_name,
                                    self_times, union_length)

TRACE_ROOT = Path(__file__).resolve().parent.parent / ".perfbench_trace"
TICK_KINDS = ("decode", "prefill", "verify")          # stage:<kind> of a tick's main program
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# name in tf_op -> the layer of PERF.md section 3 it belongs to
SCOPES = {"fused_xent": "train step", "optimizer_update": "train step",
          "paged_write_kv": "model step", "sample": "model step",
          **{k: "kernels" for k in FLASH_KERNELS + ("paged_decode", "paged_multitoken",
                                                    "fused_bgmv_decode", "bgmv", "qmm",
                                                    "qmm_wholef")}}
_MODULE_RE = re.compile(r"/(layers_[0-9]+|embed_tokens|lm_head|norm)(/|:|$)")


def scope_of(tf_op: str):
    """The named scope or kernel name in an op's ``tf_op`` (a ``/``-separated
    name stack that ends in the primitive), or None.  jax writes a scope inside
    a transform as ``transpose(jvp(fused_xent))``: the words are what count."""
    words = set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", tf_op))
    return next((s for s in SCOPES if s in words), None)


def layer_of(tf_op: str, scope=None):
    scope = scope or scope_of(tf_op)
    if scope:
        return SCOPES[scope]
    return "model (flax module)" if _MODULE_RE.search(tf_op) else None


def parse(events) -> dict:
    """Device 0's programs and ops and the host's annotations, inside the
    ``perfbench_window``; times in microseconds on the trace's timeline.
    ``ops``: (start, self time, tf_op, HLO text, index into ``modules`` or -1,
    ``scope_of(tf_op)``);
    ``host``: name -> [(start, end, step)], from the thread that opened the
    window (the engine's thread)."""
    meta = lambda key: {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
                        if e.get("ph") == "M" and e.get("name") == key}
    procs, threads = meta("process_name"), meta("thread_name")
    xs = [e for e in events if e.get("ph") == "X"]
    win = next((e for e in xs if e["name"] == WINDOW_EVENT), None)
    if win is None:
        return {}
    lo, hi = win["ts"], win["ts"] + win["dur"]
    inside = lambda e: e["ts"] >= lo and e["ts"] + e["dur"] <= hi
    pid = min((p for (p, _), n in procs.items() if n.startswith("/device:TPU:")), default=None)
    line = lambda name: sorted((e for e in xs if e["pid"] == pid and inside(e)
                                and threads.get((pid, e["tid"])) == name), key=lambda e: e["ts"])
    modules = [(e["ts"], e["dur"], program_name(e["name"])) for e in line("XLA Modules")]
    ops, m = [], 0
    for ts, self_dur, e in self_times([(e["ts"], e["dur"], e) for e in line("XLA Ops")]):
        while m < len(modules) and modules[m][0] + modules[m][1] <= ts:
            m += 1
        owner = m if m < len(modules) and modules[m][0] <= ts else -1
        args = e.get("args", {})
        tf_op = args.get("tf_op", "")
        ops.append((ts, self_dur, tf_op, args.get("long_name", e["name"]), owner, scope_of(tf_op)))
    host = defaultdict(list)
    for e in xs:
        if (e["pid"], e["tid"]) == (win["pid"], win["tid"]) and e is not win and inside(e):
            args = e.get("args", {})
            step = args.get("step")
            # the trace viewer shows ``stage:decode`` as ``decode`` and keeps the whole name here
            host[args.get("long_name", e["name"])].append(
                (e["ts"], e["ts"] + e["dur"], None if step is None else int(step)))
    return {"lo": lo, "hi": hi, "modules": modules, "ops": ops, "host": dict(host)}


def of(run):
    """The parsed trace of a traced run (cached on the record), else None."""
    if not run.get("trace"):
        return None
    if "program_trace" not in run:
        run["program_trace"] = parse(_load(run.get("trace_dir", TRACE_ROOT)))
    return run["program_trace"] or None


# -- the serving tick ------------------------------------------------------------------


def ticks(pt) -> list:
    """One entry per traced tick that ran a program.  A tick's program is the
    next run of ``<kind>*`` on the device that no earlier tick has claimed
    (never chosen by its start time, so that a program which seems to start
    before the host launched it shows as a negative margin); a prefill tick
    that samples ends with the next unclaimed ``sample*`` run.

    ``launch``: the device's idle time between the start of the tick's
    ``stage:*`` span and the start of its program (what the previous program
    still covers is not counted); ``sync``: end of the tick's last ``host_sync``
    minus the end of its last program, as idle time of the device (None for a
    prefill chunk that samples nothing); ``between``: the device's idle time from the previous tick's last
    ``host_sync`` end to this ``stage`` start (commit, trace, control, schedule,
    plan and the caller's own time); ``gap``: the device's idle time from the
    previous tick's last program end to this tick's program start, from the
    device lines alone.  For consecutive ticks ``sync`` of the one + ``between``
    + ``launch`` of the next = ``gap``.

    The causality check: ``start_margin`` (program start minus ``stage`` start),
    ``dispatch_margin`` (program start minus ``dispatch:*`` start: the tighter
    one, a program cannot start before the call that launches it) and
    ``end_margin`` (``host_sync`` end minus the last program's end).  The profiler lays host and device events on
    one timeline with an error of its own: where the device's events lie
    ``d`` too early, ``launch`` reads ``d`` short and ``sync`` ``d`` long, and
    their sum is right.  Microseconds."""
    host, modules = pt["host"], pt["modules"]
    busy = [(s, s + d) for s, d, _ in modules]
    idle = lambda a, b: (b - a) - union_length(busy, a, b) if b > a else 0.0
    stages = sorted((s, step, kind) for kind in TICK_KINDS
                    for s, _, step in host.get(f"stage:{kind}", []))
    of_step = lambda name: {step: (s, e) for s, e, step in sorted(host.get(name, []))}
    syncs, samples = of_step("host_sync"), of_step("stage:sample")     # a step's last
    launches = {kind: of_step(f"dispatch:{kind}") for kind in TICK_KINDS}
    # a program that started before the window's first tick staged belongs to no tick here
    runs = {kind: [(s, s + d) for s, d, n in modules if kind in n and stages and s >= stages[0][0]]
            for kind in TICK_KINDS + ("sample",)}

    def claim(kind, after):
        while runs[kind] and runs[kind][0][1] <= after:
            runs[kind].pop(0)
        return runs[kind].pop(0) if runs[kind] else None

    out, prev = [], None
    for s0, step, kind in stages:
        own = claim(kind, s0)
        if own is None:
            continue
        start, end = own
        h1 = syncs.get(step, (None, None))[1]
        if step in samples:
            end = (claim("sample", samples[step][0]) or own)[1]
        launched = launches[kind].get(step)
        tick = {"step": step, "kind": kind, "launch": idle(s0, start),
                "sync": None if h1 is None else idle(end, h1),
                "start_margin": start - s0,
                "dispatch_margin": None if launched is None else start - launched[0],
                "end_margin": None if h1 is None else h1 - end,
                "between": idle(prev["host_end"], s0) if prev else None,
                "gap": idle(prev["device_end"], start) if prev else None,
                "host_end": s0 if h1 is None else h1, "device_end": end}
        out.append(tick)
        prev = tick
    return out


def tick_median_ms(run, key):
    pt = of(run)
    values = [t[key] for t in ticks(pt) if t[key] is not None] if pt else []
    return statistics.median(values) * 1e-3 if values else None


# -- device time by name ---------------------------------------------------------------


def scoped_us(pt, names, programs=None) -> float:
    """Device self-time of the ops whose ``tf_op`` holds one of ``names``, inside
    whole runs of the programs whose name holds one of ``programs`` (any)."""
    return sum(d for _, d, _, _, owner, scope in pt["ops"]
               if owner >= 0 and scope in names
               and (programs is None or any(p in pt["modules"][owner][2] for p in programs)))


def main_program(pt):
    total = defaultdict(float)
    for _, d, n in pt["modules"]:
        total[n] += d
    return max(total, key=total.get) if total else None


def scoped_ms_per_run(run, names, programs=None):
    """``scoped_us`` over the number of program runs; ``programs`` None = the
    program that took most device time (training: the step).  None where the
    trace names nothing so (an untraced run, a program without the scopes)."""
    pt = of(run)
    if not pt:
        return None
    programs = programs or (main_program(pt),)
    runs = sum(any(p in n for p in programs) for _, _, n in pt["modules"])
    total = scoped_us(pt, names, programs)
    return total / runs * 1e-3 if runs and total else None


def engine_mean_ms(run, stem):
    """``<stem>_s_sum / <stem>_n`` of the engine's always-on counters, over the
    WHOLE run (ramp and drain included: the counters know no window)."""
    m = run.get("engine_metrics") or {}
    n = m.get(f"{stem}_n")
    return m[f"{stem}_s_sum"] / n * 1e3 if n else None


# -- the report ------------------------------------------------------------------------


def report(pt) -> dict:
    """The tick partition with its causality margins, the share of device time
    each layer's names claim, and the ten largest ops (``trace_reduce``'s key)
    with the names found on them.  An op no name claims is looked up one hop
    along the HLO text: the scope of an op it reads from or is read by (a
    relayout copy the compiler put around a scoped scatter carries no name of
    its own)."""
    us = lambda x: None if x is None else round(x, 1)
    out = {}
    tk = ticks(pt)
    if tk:
        med = lambda k: us(statistics.median([t[k] for t in tk if t[k] is not None] or [0.0]))
        least = lambda k: min((t[k] for t in tk if t[k] is not None), default=None)
        out["ticks"] = {
            "n": len(tk), "median_us": {k: med(k) for k in ("launch", "sync", "between", "gap")},
            "min_start_margin_us": us(least("start_margin")),
            "min_dispatch_margin_us": us(least("dispatch_margin")),
            "min_end_margin_us": us(least("end_margin")),
            "worst_identity_error_us": us(max(
                (abs(p["sync"] + t["between"] + t["launch"] - t["gap"])
                 for p, t in zip(tk, tk[1:]) if p["sync"] is not None), default=0.0)),
            "host_span_median_us": {n: us(statistics.median(e - s for s, e, _ in v))
                                    for n, v in sorted(pt["host"].items())
                                    if v[0][2] is not None},
            "annotations_per_tick": round(sum(len(v) for v in pt["host"].values()
                                              if v[0][2] is not None) / len(tk), 2)}
    hlo_name = lambda long: long.split(" = ")[0].lstrip("%").strip()
    operands = lambda long: re.findall(r"%([A-Za-z0-9_.\-]+)", long.split(" = ", 1)[-1])
    neighbour = defaultdict(set)     # HLO name -> scopes of the ops that it feeds or that feed it
    for _, _, _, long, _, scope in pt["ops"]:
        for name in ([hlo_name(long)] + operands(long)) if scope else ():
            neighbour[name].add(scope)
    by_op, share, linked, total = defaultdict(lambda: [0.0, set()]), defaultdict(float), \
        defaultdict(float), 0.0
    for _, d, tf_op, long, owner, scope in pt["ops"]:
        if owner < 0:
            continue
        total += d
        layer = layer_of(tf_op, scope)
        found = scope or layer
        if found is None:
            via = set().union(*(neighbour.get(n, ()) for n in [hlo_name(long)] + operands(long)))
            if via:
                found = "beside " + "+".join(sorted(via))
                linked[found] += d
        share[layer or "none"] += d
        key = f"{classify_op(long)}:{pt['modules'][owner][2]}:{op_shape(long)}"
        by_op[key][0] += d
        by_op[key][1].add(found or "none")
    out["device_self_us"] = us(total)
    if total:
        out["share_by_layer"] = {k: round(v / total, 4) for k, v in sorted(share.items())}
        out["share_unnamed_but_beside"] = {k: round(v / total, 4) for k, v in linked.items()}
    out["top_ops"] = [[k, us(v[0]), sorted(v[1])] for k, v in
                      sorted(by_op.items(), key=lambda kv: -kv[1][0])[:10]]
    out["scope_us"] = {k: us(scoped_us(pt, (k,))) for k in SCOPES if scoped_us(pt, (k,))}
    return out


if __name__ == "__main__":
    root = sys.argv[1] if len(sys.argv) > 1 else TRACE_ROOT
    print(json.dumps({"program_trace": report(parse(_load(root)))}))
