"""serve_trace.py — where the device time of a serving tick goes (PERF.md section 5).

    chiprun -- python benchmarks/serve_trace.py     record a window on the chip, reduce it
    python benchmarks/serve_trace.py --reduce DIR   reduce a recorded trace, on any host

Records one ``jax.profiler`` window over ``chip_smoke.py``'s serve
configuration (same widths, depth, pool and seed; all 16 slots filled so the
decode ticks run full) and reduces it from the device's own timeline:

* window: the first traced program's start to the last one's end, on the
  DEVICE clock (``device_offset_ps`` of the ``XLA Modules`` line);
* busy: the sum of the programs' device durations; idle share = 1 - busy/window;
* per program (decode, prefill buckets): runs, median device ms per run, and
  ms per run by op class (``utils.xplane.classify_op`` over the ``XLA Ops``
  that fall inside the program's span), plus the median call of each Pallas
  attention kernel.

The host's tick times (host clock around ``engine.step()``, which blocks on
the tick's tokens) are printed beside it, medians of the traced ticks.  One
JSON line per fact; the trace itself lands in ``chiprun_out/serve_trace/``.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import statistics
import sys
import time
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
WARM_DECODE_TICKS, TRACED_TICKS, SEED = 8, 20, 1


def reduce(trace_dir: str, device: str = "/device:TPU:0") -> dict:
    """The newest ``*.trace.json.gz`` under ``trace_dir`` -> window, busy,
    idle share and the per-program breakdown (see module docstring)."""
    from accelerate_tpu.utils.xplane import classify_op

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True),
               key=os.path.getmtime)
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    meta = lambda name: {(e["pid"], e.get("tid")): e["args"]["name"]
                         for e in events if e.get("ph") == "M" and e["name"] == name}
    pid = next(p for (p, _), n in meta("process_name").items() if n == device)
    lines = {tid: n for (p, tid), n in meta("thread_name").items() if p == pid}
    span = lambda e: (int(e["args"]["device_offset_ps"]), int(e["args"]["device_duration_ps"]))
    on = lambda line: sorted((span(e) + (e,) for e in events if e.get("ph") == "X"
                              and e["pid"] == pid and lines.get(e["tid"]) == line),
                             key=lambda t: t[0])
    programs, ops = on("XLA Modules"), on("XLA Ops")
    window = max(s + d for s, d, _ in programs) - programs[0][0]
    busy = sum(d for _, d, _ in programs)

    per = defaultdict(lambda: {"runs": [], "classes": defaultdict(float),
                               "kernels": defaultdict(list)})
    i = 0
    for start, dur, prog in programs:
        row = per[prog["name"]]
        row["runs"].append(dur)
        while i < len(ops) and ops[i][0] < start + dur:
            s, d, op = ops[i]
            i += 1
            if s < start:
                continue
            name = op["args"].get("long_name", op["name"])
            cls = classify_op(name)
            row["classes"][cls] += d
            if cls == "flash_attention":
                out_shape = name.split(" custom-call(")[0].split(" = ")[-1]
                row["kernels"][re.sub(r"\{.*", "", out_shape)].append(d)
    ms = lambda ps: round(ps / 1e9, 3)
    return {
        "trace": os.path.relpath(path, trace_dir), "window_ms": ms(window),
        "busy_ms": ms(busy), "idle_share": round(1 - busy / window, 4),
        "programs": {
            name: {"runs": len(row["runs"]), "median_ms": ms(statistics.median(row["runs"])),
                   "class_ms_per_run": {c: ms(t / len(row["runs"])) for c, t in
                                        sorted(row["classes"].items(), key=lambda kv: -kv[1])},
                   "kernel_call_median_ms": {k: ms(statistics.median(v))
                                             for k, v in row["kernels"].items()}}
            for name, row in per.items() if sum(row["runs"]) >= 0.001 * busy},
    }


def record(out_dir: str) -> None:
    """Fill the smoke's engine, run past the prefills, trace TRACED_TICKS ticks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke as cs
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.serving import Request, ServingEngine
    from accelerate_tpu.utils.compile_cache import enable_scoped_compilation_cache
    from accelerate_tpu.utils.dataclasses import ServingPlugin

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"serve_trace: jax found no accelerator (platform {dev.platform!r})")
    enable_scoped_compilation_cache("smoke")
    size = cs.REAL
    model = cs.build_model(size, "flash")
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), model.init(key, jnp.zeros((1, 8), jnp.int32))))(
            jax.random.key(cs.SEED))
    engine = ServingEngine(model, params, ServingPlugin(**size["serve"], decode_kernel="auto"),
                           GenerationConfig(max_new_tokens=size["new_range"][1]))
    engine.warmup()
    rng = np.random.default_rng(SEED)
    for uid in range(size["serve"]["num_slots"]):
        n = int(rng.integers(size["prompt_range"][0], size["prompt_range"][1] + 1))
        engine.add_request(Request(
            uid=uid, prompt=tuple(int(t) for t in rng.integers(1, size["model"]["vocab_size"], n)),
            max_new_tokens=size["new_range"][1]))
    while engine.metrics["decode_steps"] < WARM_DECODE_TICKS:
        engine.step()
    ticks = defaultdict(list)
    jax.profiler.start_trace(out_dir)
    for _ in range(TRACED_TICKS):
        if engine.idle():
            sys.exit("serve_trace: the engine ran dry inside the traced window")
        before = engine.metrics["decode_steps"]
        t0 = time.perf_counter()
        engine.step()
        kind = "decode" if engine.metrics["decode_steps"] > before else "prefill"
        ticks[kind].append(time.perf_counter() - t0)
    jax.profiler.stop_trace()
    print(json.dumps({"device_kind": dev.device_kind, "layers": size["model"]["num_hidden_layers"],
                      "live_slots": len(engine.sched.slots), "host_ticks": {
                          k: {"n": len(v), "median_ms": statistics.median(v) * 1e3}
                          for k, v in ticks.items()}}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reduce", metavar="DIR", help="reduce the trace under DIR; record nothing")
    args = ap.parse_args()
    trace_dir = args.reduce or os.path.join(REPO, "chiprun_out", "serve_trace")
    if not args.reduce:
        record(trace_dir)
    print(json.dumps(reduce(trace_dir)))


if __name__ == "__main__":
    main()
