"""What every cell shares: finding a cell's files by name, the device check,
the compile cache, the set-up clock, the profiler window, the per-layer
readers and the result line.  Nothing here knows a configuration, a traffic
mix or a metric by name: those are files (``perfbench/README.md``)."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import time
from pathlib import Path

PB = Path(__file__).resolve().parent
ROOT = PB.parent


class RunError(SystemExit):
    """Refusal to run: non-zero exit, no result line."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` by file name (a metric's name may hold
    dots, so this is not an import path)."""
    path = PB / kind / f"{name}.py"
    if not path.is_file():
        raise RunError(f"perfbench: no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"perfbench.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration, traffic, limits and metric entries."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunError(f"perfbench: no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return dict(bench=bench, cell=cell, config=load_json(ROOT / config_entry["file"]),
                traffic=load_json(PB / "traffic" / f"{cell['traffic']}.json"),
                limits=load_json(PB / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def limit_key(check: str) -> str:
    """The key in a cell's limits file that a compared number is held to."""
    return re.sub(r"_step[0-9]+$", "", check)


def device_peaks(kind: str) -> dict:
    peaks = load_json(PB / "peaks.json")
    if kind not in peaks:
        raise RunError(f"perfbench: no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


class Tracer:
    """One ``jax.profiler`` window inside the run, with its own host anchor so
    host-clock spans can be laid on the trace's timeline."""

    def __init__(self, out_dir: Path):
        self.out_dir, self.anchor, self._ann = out_dir, None, None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("perfbench_window")
        self._ann.__enter__()
        self.anchor = time.perf_counter()

    def stop(self):
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


class Context:
    def __init__(self, args, t_process: float):
        loaded = load_cell(args.workload)
        self.__dict__.update(loaded)
        self.args, self.t_process = args, t_process
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.cfg = self.config
        kind = self.traffic["kind"]
        self.kind = kind.split("_")[0]
        self.layers = self.cfg.get("depth_by_kind", {}).get(self.kind, self.cfg["num_hidden_layers"])
        if self.rehearse:
            self.limits = self.limits["rehearse"]
            self.cfg = {**self.cfg, **self.traffic["rehearse"]["config"]}
            self.layers = self.traffic["rehearse"]["layers"]
        self.family = importlib.import_module(f"perfbench.families.{self.cfg['model_family']}")
        self.reference = importlib.import_module(f"perfbench.reference.{self.cfg['model_family']}")
        self.record: dict = {"marks": [], "cfg": self.cfg, "layers": self.layers}
        self.t_open = None
        self.memory_peak = None
        self.tracer = Tracer(ROOT / ".perfbench_trace" / args.workload) if self.trace else None

    def sized(self, traffic: dict) -> dict:
        """The traffic as run: the rehearsal's smaller shapes laid over it."""
        if not self.rehearse:
            return traffic
        over = {k: v for k, v in traffic["rehearse"].items() if k not in ("config", "layers")}
        return {**traffic, **over}

    def say(self, **fields):
        print(json.dumps(fields, default=float), flush=True)

    def mark(self, name: str):
        self.record["marks"].append((name, time.perf_counter() - self.t_process))

    def open_window(self, after_s: float = 0.0):
        """Set-up ends here (plus a ramp of fixed length, if the kind has one)."""
        self.t_open = time.perf_counter() + after_s
        self.setup_s = self.t_open - self.t_process
        self.mark("window_open")

    def read_memory_peak(self, at_least=None):
        """The allocator's peak on the fullest chip, or ``at_least`` (what a
        compiled program says it needs per device) where that is larger."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
        peaks = [p for p in peaks + [at_least] if p is not None]
        self.memory_peak = max(peaks, default=None)


def setup_jax(args, chips: int):
    """Platform check, compile cache, RNG mode.  Returns the devices."""
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", chips)
    jax.config.update("jax_threefry_partitionable", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache = ROOT / ".jax_cache" / ("perfbench-rehearse" if args.rehearse else "perfbench")
        cache.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        raise RunError(f"perfbench: jax found no accelerator (platform "
                       f"{devices[0].platform!r}); the CPU rehearsal is --rehearse")
    if len(devices) != chips:   # fewer cannot run it; more would report another device count
        raise RunError(f"perfbench: the cell asks for {chips} chip(s), jax reports {len(devices)}")
    return devices


def per_layer_metrics(ctx, reduced) -> dict:
    """Every per-layer metric the cell lists, through its own reader.  A
    metric with no file is an error; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    ctx.record["trace"] = reduced
    out = {}
    for entry in ctx.per_layer:
        reader = load_module("metrics", entry["name"])
        for key in ("layer", "unit", "moves", "source"):
            if getattr(reader, key) != entry[key]:
                raise RunError(f"perfbench: metrics/{entry['name']}.py says {key} = "
                               f"{getattr(reader, key)!r}, BENCHMARK.json says {entry[key]!r}")
        value = reader.read(ctx.record)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def result_line(ctx, devices, outcome: dict) -> dict:
    checks = outcome["checks"]
    for name, value, limit in checks:
        ok = limit is not None and value <= limit
        ctx.say(check=name, value=value, limit=limit, ok=ok)
    correct = all(limit is not None and value <= limit for _, value, limit in checks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak}
    line = {"correct": bool(correct), "attempted": outcome["attempted"],
            "failed": outcome["failed"]}
    e2e = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
    ctx.record["end_to_end"] = e2e
    ctx.record["peaks"] = None if ctx.rehearse else device_peaks(devices[0].device_kind)
    if ctx.trace:
        from perfbench import trace_reduce

        reduced = trace_reduce.reduce_dir(
            ctx.tracer.out_dir, spans=ctx.record.get("spans", []), anchor=ctx.tracer.anchor,
            fallback_host=ctx.rehearse)
        line["metrics"] = per_layer_metrics(ctx, reduced)
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        units = {m["name"]: m["unit"] for m in ctx.end_to_end}
        missing = set(units) - set(e2e)
        if missing:
            raise RunError(f"perfbench: the cell did not report {sorted(missing)}")
        line["metrics"] = {k: {"value": float(e2e[k]), "unit": unit} for k, unit in units.items()}
    line["device"] = device
    return line
