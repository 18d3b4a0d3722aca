"""python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once.  Refuses to run (non-zero exit, no result line)
unless jax shows a TPU and exactly the chips the cell asks for; ``--rehearse``
is the explicit CPU rehearsal at tiny shapes, whose numbers are never device
numbers.  Earlier lines are free-form JSON facts; the LAST line is the result
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``)."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny shapes, interpret-mode kernels, virtual devices")
    args = ap.parse_args(argv)

    from perfbench import harness

    ctx = harness.Context(args, T_PROCESS)
    if importlib.util.find_spec("accelerate_tpu") is None:
        raise harness.RunError("perfbench: the system under test (accelerate_tpu/) is not in "
                               "this checkout")
    devices = harness.setup_jax(args, ctx.cell["chips"])
    ctx.mark("devices")
    ctx.say(phase="start", workload=args.workload, seed=args.seed, seconds=args.seconds,
            trace=args.trace, rehearse=args.rehearse, platform=devices[0].platform,
            device_kind=devices[0].device_kind, devices=len(devices), layers=ctx.layers)
    if ctx.tracer is not None:
        shutil.rmtree(ctx.tracer.out_dir, ignore_errors=True)
    kind = importlib.import_module(f"perfbench.kinds.{ctx.kind}")
    outcome = kind.run(ctx)
    ctx.say(phase="setup", setup_s=ctx.setup_s, marks=ctx.record["marks"],
            warmup_compile_s=ctx.record.get("warmup_compile_s"),
            memory_peak_bytes=ctx.memory_peak)
    line = harness.result_line(ctx, devices, outcome)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
