"""python perfbench/sweep.py --workload <open-loop cell> --rates 3,4,5,6,7 --seconds 30 [--seed n]

Finds the knee of an open-loop serving cell again: one process, one set-up,
the cell's replayed trace at each rate in turn (the same unit-rate gaps
divided by the rate, so every rate replays the same requests faster or
slower).  Prints one JSON line per rate and the knee: the highest rate at
which the requests due but still without a first token are no more at the
window's end than a quarter into it.  The cell's ``rate_rps`` is 0.8 x that,
rounded to 0.1 (PERF.md section 4 has the table this was run for)."""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0

    from perfbench import harness
    from perfbench import window as W
    from perfbench.kinds import serve
    from perfbench.traffic import build_trace, prompt_tokens

    ctx = harness.Context(args, time.perf_counter())
    if ctx.traffic["kind"] != "serve_open":
        raise harness.RunError("perfbench: the sweep is for open-loop serving cells")
    harness.setup_jax(args, ctx.cell["chips"])
    spec = ctx.sized(ctx.traffic)
    weights, engine = serve.build(ctx, spec)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        trace = build_trace(spec, rate_rps=rate)
        prompts = {r.uid: prompt_tokens(ctx.seed, r.uid, r.prompt_len, ctx.cfg["vocab_size"])
                   for r in trace if r.due_s < args.seconds}
        ticks, due, _ = serve.serve_loop(
            engine, trace, prompts, kind="serve_open", seconds=args.seconds,
            t_open=time.perf_counter() + spec["ramp_s"], drain_s=spec["drain_s"],
            num_slots=spec["engine"]["num_slots"], page_size=spec["engine"]["page_size"])
        ttft, failed = W.ttft_ms(ticks, due, args.seconds, args.seconds + spec["drain_s"])
        tpot = W.tpot_ms(ticks, args.seconds)
        row = {"rate_rps": rate, "due_in_window": len(ttft), "failed": len(failed),
               "backlog_quarter": W.backlog(ticks, due, args.seconds / 4),
               "backlog_end": W.backlog(ticks, due, args.seconds),
               "ttft_mean_ms": sum(ttft.values()) / len(ttft),
               "ttft_p90_ms": W.percentile(list(ttft.values()), 90),
               "tpot_p90_ms": W.percentile(list(tpot.values()), 90) if tpot else None,
               "tokens_per_s": W.tokens_per_s(ticks, args.seconds)}
        row["sustained"] = row["backlog_end"] <= row["backlog_quarter"] and not failed
        rows.append(row)
        ctx.say(**row)
        while not engine.idle():      # finish what is in flight before the next rate
            engine.step()
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    ctx.say(knee_rps=knee, rate_rps_at_0_8=None if knee is None else round(0.8 * knee, 1))


if __name__ == "__main__":
    main()
