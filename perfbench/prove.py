"""python perfbench/prove.py --workload <cell> --seeds 1,2,3,... [--control-seeds 1,2,3] [--seconds s]

The readings a cell's limits (``perfbench/limits/<cell>.json``) are set from:
in ONE process (one set-up of the programs), for each seed the numbers that
``correct`` compares, and for the control seeds the same numbers with the
plain reference computed in the nearest lower precision (``int8`` for the
bf16 the configurations state) put in the program's place.  Training needs no
measured window; serving runs a short one at the cell's own load.  One JSON
line per seed; nothing here is a benchmark number."""

from __future__ import annotations

import argparse
import gc
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))



def prove_train(ctx, seeds, control_seeds):
    from perfbench.kinds import train as T

    spec = ctx.sized(ctx.traffic)
    acc, step, new_state = ctx.family.build_trainer(ctx.cfg, ctx.layers, spec)
    for seed in seeds:
        state = new_state(seed)
        tokens, resident, feed = T.make_feed(ctx, spec, acc, seed)
        state, program = T.first_steps(ctx, spec, state, step, feed, seed=seed)
        del state, resident, feed
        gc.collect()
        t0 = time.perf_counter()
        ref = T.run_reference(ctx, spec, tokens, seed, steps=spec["reference_steps"])
        row = {"seed": seed, "reference_s": time.perf_counter() - t0,
               "program": {n: v for n, v, _ in T.compare(program, ref, ctx.limits)}}
        for mode in ctx.args.control.split(",") if seed in control_seeds else ():
            low = T.run_reference(ctx, spec, tokens, seed, steps=spec["reference_steps"],
                                  quant=mode)
            as_program = {"losses": low[0], "grad_norms": low[1], "change_norms": low[2]}
            row[f"control_{mode}"] = {n: v for n, v, _ in T.compare(as_program, ref, ctx.limits)}
        ctx.say(**row)


def prove_serve(ctx, seeds, control_seeds):
    from perfbench.kinds import serve as S
    from perfbench.traffic import build_trace, prompt_tokens
    from perfbench.weights import make_weights

    spec = ctx.sized(ctx.traffic)
    trace = build_trace(spec)
    lengths = {r.uid: r.prompt_len + r.output_len for r in trace}
    for seed in seeds:
        weights = make_weights(ctx.family.weight_shapes(ctx.cfg, ctx.layers), seed)
        engine = ctx.family.build_engine(ctx.cfg, ctx.layers, spec["engine"], weights,
                                         ctx.rehearse)
        engine.warmup()
        horizon = [r for r in trace if spec["kind"] == "serve_closed" or r.due_s < ctx.seconds]
        prompts = {r.uid: prompt_tokens(seed, r.uid, r.prompt_len, ctx.cfg["vocab_size"])
                   for r in horizon}
        ticks, due, _ = S.serve_loop(
            engine, trace, prompts, kind=spec["kind"], seconds=ctx.seconds,
            t_open=time.perf_counter() + spec["ramp_s"], drain_s=spec["drain_s"],
            num_slots=spec["engine"]["num_slots"], page_size=spec["engine"]["page_size"])
        finished = {u: t for u, t in engine.results.items()
                    if len(t) == lengths[u] - len(prompts[u])}
        sample = S.pick_sample(seed, finished, lengths)
        width = spec["engine"]["pages_per_slot"] * spec["engine"]["page_size"]
        gap, compared = S.reference_gaps(ctx, weights, sample, finished, prompts, width)
        row = {"seed": seed, "finished": len(finished), "tokens_compared": compared,
               "longest": lengths[sample[0]], "program": {"served_token_logit_gap": gap}}
        for mode in ctx.args.control.split(",") if seed in control_seeds else ():
            low, _ = S.reference_gaps(ctx, weights, sample, finished, prompts, width, quant=mode)
            row[f"control_{mode}"] = {"served_token_logit_gap": low}
        ctx.say(**row)
        del engine, weights
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8", help="int8, fp8 or both, comma-separated")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace, args.seed = 0, 0

    from perfbench import harness

    ctx = harness.Context(args, time.perf_counter())
    harness.setup_jax(args, ctx.cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    (prove_train if ctx.kind == "train" else prove_serve)(ctx, seeds, control)


if __name__ == "__main__":
    main()
