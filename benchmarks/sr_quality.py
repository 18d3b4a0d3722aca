"""Quality validation for the bf16-master SR recipes on a shuffled stream.

Fixed-batch bench losses are throughput probes, not quality metrics (SR
realizes full-ulp moves on an lr/ulp-probability subset each step, so it
memorizes a repeated batch faster — docs/performance.md).  This harness is
the quality measurement: train on a stream of DISTINCT Zipf-distributed
batches (identical stream for both runs), track a held-out batch, and
compare the SR recipe against its fp32-master reference at the same
hyperparameters.  Measured (r5, one v5e chip): 1.35B lion-sr over 80
steps — held-out 4.6262 vs 4.6244 (0.04%); 600m over 200 steps —
lion-sr 0.035%, adamw-sr 0.002% (5.0849 vs 5.0848), with the gaps
SHRINKING from the 60-step points (0.047% adamw-sr) — the SR noise
averages out with horizon rather than accumulating.

  python benchmarks/sr_quality.py --optimizer adamw-sr --steps 80
  python benchmarks/sr_quality.py --optimizer lion-sr --model 1b
"""

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--optimizer",
                    choices=["lion-sr", "adamw-sr", "lion-sr8", "adamw-sr8"],
                    default="adamw-sr")
    ap.add_argument("--model", choices=["600m", "1b"], default="600m")
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--int8-block", type=int, default=None,
                    help="per-block scale granularity for the -sr8 recipes "
                         "(default 128)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (smoke mode)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
    from accelerate_tpu.state import AcceleratorState, GradientState

    on_tpu = jax.default_backend() == "tpu"
    seq = args.seq_len if on_tpu else 128
    if args.model == "1b" and on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=24, num_attention_heads=16, num_key_value_heads=8,
            max_position_embeddings=seq, attn_implementation="flash",
            dtype=jnp.bfloat16,
        )
        batch = args.batch or 2  # both recipes must fit: fp32 masters cap here
    elif on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
            max_position_embeddings=seq, attn_implementation="flash",
            dtype=jnp.bfloat16,
        )
        batch = args.batch or 8
    else:
        cfg = LlamaConfig.tiny()
        batch = args.batch or 4

    # identical data stream for every run: distinct Zipf-distributed batches
    # (long-tail token stats like real text) + one held-out batch
    rng = np.random.default_rng(0)
    zipf = lambda n: np.minimum(
        rng.zipf(1.2, (n, seq)).astype(np.int64), cfg.vocab_size - 1
    ).astype(np.int32)
    stream = [zipf(batch) for _ in range(args.steps)]
    held_out = zipf(batch)

    lr = args.lr or (1e-4 if "lion" in args.optimizer else 3e-4)

    def make_tx(kind):
        # the recipe registry passes weight_decay explicitly to EVERY recipe
        # (including the optax references, whose own defaults are non-zero:
        # adamw 1e-4, lion 1e-3) — the SR-vs-reference comparison really
        # runs at the same hyperparameters
        from accelerate_tpu.optimizer import make_optimizer

        return make_optimizer(
            kind, lr, weight_decay=0.0,
            block_size=args.int8_block if kind.endswith("-sr8") else None,
        )

    def run(kind):
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        acc = Accelerator(
            parallelism_config=ParallelismConfig(dp_shard_size=jax.device_count()),
            mixed_precision="bf16",
        )
        model = LlamaForCausalLM(cfg)
        ids = jnp.ones((batch, 8), jnp.int32)
        params = acc.init_params(model, jax.random.key(0), ids)
        if kind.endswith(("-sr", "-sr8")):
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        state = acc.create_train_state(params, make_tx(kind), apply_fn=model.apply)
        loss_fn = make_llama_loss_fn(model, fused_vocab_chunks=4 if on_tpu else None)
        step = acc.prepare_train_step(loss_fn, max_grad_norm=None)
        eval_loss = jax.jit(lambda p, b: loss_fn(p, b))
        curve, evals = [], []
        for i, tokens in enumerate(stream):
            b = {"input_ids": jnp.asarray(tokens), "labels": jnp.asarray(tokens)}
            state, m = step(state, b)
            curve.append(round(float(m["loss"]), 4))
            if (i + 1) % args.eval_every == 0:
                h = {"input_ids": jnp.asarray(held_out), "labels": jnp.asarray(held_out)}
                evals.append(round(float(eval_loss(state.params, h)), 4))
        return curve, evals

    sr_kind = args.optimizer
    from accelerate_tpu.optimizer import reference_recipe

    ref_kind = reference_recipe(sr_kind)
    sr_curve, sr_evals = run(sr_kind)
    ref_curve, ref_evals = run(ref_kind)
    print(json.dumps({
        "metric": "sr_quality_shuffled_stream",
        # report the EFFECTIVE config: off-TPU the harness substitutes the
        # tiny CPU model, so labeling the output with the requested TPU
        # model name would misattribute smoke numbers
        "model": args.model if on_tpu else "tiny-cpu",
        "backend": jax.default_backend(),
        "steps": args.steps, "batch": batch, "seq_len": seq, "lr": lr,
        "sr": {"optimizer": sr_kind, "train_every10": sr_curve[9::10],
               "held_out": sr_evals},
        "ref": {"optimizer": ref_kind, "train_every10": ref_curve[9::10],
                "held_out": ref_evals},
        "final_held_out_gap_pct": round(
            100.0 * abs(sr_evals[-1] - ref_evals[-1]) / max(abs(ref_evals[-1]), 1e-9), 3
        ) if sr_evals and ref_evals else None,
    }))


if __name__ == "__main__":
    main()
