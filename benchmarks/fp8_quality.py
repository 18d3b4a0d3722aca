"""Quality validation for the fp8 train recipe on a shuffled stream.

Fixed-batch bench losses are throughput probes, not quality metrics — the
same discipline as ``sr_quality.py``: train on a stream of DISTINCT
Zipf-distributed batches (identical stream for both runs), track a
held-out batch, and compare ``mixed_precision="fp8"`` (delayed scaling:
e4m3 forward / e5m2 backward, per-tensor amax history riding
``TrainState.fp8_state``) against the bf16 reference at the same
hyperparameters.  Two envelopes come out:

- ``train_envelope_max_pct`` — the worst per-step train-loss divergence
  over the run (fp8 quantization noise is per-step, so this is the noisy
  bound);
- ``final_held_out_gap_pct`` — the held-out gap at the horizon (the
  number docs/performance.md's "validated envelopes" table pins; like SR,
  the per-step noise should average out rather than accumulate).

  python benchmarks/fp8_quality.py --steps 240
  python benchmarks/fp8_quality.py --steps 240 --current-scaling
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["600m", "1b"], default="600m")
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--optimizer", default="lion-sr")
    ap.add_argument("--current-scaling", action="store_true",
                    help="disable the delayed-scaling amax history "
                         "(ACCELERATE_FP8_DELAYED=0): per-step current "
                         "scaling, the A/B for the history's contribution")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (smoke mode)")
    args = ap.parse_args()

    if args.current_scaling:
        os.environ["ACCELERATE_FP8_DELAYED"] = "0"

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
        batch = args.batch or 4
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

    def run(precision):
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        from accelerate_tpu.optimizer import make_optimizer

        acc = Accelerator(
            parallelism_config=ParallelismConfig(dp_shard_size=jax.device_count()),
            mixed_precision=precision,
        )
        model = LlamaForCausalLM(cfg)
        ids = jnp.ones((batch, 8), jnp.int32)
        params = acc.init_params(model, jax.random.key(0), ids)
        tx = make_optimizer(args.optimizer, args.lr, weight_decay=0.0)
        state = acc.create_train_state(params, tx, apply_fn=model.apply)
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

    fp8_curve, fp8_evals = run("fp8")
    ref_curve, ref_evals = run("bf16")
    train_env = max(
        abs(a - b) / max(abs(b), 1e-9) for a, b in zip(fp8_curve, ref_curve)
    )
    print(json.dumps({
        "metric": "fp8_quality_shuffled_stream",
        # report the EFFECTIVE config: off-TPU the harness substitutes the
        # tiny CPU model, so labeling the output with the requested TPU
        # model name would misattribute smoke numbers
        "model": args.model if on_tpu else "tiny-cpu",
        "backend": jax.default_backend(),
        "scaling": "current" if args.current_scaling else "delayed",
        "steps": args.steps, "batch": batch, "seq_len": seq, "lr": args.lr,
        "optimizer": args.optimizer,
        "fp8": {"train_every10": fp8_curve[9::10], "held_out": fp8_evals},
        "ref": {"train_every10": ref_curve[9::10], "held_out": ref_evals},
        "train_envelope_max_pct": round(100.0 * train_env, 3),
        "final_held_out_gap_pct": round(
            100.0 * abs(fp8_evals[-1] - ref_evals[-1]) / max(abs(ref_evals[-1]), 1e-9), 3
        ) if fp8_evals and ref_evals else None,
    }))


if __name__ == "__main__":
    main()
