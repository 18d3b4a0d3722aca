"""Autoregressive generation with a KV cache (reference capability:
big-model inference — the reference's benchmarks/big_model_inference loads GPT-class models
and generates via transformers ``model.generate``; here the decode loop is
in-tree and jit-compiled).

Run::

    accelerate-tpu launch examples/by_feature/generation.py
    python examples/by_feature/generation.py --do_sample --top_k 50
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.generation import GenerationConfig, generate
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM


def main(args):
    cfg = LlamaConfig.tiny(num_hidden_layers=4, hidden_size=128)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    # two right-padded "prompts" of different lengths in one batch
    prompts = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 12)), jnp.int32)
    lengths = jnp.asarray([12, 7], jnp.int32)
    params = model.init(jax.random.key(0), prompts[:, :8])

    gen_cfg = GenerationConfig(
        max_new_tokens=args.max_new_tokens,
        do_sample=args.do_sample,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
    )
    t0 = time.perf_counter()
    out = generate(model, params, prompts, gen_cfg, prompt_lengths=lengths,
                   rng=jax.random.PRNGKey(args.seed))
    out.block_until_ready()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = generate(model, params, prompts, gen_cfg, prompt_lengths=lengths,
                   rng=jax.random.PRNGKey(args.seed + 1))
    out.block_until_ready()
    run_s = time.perf_counter() - t0

    toks = out.shape[0] * out.shape[1]
    print(f"generated {out.shape} tokens; first-call {compile_s:.2f}s (compile), "
          f"steady {run_s * 1e3:.1f}ms ({toks / max(run_s, 1e-9):.0f} tok/s)")
    for row, (ids, n) in enumerate(zip(np.asarray(out), np.asarray(lengths))):
        print(f"  prompt[{row}] (len {n}) -> {[int(i) for i in ids]}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--max_new_tokens", type=int, default=16)
    p.add_argument("--do_sample", action="store_true")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=None)
    p.add_argument("--top_p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    main(p.parse_args())
