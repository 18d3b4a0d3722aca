"""Big-model inference benchmark (reference
benchmarks/big_model_inference/README.md: GPT-J/NeoX/OPT load time +
per-token generation latency on consumer GPUs).

TPU-native equivalents of the same three numbers:
- **load**: write a sharded safetensors checkpoint to disk once, then time
  ``load_checkpoint_and_dispatch`` streaming it into device placement
  (abstract init -> plan -> shard-stream; no full-model host copy);
- **prefill latency**: one jitted forward over the prompt writing the KV
  cache;
- **per-token latency**: steady-state decode step (the number the reference
  reports as "generate time per token").

Prints one JSON line per metric, bench.py-style.  Model: ~1.1B Llama
(``llama2_1b``) in bf16 — sized to one v5e chip like the reference's
GPT-J-6B was sized to its 2x Titan RTX.

Run: ``python benchmarks/big_model_inference.py [--layers N]``
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def over_hbm_main(args):
    """A model ~1.7x the chip's HBM decodes via layer-streamed generation
    (reference rows: OPT-30B fp16 CPU-offload at 2.37 s/token on a 24GB
    card, benchmarks/big_model_inference/README.md:36).  ~26B int8 weights
    live in pinned host memory (~26GiB); HBM holds one layer + the KV
    cache; every token sweeps the weights over PCIe."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.generation import GenerationConfig, generate_streamed
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.parallel.sharding import (
        host_offload_supported, single_device_sharding,
    )
    from accelerate_tpu.utils.quantization import _quantize_int8_on_device

    assert jax.default_backend() == "tpu" and host_offload_supported(), \
        "--over_hbm needs a real TPU (pinned host memory)"
    host = single_device_sharding("pinned_host")

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=7168, intermediate_size=19456,
        num_hidden_layers=args.layers or 48, num_attention_heads=56,
        num_key_value_heads=8, max_position_embeddings=64,
        attn_implementation="native", dtype=jnp.bfloat16,
    )
    model = LlamaForCausalLM(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )

    t0 = time.perf_counter()
    gen_jits: dict = {}

    def _gen(shape, dtype, key):
        k = (shape, str(dtype))
        if k not in gen_jits:
            gen_jits[k] = jax.jit(
                lambda kk: (jax.random.normal(kk, shape, jnp.float32) * 0.02).astype(dtype)
            )
        return gen_jits[k](key)

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves, n_bytes = [], 0
    for i, (path, sds) in enumerate(flat):
        name = "/".join(str(getattr(p, "key", p)) for p in path).lower()
        if sds.ndim == 2 and not any(s in name for s in ("embed", "lm_head", "norm")):
            w = _gen(sds.shape, jnp.bfloat16, jax.random.key(i))
            qt = _quantize_int8_on_device(w, 128)
            qt.data = jax.device_put(qt.data, host)
            qt.scale = jax.device_put(qt.scale, host)
            n_bytes += qt.data.nbytes + qt.scale.nbytes
            leaves.append(qt)
        elif "norm" in name or "scale" in name:
            leaves.append(jax.device_put(jnp.ones(sds.shape, jnp.bfloat16), host))
            n_bytes += int(np.prod(sds.shape)) * 2
        else:
            w = _gen(sds.shape, jnp.bfloat16, jax.random.key(i))
            leaves.append(jax.device_put(w, host))
            n_bytes += w.nbytes
    host_params = jax.tree_util.tree_unflatten(treedef, leaves)
    n_params = sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(abstract)
    )
    build_s = time.perf_counter() - t0
    print(f"built {n_params/1e9:.1f}B params, {n_bytes/2**30:.1f} GiB in host memory, "
          f"{build_s:.0f}s", flush=True)

    from accelerate_tpu.ops.streaming import StreamStats

    prefetch = not args.no_prefetch
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, args.prompt_len)), jnp.int32)
    gen_cfg = GenerationConfig(max_new_tokens=args.new_tokens)
    t0 = time.perf_counter()
    # warmup/compile run with stats ON: stats routes fetches through the
    # prefetcher even when disabled, so every timed run below (all
    # stats-on) sees the same device-resident jit signature — otherwise a
    # --no-prefetch warmup would pass host-resident trees and the serial
    # baseline's timed window would absorb n_layers recompiles, inflating
    # speedup_vs_serial
    out = generate_streamed(model, host_params, prompt, gen_cfg,
                            prefetch=prefetch, stream_stats=StreamStats())
    np.asarray(out)
    first_s = time.perf_counter() - t0
    # Serial-transfer baseline for the achieved-overlap number: one timed
    # run with prefetch OFF, stats on — its blocking fetches measure the
    # un-hidden per-token PCIe sweep the double buffer exists to hide.
    serial_stats = StreamStats()
    t0 = time.perf_counter()
    out = generate_streamed(
        model, host_params,
        jnp.asarray(rng.integers(0, cfg.vocab_size, prompt.shape), jnp.int32),
        gen_cfg, prefetch=False, stream_stats=serial_stats,
    )
    np.asarray(out)
    serial_per_token = (time.perf_counter() - t0) / args.new_tokens
    stats = StreamStats()
    t0 = time.perf_counter()
    out = generate_streamed(
        model, host_params,
        jnp.asarray(rng.integers(0, cfg.vocab_size, prompt.shape), jnp.int32),
        gen_cfg, prefetch=prefetch, stream_stats=stats,
    )
    np.asarray(out)
    per_token = (time.perf_counter() - t0) / args.new_tokens
    overlap = stats.overlap_report(serial_transfer_s=serial_stats.fetch_wait_s)
    overlap["serial_s_per_token"] = round(serial_per_token, 3)
    overlap["speedup_vs_serial"] = round(serial_per_token / max(per_token, 1e-9), 3)
    print(json.dumps({
        "metric": "over_hbm_decode_seconds_per_token", "value": round(per_token, 3),
        "unit": "s/token",
        "extra": {"params": n_params, "host_GiB": round(n_bytes / 2**30, 2),
                  "hbm_GiB": 16, "layers": cfg.num_hidden_layers,
                  "compile_s": round(first_s - per_token * args.new_tokens, 1),
                  "prompt_len": args.prompt_len, "new_tokens": args.new_tokens,
                  "prefetch": prefetch,
                  "overlap_frac": overlap.get("overlap_frac", 0.0),
                  "h2d_bytes": overlap["h2d_bytes"],
                  "d2h_bytes": overlap["d2h_bytes"],
                  "streaming": overlap},
    }))


def main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.big_modeling import load_checkpoint_and_dispatch
    from accelerate_tpu.checkpointing import save_model
    from accelerate_tpu.generation import GenerationConfig, generate
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig.llama2_1b(num_hidden_layers=args.layers or 22)
    else:  # CPU smoke
        cfg = LlamaConfig.tiny(num_hidden_layers=args.layers or 2)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        # one-time checkpoint authoring (not timed — the reference times the
        # *load*, the checkpoint already exists on disk)
        params = jax.jit(
            lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
        )()
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
        save_model(None, params, ckpt_dir)
        del params

        t0 = time.perf_counter()
        loaded, _ = load_checkpoint_and_dispatch(
            model, ckpt_dir, sample_args=(jnp.ones((1, 8), jnp.int32),),
            device_map=None, dtype=jnp.bfloat16,
        )
        jax.block_until_ready(loaded)
        load_s = time.perf_counter() - t0

    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
    gen_cfg = GenerationConfig(max_new_tokens=args.new_tokens)
    wrapped = {"params": loaded["params"]} if "params" in loaded else loaded

    if args.load_in_8bit:
        # int8 weight-only decode (reference bnb path): decode reads ~half
        # the weight bytes per step, and decode is HBM-bound.  QuantizedTensor
        # kernels route natively through the Pallas in-tile-dequant matmul in
        # QuantizableDense — no apply wrapper needed.
        from accelerate_tpu.utils.quantization import QuantizationConfig, quantize_params

        wrapped = quantize_params(wrapped, QuantizationConfig(load_in_8bit=True))

    def time_decode(params, reps=1):
        # host clock around work that ends in a fetch of the last token
        t0 = time.perf_counter()
        out = generate(model, params, prompt, gen_cfg)
        float(out[0, -1])
        first_s = time.perf_counter() - t0  # includes compile
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = generate(model, params, jnp.asarray(
                rng.integers(0, cfg.vocab_size, prompt.shape), jnp.int32), gen_cfg)
            float(out[0, -1])
            best = min(best or 1e9, time.perf_counter() - t0)
        return best / args.new_tokens, first_s - best

    per_token, compile_s = time_decode(wrapped, reps=args.reps)

    meta = {"params": n_params, "batch": args.batch, "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens, "backend": jax.default_backend(),
            "int8": bool(args.load_in_8bit),
            "compile_s": round(compile_s, 2)}
    print(json.dumps({"metric": "big_model_load_seconds", "value": round(load_s, 2),
                      "unit": "s", "extra": meta}))
    print(json.dumps({"metric": "big_model_decode_seconds_per_token",
                      "value": round(per_token, 4), "unit": "s/token", "extra": meta}))

    if args.ab:
        # same-process A/B: quantize the SAME loaded weights and re-measure,
        # so bf16 and int8 see identical chip state
        from accelerate_tpu.utils.quantization import QuantizationConfig, quantize_params

        q = quantize_params(wrapped, QuantizationConfig(load_in_8bit=True))
        q_per_token, _ = time_decode(q, reps=args.reps)
        print(json.dumps({"metric": "int8_vs_bf16_decode_ratio",
                          "value": round(q_per_token / per_token, 3),
                          "unit": "x (lower is better)",
                          "extra": {"bf16_s_per_tok": round(per_token, 4),
                                    "int8_s_per_tok": round(q_per_token, 4)}}))


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--load_in_8bit", action="store_true")
    p.add_argument("--ab", action="store_true",
                   help="measure bf16 then int8 on the same weights in one process")
    p.add_argument("--reps", type=lambda v: max(1, int(v)), default=3,
                   help="steady-state repetitions (min 1); best is reported")
    p.add_argument("--over_hbm", action="store_true",
                   help="~26B int8 model in host memory, layer-streamed decode")
    p.add_argument("--no-prefetch", action="store_true",
                   help="--over_hbm only: disable the layer double buffer "
                        "(ops/streaming.LayerPrefetcher) — the serialized "
                        "fetch-then-compute baseline")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt_len", type=int, default=None,
                   help="default: 128 (32 with --over_hbm)")
    p.add_argument("--new_tokens", type=int, default=None,
                   help="default: 64 (4 with --over_hbm)")
    _args = p.parse_args()
    if _args.ab and _args.load_in_8bit:
        p.error("--ab measures bf16-then-int8 itself; drop --load_in_8bit "
                "(combining them would compare int8 against int8)")
    if _args.ab and _args.over_hbm:
        p.error("--ab has no effect with --over_hbm (the layer-streamed path "
                "has its own quantization scheme); drop one of them")
    if _args.over_hbm:
        _args.prompt_len = _args.prompt_len or 32
        _args.new_tokens = _args.new_tokens or 4
        over_hbm_main(_args)
    else:
        _args.prompt_len = _args.prompt_len or 128
        _args.new_tokens = _args.new_tokens or 64
        main(_args)
