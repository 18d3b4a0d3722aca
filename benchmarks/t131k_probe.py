"""Isolation probe for the T>=2^17 single-chip crash (docs/long_context.md).

Runs ONE suspect component at a given sequence length in a fresh process so
the crashing component can be bisected out of the full train step:

  --component flash      Pallas flash attention fwd+bwd at [1, T, 16, 96]
  --component matmul     plain [T, H] @ [H, H] chain fwd+bwd (control)
  --component offload    the scan+boundary-offload skeleton, identity math,
                         no attention (the D2H/H2D path alone)
  --component scanflash  scan+boundary-offload WITH flash attention in the
                         body (--layers to vary depth; --splits to divide
                         the stack into consecutive independent scans —
                         probes whether 2x8 dodges the >=16-layer bug cell)

Outcome (2026-08-01, one v5e, before PR 1): every component PASSES
standalone at T=131,072, which ruled a per-component dimension limit OUT.
The full-step crash set (capacity-fitting configs only) is the exact shape
cell {T >= 2^17, scanned layers >= 16, hidden 1536}; neighboring cells
(15L, 17L at shorter T, hidden 1024) run, and every capacity metric is
non-monotone with crashing — a shape-conditioned runtime bug.  The
complete run matrix lives in docs/long_context.md "Where the single-chip
ceiling actually is".

The reproducer is NOT minimal: `--component scanflash --layers 16` (a
16-iteration scan whose body runs real flash attention with the boundary
offloaded) PASSES at T=131,072, so the trigger needs still more of the
full step (MLP/RMSNorm/fused-CE/optimizer/donation) — left for an
upstream report rather than further bisection here.
"""

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, required=True)
    ap.add_argument("--component",
                    choices=["flash", "matmul", "offload", "scanflash"],
                    default="flash")
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--splits", type=int, default=1,
                    help="scanflash only: number of consecutive independent "
                         "scans the layer stack is divided into")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (smoke mode; flash/offload "
                         "components need the TPU for their real form)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    T = args.seq_len
    out = {"metric": "t131k_probe", "component": args.component, "seq_len": T}

    if args.component == "flash":
        from accelerate_tpu.ops.flash_attention import flash_attention

        B, H, Hkv, D = 1, 16, 8, 96
        key = jax.random.key(0)
        q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, D), jnp.bfloat16)
        kw = {}
        if args.block_q:
            kw["block_q"] = args.block_q
        if args.block_k:
            kw["block_k"] = args.block_k

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, **kw).astype(jnp.float32).sum()

        val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        out["value"] = float(val)
        out["grad_norm"] = float(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in grads) ** 0.5
        )
    elif args.component == "matmul":
        Hd = 1536
        key = jax.random.key(0)
        x = jax.random.normal(key, (T, Hd), jnp.bfloat16)
        w = jax.random.normal(jax.random.fold_in(key, 1), (Hd, Hd), jnp.bfloat16)

        def loss(x, w):
            y = x
            for _ in range(4):
                y = jnp.tanh(y @ w)
            return y.astype(jnp.float32).sum()

        val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w)
        out["value"] = float(val)
    elif args.component == "scanflash":
        from jax.ad_checkpoint import checkpoint_name

        from accelerate_tpu.ops.flash_attention import flash_attention

        Hd, Hq, Hkv, D = 1536, 16, 8, 96
        L, S = args.layers, args.splits
        assert L % S == 0, "--layers must divide by --splits"
        policy = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["boundary"],
            offload_src="device", offload_dst="pinned_host",
        )

        def body(x, w):
            # one flash-attention "layer": qkv projections off a shared
            # weight, flash over the full sequence, out-projection residual
            x = checkpoint_name(x, "boundary")
            q = (x @ w).reshape(1, T, Hq, D)
            kv = (x @ w[:, : Hkv * D * 2]).reshape(1, T, Hkv, 2 * D)
            k, v = kv[..., :D], kv[..., D:]
            o = flash_attention(q, k, v, causal=True).reshape(T, Hq * D)
            return (x + o @ w.T).astype(jnp.bfloat16), None

        def loss(x, ws_list):
            for ws in ws_list:  # S consecutive, independent scans
                x, _ = jax.lax.scan(
                    jax.checkpoint(body, policy=policy, prevent_cse=False), x, ws
                )
            return x.astype(jnp.float32).sum()

        key = jax.random.key(0)
        x = jax.random.normal(key, (T, Hd), jnp.bfloat16) * 0.02
        ws_list = [
            jax.random.normal(jax.random.fold_in(key, i), (L // S, Hd, Hq * D),
                              jnp.bfloat16) * 0.02
            for i in range(S)
        ]
        val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0,)))(x, ws_list)
        out["value"] = float(val)
        out["layers"], out["splits"] = L, S
    else:  # offload skeleton: scan with boundary offload, elementwise body
        from jax.ad_checkpoint import checkpoint_name

        Hd, L = 1536, 16
        policy = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["boundary"],
            offload_src="device", offload_dst="pinned_host",
        )

        def body(x, w):
            x = checkpoint_name(x, "boundary")
            return jnp.tanh(x @ w), None

        def loss(x, ws):
            y, _ = jax.lax.scan(
                jax.checkpoint(body, policy=policy, prevent_cse=False), x, ws
            )
            return y.astype(jnp.float32).sum()

        key = jax.random.key(0)
        x = jax.random.normal(key, (T, Hd), jnp.bfloat16)
        ws = jax.random.normal(jax.random.fold_in(key, 1), (L, Hd, Hd), jnp.bfloat16)
        val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0,)))(x, ws)
        out["value"] = float(val)

    print(json.dumps(out))


if __name__ == "__main__":
    main()


