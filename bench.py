"""Headline benchmark: Llama decoder training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric is tokens/sec/chip for a bf16 Llama-family causal-LM train step
(flash-attention Pallas kernel, donated buffers, fused optimizer under one
jit).  ``vs_baseline`` is measured MFU / 0.45 — the BASELINE.json north-star
MFU target for the reference's TPU path ("Llama fine-tune at >=45% MFU").

Every report carries ``schema_version`` (bumped when field semantics
change), the unified ``twins`` block (telemetry/twins.py: every registered
predicted/measured pair with per-twin rel_err and drift status — the
canonical nine are always present, zeros-clean when idle), and the
measured ``telemetry_overhead_frac`` (0.0 with telemetry off; telemetry
on/off never changes a token or the loss).
"""

import json
import time

import numpy as np

# bump when a report field's meaning changes (BENCH_*.json consumers key
# their cross-round comparisons on this)
BENCH_SCHEMA_VERSION = 1


def _twins_block() -> dict:
    """The unified twins block: declare the canonical nine (zeros-clean),
    then render everything the run recorded."""
    from accelerate_tpu.telemetry import twin_registry

    reg = twin_registry()
    reg.declare_standard_twins()
    return reg.drift_report()

# Per-chip peak bf16 FLOP/s by TPU generation (public spec sheets).
_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12, "v5litepod": 197e12, "v5lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12, "trillium": 918e12,
}


def _peak_flops(device):
    """Per-chip peak bf16 FLOP/s of a TPU ``device``.  ``None`` on the CPU
    backend (no device peak: MFU is then "not measured", never a number);
    an accelerator kind missing from the table is an error, not a default."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower().replace(" ", "")
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"bench.py: device kind {device.device_kind!r} is not in the peak "
        f"table {sorted(_PEAK_FLOPS)}; add it with its source"
    )


def selftest(report: dict) -> None:
    """On-chip kernel parity: flash fwd+grad vs the XLA-native path, on the
    real device (the CPU suite runs the kernels interpret-mode only, so a
    Mosaic lowering bug could otherwise ship behind a green suite)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.ops.flash_attention import flash_attention
    from accelerate_tpu.models.llama import native_attention

    b, t, h, hkv, d = 2, 1024, 8, 4, 64
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(k2, (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(k3, (b, t, hkv, d), jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    def loss_native(q, k, v):
        return jnp.mean(native_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    lf, gf = jax.jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    ln, gn = jax.jit(jax.value_and_grad(loss_native, argnums=(0, 1, 2)))(q, k, v)
    import numpy as np

    np.testing.assert_allclose(float(lf), float(ln), rtol=2e-2)
    for a, c, name in zip(gf, gn, "qkv"):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32))))
        ref = float(jnp.max(jnp.abs(c.astype(jnp.float32)))) + 1e-6
        assert err / ref < 5e-2, f"flash d{name} mismatch: rel {err / ref:.4f}"
    report["selftest"] = "ok"


def _grad_close(f_test, f_ref, args, name, rtol=2e-2, grtol=5e-2):
    """value_and_grad parity of two scalar functions on the real chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    argnums = tuple(range(len(args)))
    lt, gt = jax.jit(jax.value_and_grad(f_test, argnums=argnums))(*args)
    lr, gr = jax.jit(jax.value_and_grad(f_ref, argnums=argnums))(*args)
    np.testing.assert_allclose(float(lt), float(lr), rtol=rtol, err_msg=name)
    for i, (a, c) in enumerate(zip(gt, gr)):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32))))
        ref = float(jnp.max(jnp.abs(c.astype(jnp.float32)))) + 1e-6
        assert err / ref < grtol, f"{name} grad[{i}] mismatch: rel {err / ref:.4f}"


def selftest_kernels(report: dict) -> None:
    """Widened on-chip kernel parity matrix (VERDICT r2 weak #4): every
    masking variant the long-context suite uses interpret-mode on CPU is
    checked against its XLA-native reference on the real device, plus the
    int8 matmul and the fused linear+CE.  A Mosaic lowering bug in any of
    these paths fails the bench loudly instead of shipping behind green
    CPU tests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.flash_attention import flash_attention
    from accelerate_tpu.models.llama import native_attention

    checks = {}
    b, t, h, hkv, d = 1, 512, 4, 2, 64
    k1, k2, k3 = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(k2, (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(k3, (b, t, hkv, d), jnp.bfloat16)

    def msq(x):
        return jnp.mean(x.astype(jnp.float32) ** 2)

    # 1. non-causal (bidirectional encoder shape)
    _grad_close(
        lambda q, k, v: msq(flash_attention(q, k, v, causal=False)),
        lambda q, k, v: msq(native_attention(q, k, v, causal=False)),
        (q, k, v), "flash_noncausal",
    )
    checks["flash_noncausal"] = "ok"

    # 2. packed-sequence segment ids (uneven split, causal)
    seg = jnp.asarray(
        np.concatenate([np.zeros((b, 192), np.int32), np.ones((b, t - 192), np.int32)], 1)
    )
    _grad_close(
        lambda q, k, v: msq(flash_attention(q, k, v, causal=True, segment_ids=seg)),
        lambda q, k, v: msq(native_attention(q, k, v, causal=True, segment_ids=seg)),
        (q, k, v), "flash_segment_ids",
    )
    checks["flash_segment_ids"] = "ok"

    # 3. explicit global positions (the ring-CP zigzag layout: this shard
    # holds non-contiguous global chunks, so the causal mask must key on
    # positions, not array index)
    half = t // 2
    pos = jnp.asarray(
        np.concatenate([np.arange(half), np.arange(2 * t - half, 2 * t)])[None].repeat(b, 0)
    ).astype(jnp.int32)

    def native_positioned(q, k, v):
        scores = jnp.einsum("bthd,bshd->bhts",
                            q, jnp.repeat(k, h // hkv, axis=2)).astype(jnp.float32) / np.sqrt(d)
        mask = pos[:, :, None] >= pos[:, None, :]
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bshd->bthd", probs, jnp.repeat(v, h // hkv, axis=2))

    _grad_close(
        lambda q, k, v: msq(flash_attention(q, k, v, causal=True, positions=pos, kv_positions=pos)),
        lambda q, k, v: msq(native_positioned(q, k, v)),
        (q, k, v), "flash_positions",
    )
    checks["flash_positions"] = "ok"

    # 4. int8 in-tile-dequant matmul vs dequantize-then-matmul
    from accelerate_tpu.ops.quantized_matmul import quantized_matmul
    from accelerate_tpu.utils.quantization import QuantizationConfig, dequantize, quantize

    # m=64 -> the tiled (M, F, K) kernel; m=1 -> the whole-F-resident decode
    # kernel (its own Mosaic-sensitive constructs: K-only grid, in-kernel
    # chunked dequant, masked partial K for non-divisor H like 7B's 11008/4)
    jitted_qmm = jax.jit(quantized_matmul)  # one wrapper; jit caches per shape
    for mm, hh2, ff2, label in [
        (64, 512, 1024, "int8_matmul"),
        (1, 2048, 5632, "int8_decode"),
        (1, 2752, 1024, "int8_decode_masked_k"),
    ]:
        w = (np.random.default_rng(5).standard_normal((hh2, ff2)) * 0.02).astype(np.float32)
        x = jax.random.normal(jax.random.key(12), (mm, hh2), jnp.bfloat16)
        qt = quantize(jax.device_put(jnp.asarray(w)), QuantizationConfig(load_in_8bit=True))
        got = np.asarray(jitted_qmm(x, qt).astype(jnp.float32))
        want = np.asarray(x.astype(jnp.float32) @ dequantize(qt, jnp.float32))
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert err < 2e-2, f"{label} mismatch: rel {err:.4f}"
        checks[label] = "ok"

    # 5. fused linear+CE (chunked, logits never materialized) vs naive CE
    from accelerate_tpu.ops.fused_xent import fused_causal_lm_loss

    bb, tt, hh, vv = 2, 256, 256, 1024
    hid = jax.random.normal(jax.random.key(13), (bb, tt, hh), jnp.bfloat16)
    wv = jax.random.normal(jax.random.key(14), (vv, hh), jnp.float32) * 0.02
    labels = jnp.asarray(np.random.default_rng(6).integers(0, vv, (bb, tt)), jnp.int32)

    def naive(hid, wv):
        logits = (hid.astype(jnp.float32)[:, :-1] @ wv.T).reshape(-1, vv)
        lab = labels[:, 1:].reshape(-1)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]
        return jnp.mean(lse - picked)

    _grad_close(
        lambda hid, wv: fused_causal_lm_loss(hid, wv, labels, vocab_major=True, num_chunks=4),
        naive, (hid, wv), "fused_ce", rtol=1e-2, grtol=5e-2,
    )
    checks["fused_ce"] = "ok"

    report["kernels"] = checks


def _7b_config(jnp, seq):
    from accelerate_tpu.models import LlamaConfig

    # Llama-2-7B, the BASELINE.json reference shape
    return LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=seq, attn_implementation="flash",
        remat=True, dtype=jnp.bfloat16,
    )


# the recipes that store the params themselves in bf16 with stochastic
# rounding (no fp32 master tree); -sr8 additionally stores the moments as
# int8 codes + per-block scales (ops/int8_state.py)
SR_KINDS = ("lion-sr", "adamw-sr", "lion-sr8", "adamw-sr8")


def plan_report(n_devices: int, seq: int, batch_per_device: int, offload: bool,
                optimizer: str = "lion"):
    """Abstract per-device memory plan for Llama-2-7B on an ``n_devices``
    v5e mesh (FSDP over dp_shard) — pure eval_shape + sharding-plan
    arithmetic, no chips needed (VERDICT r1 missing #4)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from accelerate_tpu.models import LlamaForCausalLM
    from accelerate_tpu.parallel.sharding import (
        make_sharding_plan, plan_bytes_per_device,
    )
    from accelerate_tpu.parallelism_config import ParallelismConfig

    cfg = _7b_config(jnp, seq)
    model = LlamaForCausalLM(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )
    mesh = AbstractMesh((n_devices,), ("dp_shard",))
    pcfg = ParallelismConfig(dp_shard_size=n_devices)
    plan = make_sharding_plan(params, mesh, parallelism_config=pcfg)
    p_bytes = plan_bytes_per_device(params, plan)  # fp32 leaves as initialized
    bf16 = p_bytes // 2          # compute copy
    # masters: fp32 tree (lion/adamw) or none at all (the -sr/-sr8 recipes
    # store the params themselves in bf16 — the compute copy IS the master)
    fp32 = 0 if optimizer in SR_KINDS else p_bytes
    # matches the bench optimizer choices: lion/lion-sr = bf16 momentum
    # only, adamw-sr = bf16 m + v (SR-maintained), adamw = fp32 m + v,
    # -sr8 = int8 codes (1 B/param per moment; scales ~4/128 ride free)
    opt_state = {
        "lion": p_bytes // 2, "lion-sr": p_bytes // 2,
        "lion-sr8": p_bytes // 4,
        "adamw-sr": p_bytes, "adamw-sr8": p_bytes // 2,
        "adamw": 2 * p_bytes,
    }[optimizer]
    if offload:
        # grads stream D2H as backward produces them (clipping off — see
        # docs/offload.md); resident at once: ~the largest leaf, in bf16
        import numpy as _np

        largest = max(
            int(_np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
        )
        grads = largest * 2
    else:
        grads = p_bytes // 2     # full bf16 grad tree resident (clip barrier)
    # activations: full remat keeps one bf16 [B, T, H] per layer boundary
    # plus the flash workspace; fused CE avoids [B, T, V] logits
    act = batch_per_device * seq * cfg.hidden_size * 2 * (cfg.num_hidden_layers + 2)
    hbm = bf16 + grads + act + (0 if offload else fp32 + opt_state)
    # offloaded host set: the master tree (bf16 params themselves under
    # the -sr/-sr8 recipes) + optimizer state
    host = ((bf16 if optimizer in SR_KINDS else fp32)
            + opt_state) if offload else 0
    gib = lambda b: round(b / 2**30, 2)
    return {
        "model": "llama2-7b", "n_devices": n_devices,
        "per_device_GiB": {
            "params_bf16": gib(bf16), "grads_bf16": gib(grads),
            "master_fp32": gib(0 if offload else fp32),
            "optimizer_state": gib(0 if offload else opt_state),
            "activations_est": gib(act), "total_hbm": gib(hbm),
        },
        "host_GiB_per_device": gib(host),
        "fits_v5e_16GiB": hbm < 15 * 2**30,
        "grads_streamed": offload,
        "offload": offload, "optimizer": optimizer,
        "seq_len": seq, "batch_per_device": batch_per_device,
    }


def _1b_config(jnp, seq, remat_policy):
    from accelerate_tpu.models import LlamaConfig

    # ~1.34B Llama-style decoder (hidden 2048 / inter 5504 / 24 layers):
    # the "representative depth/width" resident-HBM point (VERDICT r3 weak
    # #2) — bf16 params 2.7GiB, so params+adam(m bf16)+grads+masters all
    # stay in HBM on a 16GiB v5e, unlike the offloaded 7B config.
    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=24, num_attention_heads=16, num_key_value_heads=16,
        max_position_embeddings=seq, attn_implementation="flash",
        remat=remat_policy != "none", dtype=jnp.bfloat16,
        remat_policy=remat_policy if remat_policy != "none" else "full",
    )


def _70b_config(jnp):
    from accelerate_tpu.models import LlamaConfig

    # Llama-2-70B (GQA): the BASELINE "sharded inference" reference shape
    return LlamaConfig(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8,
        max_position_embeddings=4096, attn_implementation="flash",
        dtype=jnp.bfloat16,
    )


def plan_infer_report(n_devices: int, seq: int, batch: int):
    """Abstract per-device memory plan for **sharded Llama-2-70B decode** on
    an ``n_devices`` v5e mesh (TP over the 8 KV heads × FSDP over the rest)
    — the model is ~9x one chip's HBM; the plan shows each device holding a
    slice plus its KV-cache shard (VERDICT r2 next #2; reference analog:
    GPT-NeoX-20B across 2 GPUs, big_model_inference/README.md:33)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from accelerate_tpu.models import LlamaForCausalLM
    from accelerate_tpu.parallel.sharding import (
        get_tp_rules, make_sharding_plan, plan_bytes_per_device,
    )
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

    cfg = _70b_config(jnp)
    model = LlamaForCausalLM(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )
    # TP capped at the KV-head count (GQA: the kv projections stop dividing
    # past 8); the rest of the mesh is FSDP (ZeRO-3-style param sharding —
    # every shard is fetched layer-by-layer during decode via all-gather)
    tp = 8 if n_devices % 8 == 0 else (2 if n_devices % 2 == 0 else 1)
    dp = n_devices // tp
    mesh = AbstractMesh((dp, tp), ("dp_shard", "tp"))
    pcfg = ParallelismConfig(dp_shard_size=dp, tp_size=tp)
    plan = make_sharding_plan(
        params, mesh, parallelism_config=pcfg,
        fsdp_plugin=FullyShardedDataParallelPlugin(min_weight_size=0),
        tp_rules=get_tp_rules("auto"),
    )
    p_bytes = plan_bytes_per_device(params, plan) // 2  # bf16 serving copy
    total_bf16 = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
    ) * 2
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    # KV cache: [L, B, S, kv_heads, head_dim] x2, kv heads sharded over tp,
    # batch over dp_shard
    kv = (
        2 * cfg.num_hidden_layers * max(1, batch // dp) * seq
        * (cfg.num_key_value_heads * head_dim // tp) * 2
    )
    workspace = 512 * 2**20  # decode activations + collective buffers
    hbm = p_bytes + kv + workspace
    gib = lambda b: round(b / 2**30, 2)
    return {
        "model": "llama2-70b-inference", "n_devices": n_devices,
        "mesh": {"tp": tp, "dp_shard": dp},
        "model_total_GiB_bf16": gib(total_bf16),
        "chips_worth_of_weights": round(total_bf16 / (15 * 2**30), 1),
        "per_device_GiB": {
            "params_bf16": gib(p_bytes), "kv_cache": gib(kv),
            "workspace_est": gib(workspace), "total_hbm": gib(hbm),
        },
        "fits_v5e_16GiB": hbm < 15 * 2**30,
        "seq_len": seq, "batch": batch,
    }


def serve_report(args) -> dict:
    """``--serve``: replay a seeded request trace (Poisson arrivals, mixed
    prompt/output lengths) through the continuous-batching serving engine
    (accelerate_tpu/serving/) and report the serving fields — ALWAYS all of
    them (tokens/s/chip, p50/p99 per-token latency, KV-pool utilization
    predicted+measured, padding-waste fraction, scheduler occupancy), zeros
    when the trace is empty, so BENCH_*.json tracks them across rounds.
    The static-batching twin re-counts the SAME measured per-request work
    under the fixed-batch schedule — the CPU-measurable proxy continuous
    batching must beat on padding waste and scheduled-token efficiency.

    ``--adapters N``: multi-tenant mode — N LoRA tenants share the base
    model through the segment-batched adapter matmul (ops/lora.py), cold
    adapters hot-swap from OffloadStore memmaps through a fixed device
    pool, and the report adds the adapter fields (ALWAYS emitted, zeros
    without adapters): pool hit rate (predicted+measured twins), swap
    count/bytes, the predicted pool ladder, and the **per-adapter-loop
    twin** — the same trace re-served one tenant at a time, which the
    batched einsum must beat on tokens/s (the S-LoRA win, CPU-measurable
    as slot occupancy).

    ``--speculate [K]``: speculative multi-token decode (n-gram
    self-drafting, K drafts per verify pass).  The speculate fields ride
    EVERY serve report zeros-clean: ``accept_rate`` (+``_predicted`` via
    the model-free trace replay — the TwinRegistry pair), ``tokens_per_step``
    (+``_predicted``; 1.0 is the plain-decode floor the speculative run
    must beat), ``draft_overhead_frac``, ``speculative_rollbacks``.

    The overload-control block (serving/overload.py) rides EVERY serve
    report zeros-clean too: ``requests_shed`` / ``deadline_misses`` /
    ``cancelled`` / ``pages_reclaimed_on_cancel`` /
    ``request_goodput_frac`` (1.0 on a clean busy replay) /
    ``transfer_retries`` (adapter hot-swap transients absorbed by the
    bounded retry layer) / ``ladder_stage`` + ``ladder_engagements`` (the
    graceful-degradation ladder's standing), with the matching
    ``serving.*`` rows in the ``twins`` block pinned to the clean-run
    model (zero sheds/misses/cancels, goodput 1.0)."""
    import dataclasses as _dc
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
    from accelerate_tpu.serving import (
        AdapterStore, ServingEngine, adapter_pool_accounting,
        kv_pool_accounting, replay, static_batching_report, synthesize_trace,
    )
    from accelerate_tpu.utils.dataclasses import LoraPlugin, ServingPlugin

    on_tpu = jax.default_backend() == "tpu"
    spec_k = getattr(args, "speculate", None)
    spec_kw = ({"speculate": "ngram", "speculate_k": int(spec_k)}
               if spec_k else {})
    prefix_share = getattr(args, "prefix_share", None)
    if prefix_share:
        # --prefix-share arms the COW prefix cache on the serving engine
        spec_kw["prefix_cache"] = "on"
    kv_dtype = getattr(args, "kv_dtype", "bf16") or "bf16"
    if kv_dtype != "bf16":
        # --kv-dtype arms the quantized page pool (codes + per-page scales)
        spec_kw["kv_dtype"] = kv_dtype
    if on_tpu:
        # the 600m-class decode shape (the headline bench's model family);
        # pool sized off the KV-HBM ladder, paged Pallas decode kernel
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
            max_position_embeddings=4096, attn_implementation="flash",
            dtype=jnp.bfloat16,
        )
        plugin = ServingPlugin(
            num_slots=args.batch or 16, page_size=64, pages_per_slot=32,
            num_pages=(args.batch or 16) * 16, prefill_chunk=512, **spec_kw,
        )
        prompt_range, new_range = (64, 512), (32, 256)
    else:  # CPU-tiny smoke shape (the --batch 8 convention)
        cfg = LlamaConfig.tiny()
        plugin = ServingPlugin(
            num_slots=args.batch or 4, page_size=4, pages_per_slot=16,
            num_pages=(args.batch or 4) * 10, prefill_chunk=16,
            decode_kernel="native", **spec_kw,
        )
        prompt_range, new_range = (4, 24), (4, 24)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    n_adapters = getattr(args, "adapters", 0) or 0
    trace = synthesize_trace(
        args.serve_seed, args.serve_requests, vocab_size=cfg.vocab_size,
        mean_interarrival_steps=0.5, prompt_len_range=prompt_range,
        new_tokens_range=new_range, adapters=n_adapters,
        prefix_share=prefix_share or 0.0,
    )
    gen_cfg = GenerationConfig(max_new_tokens=new_range[1])
    store = store_dir = None
    lora_plugin = None
    if n_adapters > 0:
        lora_plugin = LoraPlugin(
            rank=16 if on_tpu else 4,
            # undersized on purpose: the pool must hot-swap on the seeded
            # trace so the hit-rate/swap-bytes fields measure something
            pool_slots=max(2, (n_adapters + 1) // 2),
            kernel="auto" if on_tpu else "native",
        )
        store_dir = tempfile.TemporaryDirectory(prefix="bench_adapters_")
        store = AdapterStore(params, lora_plugin, dtype=cfg.dtype,
                             offload_dir=store_dir.name)
        for t in range(1, n_adapters + 1):
            store.publish_random(t, jax.random.PRNGKey(1000 + t))
    # the no-reuse baseline runs FIRST (its registry records are then
    # overwritten by the main replay's): same trace, prefix cache off — the
    # ttft with/without-reuse comparison the prefix twin records (ticks:
    # deterministic on CPU where wall clocks flake)
    ttft_no_reuse_ticks = 0.0
    no_reuse_results = None
    if prefix_share:
        base_engine = ServingEngine(
            model, params, _dc.replace(plugin, prefix_cache="off"), gen_cfg,
            adapters=store,
        )
        base_rep = replay(base_engine, trace)
        ttft_no_reuse_ticks = base_rep["ttft_p50_ticks"]
        no_reuse_results = base_rep["results"]
    engine = ServingEngine(model, params, plugin, gen_cfg, adapters=store)
    trace_out = getattr(args, "trace_requests", None)
    if trace_out is not None:
        # request-level lifecycle + step-phase spans (telemetry/spans.py):
        # host-side only — tokens bitwise identical, strict_compiles still
        # enforced by the replay below, overhead measured into
        # telemetry_overhead_frac
        engine.enable_tracing()
    rep = replay(engine, trace)
    rep["ttft_no_reuse_p50_ticks"] = ttft_no_reuse_ticks
    rep["prefix_reuse_token_parity"] = (
        no_reuse_results == rep["results"] if no_reuse_results is not None
        else True
    )
    if prefix_share:
        from accelerate_tpu.telemetry import twin_registry as _tr

        # predicted = the no-reuse baseline's TTFT, measured = with reuse:
        # the drift IS the reuse win (tolerance 1.0 — informational row)
        _tr().record("prefix_cache.ttft_ticks",
                     predicted=ttft_no_reuse_ticks,
                     measured=rep["ttft_p50_ticks"],
                     source="bench.serve prefix baseline")
    # multi-tenant stores for the disaggregated/fleet replicas below: each
    # engine pool publishes the SAME seeded adapter trees (a fleet shares
    # the tenant registry), each from its own offload dir
    _extra_store_dirs = []

    def _replica_store():
        if n_adapters <= 0:
            return None
        d = tempfile.TemporaryDirectory(prefix="bench_fleet_adapters_")
        _extra_store_dirs.append(d)
        s = AdapterStore(params, lora_plugin, dtype=cfg.dtype,
                         offload_dir=d.name)
        for t in range(1, n_adapters + 1):
            s.publish_random(t, jax.random.PRNGKey(1000 + t))
        return s

    def _make_pair():
        from accelerate_tpu.serving import DisaggregatedPair

        # one AdapterStore per role: the tenant crosses the prefill→decode
        # split with its request (both-or-neither, enforced by the pair)
        kw = {}
        if n_adapters > 0:
            kw = {"adapters": _replica_store(),
                  "prefill_adapters": _replica_store()}
        return DisaggregatedPair(model, params, plugin, gen_cfg, **kw)

    if getattr(args, "disaggregate", False):
        from accelerate_tpu.serving import transfer_accounting

        # the disaggregated prefill→decode slice on the same trace:
        # page_transfer_bytes measured vs the dcn accounting model (the
        # transfer.page_bytes twin — exact unless a request never reached
        # the handoff); speculation and adapters ride the split
        pair = _make_pair()
        pair.warmup()
        pair_results = pair.run(trace)
        pair_rep = pair.report()
        pair_rep["token_parity_vs_fused"] = pair_results == rep["results"]
        rep["disaggregated"] = pair_rep
        rep["page_transfers"] = pair_rep["page_transfers"]
        rep["page_transfer_pages"] = pair_rep["page_transfer_pages"]
        rep["page_transfer_bytes"] = pair_rep["page_transfer_bytes"]
        rep["transfer_accounting"] = transfer_accounting(
            cfg, trace, plugin.page_size,
            dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
            kv_dtype=plugin.kv_dtype if plugin.kv_dtype != "bf16" else "",
        )
    else:
        rep["disaggregated"] = {"page_transfers": 0, "page_transfer_bytes": 0,
                                "token_parity_vs_fused": True}
    n_fleet = getattr(args, "fleet", 0) or 0
    if n_fleet > 0:
        from accelerate_tpu.serving import FleetRouter, fleet_replay

        # --fleet N: the same trace through N replicas (fused engines, or
        # prefill→decode pairs with --disaggregate) behind the
        # prefix-/adapter-affinity router — tokens must stay BITWISE equal
        # to the single fused engine above, zero post-warmup compiles per
        # replica (fleet_replay raises otherwise)
        def _backend():
            if getattr(args, "disaggregate", False):
                return _make_pair()
            return ServingEngine(model, params, plugin, gen_cfg,
                                 adapters=_replica_store())

        router = FleetRouter([_backend() for _ in range(n_fleet)])
        fleet_rep = fleet_replay(router, trace)
        fleet_results = fleet_rep.pop("results")
        fleet_rep["token_parity_vs_fused"] = fleet_results == rep["results"]
        rep["fleet"] = fleet_rep
    else:
        rep["fleet"] = {
            "replicas": 0, "alive": 0, "policy": "",
            "requests": 0, "completed": 0, "goodput_frac": 0.0,
            "ttft_p50_ticks": 0.0, "prefix_hit_rate": 0.0,
            "adapter_pool_hit_rate": 0.0, "page_transfer_bytes": 0,
            "compiles_warmup_by_role": {}, "compiles_measured": 0,
            "routed_by_prefix": 0, "routed_by_adapter": 0,
            "routed_by_load": 0, "drain_events": [], "fleet_clock": 0,
            "per_replica": [], "token_parity_vs_fused": True,
        }
    for d in _extra_store_dirs:
        d.cleanup()
    if trace_out is not None and trace_out != "-":
        engine.trace.write_chrome_trace(trace_out)
        rep["trace_file"] = trace_out
    # per-adapter-loop twin: the same requests served one tenant at a time
    # (what a per-adapter matmul loop forces) — the batched einsum keeps
    # every tenant in one fixed-shape program and must win on tokens/s
    loop_twin = {"tokens_per_sec_per_chip": 0.0, "wall_s": 0.0, "groups": 0}
    speedup = 0.0
    if n_adapters > 0:
        groups: dict = {}
        for r in trace:
            groups.setdefault(r.adapter_id, []).append(r)
        wall, toks = 0.0, 0
        for tid in sorted(groups):
            s = AdapterStore(params, lora_plugin, dtype=cfg.dtype,
                             offload_dir=store_dir.name)
            if tid:
                # only this group's tenant is ever pinned — same seeded
                # weights as the batched store, published once per group
                s.publish_random(tid, jax.random.PRNGKey(1000 + tid))
            eng_t = ServingEngine(model, params, plugin, gen_cfg, adapters=s)
            eng_t.warmup()
            t0 = _time.perf_counter()
            res = eng_t.run([_dc.replace(r, arrival_step=0) for r in groups[tid]])
            wall += _time.perf_counter() - t0
            toks += sum(len(v) for v in res.values())
        loop_twin = {
            "tokens_per_sec_per_chip": round(
                toks / wall / jax.device_count(), 2) if wall > 0 else 0.0,
            "wall_s": round(wall, 4),
            "groups": len(groups),
        }
        if loop_twin["tokens_per_sec_per_chip"] > 0:
            speedup = round(
                rep["tokens_per_sec_per_chip"] / loop_twin["tokens_per_sec_per_chip"], 3
            )
    rep["per_adapter_loop"] = loop_twin
    rep["batched_speedup_vs_loop"] = speedup
    if n_adapters > 0:
        rep["adapter_pool"] = adapter_pool_accounting(
            store.spec, rank=lora_plugin.rank, pool_slots=lora_plugin.pool_slots,
            dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        )
        store_dir.cleanup()
    else:
        rep["adapter_pool"] = {"pool_slots": 0, "pool_bytes": 0,
                               "swap_s_pred": 0.0, "kind": "predicted"}
    results = rep.pop("results")
    per_request = [(len(r.prompt), len(results.get(r.uid, ()))) for r in trace]
    rep["static_baseline"] = static_batching_report(per_request, plugin.num_slots)
    rep["kv_pool"] = kv_pool_accounting(
        cfg, plugin.num_pages, plugin.page_size,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        kv_dtype=plugin.kv_dtype if plugin.kv_dtype != "bf16" else "",
    )
    # ALWAYS emitted, zeros-clean: the pool's page dtype and the capacity
    # ladder (token-capacity multiple vs bf16 at equal HBM for each page
    # dtype this geometry supports — pure kv_page_bytes arithmetic)
    from accelerate_tpu.serving.paged_cache import kv_page_bytes as _kpb

    _bf16_page = _kpb(cfg, plugin.page_size,
                      jnp.dtype(cfg.dtype).itemsize)
    rep["kv_dtype"] = plugin.kv_dtype or "bf16"
    rep["fp8_amax_history_len"] = 0  # train-bench field; zeros-clean here
    rep["kv_pool_capacity_ladder"] = {
        "bf16": 1.0,
        "int8": round(_bf16_page / _kpb(cfg, plugin.page_size, 1, "int8"), 4),
        "fp8": round(_bf16_page / _kpb(cfg, plugin.page_size, 1, "fp8"), 4),
    }
    rep["serve_seed"] = args.serve_seed
    rep["decode_kernel"] = engine.model.config.attn_implementation
    rep["backend"] = jax.default_backend()
    rep["device"] = getattr(jax.devices()[0], "device_kind", "?")
    rep["n_devices"] = jax.device_count()
    rep["schema_version"] = BENCH_SCHEMA_VERSION
    # the goodput twin's serve-side clean-run model: no faults injected, so
    # the prediction is 1.0 (replay() recorded the kv/adapter/compiles rows)
    from accelerate_tpu.telemetry import twin_registry

    twin_registry().record_predicted(
        "goodput.goodput_frac", 1.0, source="bench.serve clean-run model"
    )
    rep["twins"] = _twins_block()
    return {
        "metric": "serving_tokens_per_sec_per_chip",
        "value": rep["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip",
        "extra": rep,
    }


def main():
    import argparse

    import jax
    import jax.numpy as jnp
    import optax

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=["600m", "1b", "7b"], default="600m")
    ap.add_argument("--remat", choices=["none", "dots", "full", "offload"], default=None,
                    help="1b mode only: rematerialization policy (default none)")
    ap.add_argument("--ce-chunks", type=int, default=None,
                    help="fused-CE vocab chunks override")
    ap.add_argument("--flash-block", type=int, default=None,
                    help="override flash (block_q, block_k) with a square tile")
    ap.add_argument("--grad-dtype", choices=["bf16", "fp32"], default=None,
                    help="gradient width (default: bf16 — compute-width grads "
                         "measured +0.6 MFU at 600m and required at 1b; fp32 "
                         "restores master-width grads)")
    ap.add_argument("--clip", type=float, default=-1,
                    help="max grad norm; 0 disables clipping (default: 1.0, 7b: off)")
    ap.add_argument("--seq-len", type=int, default=None, help="override sequence length")
    ap.add_argument("--batch", type=int, default=None, help="override batch size")
    ap.add_argument("--offload", action="store_true",
                    help="ZeRO-offload: optimizer state + fp32 masters in pinned host memory")
    ap.add_argument("--no-selftest", action="store_true",
                    help="skip the on-chip flash-vs-native parity check")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="capture an xplane trace of 2 post-warmup steps into DIR and "
                         "report the per-op-class device-time breakdown (the MFU "
                         "attribution table; utils/xplane.py decodes it in-process)")
    ap.add_argument("--scan-block", type=int, default=None,
                    help="override scan_block_size (layers per scan iteration)")
    ap.add_argument("--boundary-frac", type=float, default=None,
                    help="boundary_offload_fraction for offload-remat scan configs: "
                         "<1 keeps the tail slice of each boundary in device HBM, "
                         "shrinking the pinned-host residual buffer (the 131k lever)")
    ap.add_argument("--precision", choices=["bf16", "fp8"], default="bf16",
                    help="mixed_precision for the train step (fp8: scaled-e4m3 matmuls)")
    ap.add_argument("--fp8", action="store_true",
                    help="shorthand for --precision fp8: fp8 train-step matmuls "
                         "with delayed scaling (e4m3 forward / e5m2 backward, "
                         "per-tensor amax history riding TrainState.fp8_state; "
                         "ops/fp8.py).  The report always carries "
                         "fp8_amax_history_len (0 when fp8 is off)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8", "fp8"], default="bf16",
                    help="with --serve: quantized KV page pool — int8/fp8 codes "
                         "with per-(kv-head, page) scales beside the block "
                         "tables (~1.9-2x token capacity at equal HBM; the "
                         "kv_pool_capacity_ladder field).  Greedy tokens stay "
                         "within the pinned decode tolerance; the "
                         "kv_quant.page_bytes twin pins allocated vs modeled "
                         "bytes exactly")
    ap.add_argument("--optimizer",
                    choices=["lion", "adamw", "lion-sr", "adamw-sr",
                             "lion-sr8", "adamw-sr8"],
                    default=None,
                    help="default lion-sr (bf16 masters with stochastic rounding — "
                         "no fp32 master tree; the measured-best recipe at every "
                         "scale: 600m 66.0%% vs 63.0%% MFU, 1b 70.3%% vs 64.9%%, "
                         "7b 859 vs 602 tok/s — host bytes 16 -> 10 B/param). "
                         "adamw-sr is the adam-shaped SR recipe (bf16 params + "
                         "bf16 m/v, host bytes 28 -> 14 B/param at 7b). "
                         "lion-sr8/adamw-sr8 additionally store the moments as "
                         "int8 codes + per-block scales with SR requantization "
                         "(ops/int8_state.py): lion 10 -> ~8, adamw 14 -> ~10 "
                         "host B/param, and adamw's pinned host tree shrinks "
                         "37.7 -> ~25 GiB at 7b. "
                         "lion restores fp32 masters + bf16 momentum; adamw (7b: "
                         "full m+v, needs ~67GiB host RAM).")
    ap.add_argument("--int8-block", type=int, default=None,
                    help="per-block scale granularity for the -sr8 recipes "
                         "(default: FSDP plugin int8_state_block_size, i.e. 128)")
    ap.add_argument("--chunk-gib", type=float, default=None,
                    help="host-update chunk size in GiB (bounds the host's transient "
                         "working set; default 1.0 under --offload/7b, 0 = monolithic)")
    ap.add_argument("--pipeline", choices=["on", "off"], default="on",
                    help="3-stage software pipeline over the chunked host update "
                         "(ops/streaming.py: chunk k+1's grads stage D2H and chunk "
                         "k-1's outputs write back while chunk k updates). 'off' "
                         "restores the fully serialized schedule — the A/B "
                         "baseline for the overlap accounting")
    ap.add_argument("--dcn-slices", type=int, default=1, metavar="N",
                    help="simulate an N-slice topology: the mesh gets an explicit "
                         "dcn outer axis of size N (devices split N x dp_shard, "
                         "params replicated across slices) and the hierarchical "
                         "ICI->DCN gradient sync engages "
                         "(parallel/hierarchical.py)")
    ap.add_argument("--dcn-compress", choices=["on", "off"], default="off",
                    help="PowerSGD-compress the cross-slice (DCN) hop of the "
                         "hierarchical gradient sync "
                         "(GradSyncKwargs.dcn_compression='powersgd'); needs "
                         "--dcn-slices > 1")
    ap.add_argument("--collective-matmul", choices=["on", "off", "bidir"], default="off",
                    help="ring collective-matmul for the TP/SP hot path "
                         "(ops/collective_matmul.py): decompose the monolithic "
                         "all-gather/reduce-scatter around tensor-parallel "
                         "matmuls into ppermute ring schedules whose hops hide "
                         "under the partial matmuls; 'bidir' halves ring depth "
                         "with opposing half-rings.  State is echoed in extra "
                         "and tp_overlap_frac is ALWAYS reported (0.0 when the "
                         "TP axis is trivial — e.g. this bench's dp-only mesh)")
    ap.add_argument("--skip-quiet-box", action="store_true",
                    help="skip the loadavg + calibration quiet-box gate on the "
                         "host-bound offload configs (the gate only warns, never "
                         "refuses, but costs ~1s)")
    ap.add_argument("--serve", action="store_true",
                    help="serving-core traffic replay instead of the train "
                         "bench: a seeded request trace (Poisson arrivals, "
                         "mixed lengths) runs through the paged-KV "
                         "continuous-batching engine; ALWAYS emits "
                         "tokens/s/chip, p50/p99 per-token latency, KV-pool "
                         "utilization (predicted+measured), padding-waste "
                         "fraction and scheduler occupancy (zeros when the "
                         "trace is empty), plus the static-batching twin. "
                         "--batch sets the decode-slot count")
    ap.add_argument("--serve-requests", type=int, default=16,
                    help="trace length for --serve (0 = idle-engine report)")
    ap.add_argument("--serve-seed", type=int, default=0,
                    help="trace seed for --serve (same seed -> same trace "
                         "-> same schedule, pinned by the determinism test)")
    ap.add_argument("--speculate", nargs="?", const=4, type=int, default=None,
                    metavar="K",
                    help="with --serve: speculative multi-token decode — the "
                         "n-gram/prompt-lookup self-drafter proposes K tokens "
                         "per slot (default 4) and ONE batched verify pass "
                         "accepts the longest greedy-matching prefix, "
                         "bitwise-identical to single-token decode (the "
                         "generate() parity pin).  The report's always-"
                         "emitted accept_rate / tokens_per_step (predicted + "
                         "measured twins), draft_overhead_frac and "
                         "speculative_rollbacks fields measure the win; "
                         "tokens_per_step must beat the speculate-off 1.0 "
                         "on the seeded trace (pinned by smoke)")
    ap.add_argument("--prefix-share", type=float, default=None, metavar="P",
                    help="with --serve: shared-system-prompt traffic mix — "
                         "each request opens, with probability P, with one of "
                         "two seeded preambles, and the engine arms the "
                         "content-addressed COW prefix cache "
                         "(serving/prefix_cache.py).  The report's always-"
                         "emitted prefix block (prefix_hit_rate predicted + "
                         "measured twins, pages_shared_peak, cow_forks, "
                         "prefill_tokens_skipped) measures the reuse; a "
                         "no-reuse baseline replay of the SAME trace feeds "
                         "the ttft with/without-reuse comparison "
                         "(ttft_p50_ticks must improve — pinned by smoke).  "
                         "Tokens are bitwise identical with reuse on or off")
    ap.add_argument("--disaggregate", action="store_true",
                    help="with --serve: run the trace through the "
                         "disaggregated prefill→decode pair "
                         "(serving/transfer.py) instead of one fused engine "
                         "— finished KV pages stream between the two engines "
                         "through the fixed-shape wire programs, and "
                         "page_transfer_bytes is reported against the "
                         "dcn-accounting model (the transfer.page_bytes "
                         "twin, exact by construction)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="with --serve: route the same trace across N "
                         "replicas (fused engines, or prefill→decode pairs "
                         "with --disaggregate) behind the deterministic "
                         "prefix-/adapter-affinity router "
                         "(serving/router.py).  Adds the fleet block to the "
                         "report (routed-by counts, per-replica occupancy "
                         "and hit rates, drain events, fleet twins) — "
                         "fields always present, zeros when N=0.  Tokens "
                         "stay bitwise identical to the single fused "
                         "engine, zero post-warmup compiles per replica")
    ap.add_argument("--trace-requests", nargs="?", const="-", default=None,
                    metavar="FILE",
                    help="with --serve: record request-level lifecycle spans "
                         "(submit/admit/prefill-chunk/decode/evict/retire) + "
                         "per-step phase spans into the engine's bounded "
                         "ring (telemetry/spans.py) and, with FILE, export "
                         "Chrome trace-event JSON (Perfetto-loadable).  "
                         "Host-side only: tokens are bitwise identical and "
                         "strict_compiles still passes; the measured cost "
                         "lands in telemetry_overhead_frac")
    ap.add_argument("--telemetry", choices=["on", "off"], default="off",
                    help="train bench: arm the training step timeline "
                         "(telemetry/timeline.py — data_wait/h2d_staging/"
                         "step_dispatch/guard_sync/checkpoint_drain phase "
                         "spans) and report its summary + measured "
                         "telemetry_overhead_frac.  Loss is bitwise "
                         "identical on or off")
    ap.add_argument("--adapters", type=int, default=0, metavar="N",
                    help="with --serve: multi-tenant batched LoRA — N tenants' "
                         "adapters share the base model via one gathered einsum "
                         "over per-slot adapter ids (ops/lora.py), hot-swapping "
                         "through an (undersized on purpose) device pool from "
                         "OffloadStore memmaps.  Adds the adapter fields to the "
                         "report (pool hit rate predicted+measured, swap bytes, "
                         "predicted pool ladder) plus the per-adapter-loop twin "
                         "the batched path must beat (fields always present, "
                         "zeros when N=0)")
    ap.add_argument("--plan", type=int, default=None, metavar="N",
                    help="print the abstract per-device memory plan for an N-chip mesh and exit")
    ap.add_argument("--plan-task", choices=["train", "infer"], default="train",
                    help="--plan flavor: 7B training (default) or sharded 70B inference")
    ap.add_argument("--audit", action="store_true",
                    help="with --plan: also graft-lint the selected step — trace a "
                         "tiny train step through the real prepare_train_step "
                         "machinery with the selected optimizer and embed the "
                         "jaxpr-audit summary (analysis/jaxpr_audit.py; pure "
                         "trace, CPU-safe, no device execution)")
    args = ap.parse_args()
    if args.fp8:
        args.precision = "fp8"

    if args.plan:
        if args.plan_task == "infer":
            rep = {
                "metric": "llama2_70b_sharded_inference_plan", "value": args.plan,
                "unit": "devices",
                "extra": plan_infer_report(args.plan, args.seq_len or 2048, args.batch or 8),
            }
        else:
            rep = {
                "metric": "llama2_7b_memory_plan", "value": args.plan, "unit": "devices",
                "extra": plan_report(args.plan, args.seq_len or 2048, args.batch or 1,
                                     offload=args.offload,
                                     optimizer=args.optimizer or "lion-sr"),
            }
        rep["extra"]["schema_version"] = BENCH_SCHEMA_VERSION
        if args.audit:
            from accelerate_tpu.analysis import Report, apply_suppressions
            from accelerate_tpu.commands.lint import audit_canonical_step
            from accelerate_tpu.commands.preflight import preflight_train
            from accelerate_tpu.state import AcceleratorState, GradientState
            from accelerate_tpu.utils.dataclasses import PreflightConfig

            audit = audit_canonical_step(args.optimizer or "lion-sr")
            rep["extra"]["audit"] = audit.summary()
            AcceleratorState._reset_state(reset_partial_state=True)
            GradientState._reset_state()
            # the compiled twin rides next to the trace audit: AOT-compile
            # the same canonical step and audit the executable (GL301-303
            # + the flops/bytes cost row the predicted-MFU math feeds on)
            findings, rows = preflight_train(
                PreflightConfig(optimizer=args.optimizer or "lion-sr")
            )
            compiled_report = Report(apply_suppressions(findings))
            rep["extra"]["compiled_audit"] = {
                **compiled_report.summary(), "programs": rows,
            }
            # the distributed twin: the GL4xx pair audit of the serving
            # handoff (wire schema + handoff schedule + warmup coverage),
            # static slice only — trace-free, so the plan path stays cheap
            from accelerate_tpu.commands.lint import audit_distributed_contracts

            dist_findings = apply_suppressions(audit_distributed_contracts())
            rep["extra"]["distributed_audit"] = {
                **Report(dist_findings).summary(),
                "rules": sorted({f.rule for f in dist_findings}),
            }
        print(json.dumps(rep))
        return

    # persistent compile cache: repeat bench runs skip the first-compile of
    # the train step; placed by the one rule in utils/compile_cache.py
    from accelerate_tpu.utils.compile_cache import enable_scoped_compilation_cache

    enable_scoped_compilation_cache("bench", min_compile_time_secs=1.0)

    if args.serve:
        print(json.dumps(serve_report(args)))
        return

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
    from accelerate_tpu.models.llama import count_params, flops_per_token

    on_tpu = jax.default_backend() == "tpu"
    if args.optimizer is None:
        # lion-sr measured best at every TPU scale (see --optimizer help);
        # CPU runs keep the historical recipes (lion at 7b/1b, adamw smoke)
        args.optimizer = ("lion-sr" if on_tpu
                          else "lion" if args.model in ("7b", "1b") else "adamw")

    def make_sr_tx(kind):
        """The named SR recipe at its bench hyperparameters (lr via the
        registry defaults: lion family 1e-4, adam family 3e-4).  -sr8 block
        size resolves --int8-block > the FSDP plugin knob (which itself
        reads ACCELERATE_INT8_STATE_BLOCK) > registry default 128."""
        from accelerate_tpu.optimizer import make_optimizer

        block = None
        if kind.endswith("-sr8"):
            block = args.int8_block
            if block is None and fsdp_plugin is not None:
                block = fsdp_plugin.int8_state_block_size
            if block is None:
                import os

                env = os.environ.get("ACCELERATE_INT8_STATE_BLOCK")
                block = int(env) if env else None
            extra_report["int8_state_block"] = block or 128
        return make_optimizer(kind, block_size=block)

    def sr_recipe(params, kind="lion-sr"):
        """bf16 masters + stochastic rounding (ops/stochastic_rounding.py,
        ops/int8_state.py for the -sr8 int8-state variants): the shared
        resident-model setup — cast the stored params to bf16 (they ARE the
        masters) and return the SR transform (lion- or adam-shaped, all
        per-leaf independent + traced-hyperparam)."""
        cast = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else p,
            params,
        )
        return make_sr_tx(kind), cast
    extra_report = {}
    if on_tpu and not args.no_selftest:
        selftest(extra_report)
        selftest_kernels(extra_report)
    if on_tpu and args.model == "7b":
        # Llama-2-7B on ONE 16GiB chip: only possible with ZeRO-offload
        # (bf16 params alone are 12.6GiB; masters + moments live host-side)
        seq = args.seq_len or 2048
        cfg = _7b_config(jnp, seq)
        batch = args.batch or 1
        iters = args.iters or 3
        args.offload = True
    elif on_tpu and args.model == "1b":
        # resident-HBM point at representative depth/width: no offload, the
        # full train state lives on-chip.  remat-off batch 2 is the measured
        # sweet spot (dots fits only batch 2 and recomputes flash fwd; batch
        # 3+ OOMs at every policy with fp32 masters resident)
        seq = args.seq_len or 2048
        cfg = _1b_config(jnp, seq, args.remat or "none")
        # lion-sr frees the fp32 master tree (~8GiB with its transients):
        # batch 3 fits and is the measured sweet spot (70.3% MFU; batch 4
        # fits too at 70.0%); fp32-master recipes cap at batch 2.  adamw-sr
        # also fits batch 3 (64.9% MFU measured) — fp32-master adamw OOMs
        # at EVERY batch here (the fp32 second moment alone adds 5.4GiB)
        batch = args.batch or (3 if args.optimizer in SR_KINDS else 2)
        iters = args.iters or 8
    elif on_tpu:
        seq = args.seq_len or 2048
        # Long sequences need full remat (activations dominate); the shipped
        # 2048 config runs remat-off — with the fused CE keeping [B,T,V]
        # logits out of HBM, full activations fit in 16G, worth +7% step
        # time over remat_policy="dots" (measured on v5e)
        long_ctx = seq > 4096
        # ~600M decoder: fits one v5e chip with fp32 Adam state at seq 2048.
        # Past ~96k the remat boundary activations alone exceed HBM — the
        # "offload" policy parks them in pinned host memory
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
            max_position_embeddings=seq, attn_implementation="flash",
            remat=long_ctx, dtype=jnp.bfloat16,
            remat_policy="offload" if seq > 98304 else "full",
            # scanned stack: inside lax.scan the offloaded boundaries
            # actually leave HBM (unrolled, the scheduler parks ~5GiB of
            # them — the r2 131k blocker).  Past 112k the WORKER HOST's
            # pinned allocation becomes the ceiling (6.4GiB of boundaries
            # at 131k crashed it); pair iterations halve the offloaded
            # boundary count for ~25% extra recompute.
            scan_layers=seq > 98304,
            scan_block_size=(
                args.scan_block or (2 if seq > 114688 else 1)
            ) if seq > 98304 else 1,
            boundary_offload_fraction=(
                args.boundary_frac if args.boundary_frac is not None else 1.0
            ),
        )
        # batch 10 is the HBM sweet spot without remat (8: -4%, 12: OOM)
        batch = args.batch or (1 if long_ctx else 10)
        iters = args.iters or (4 if long_ctx else 10)
        if args.boundary_frac is not None and seq > 98304:
            extra_report["boundary_offload_fraction"] = args.boundary_frac
    else:  # CPU smoke mode
        cfg = LlamaConfig.tiny()
        batch, seq, iters = args.batch or 4, args.seq_len or 128, args.iters or 3

    if args.boundary_frac is not None and "boundary_offload_fraction" not in extra_report:
        # only the 600m boundary-offload remat configs (TPU, seq > 98304)
        # consume the knob; say so instead of silently ignoring it
        import sys

        print(
            "bench.py: --boundary-frac only applies to the 600m long-context "
            "boundary-offload configs (seq > 98304 on TPU); ignored for "
            f"model={args.model!r} seq={seq} backend={jax.default_backend()!r}",
            file=sys.stderr,
        )
        extra_report["boundary_frac_ignored"] = args.boundary_frac

    if args.flash_block:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, flash_block_q=args.flash_block, flash_block_k=args.flash_block)
        extra_report["flash_block"] = args.flash_block
    elif args.offload and cfg.attn_implementation == "flash":
        # under host offload the D2H transfers XLA fuses around the flash
        # backward push the (1024, 1024) tile ~192KB over the Mosaic
        # scoped-VMEM stack limit (same failure class as the documented
        # d>=128-under-remat case); the 512 tile costs ~1.5% and compiles
        import dataclasses as _dc

        cfg = _dc.replace(cfg, flash_block_q=512, flash_block_k=1024)
        extra_report["flash_block"] = "512x1024 (offload clamp)"
    model = LlamaForCausalLM(cfg)
    n_dev = jax.device_count()
    fsdp_plugin = None
    if args.offload:
        from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin

        # chunked host update by default: per-leaf-group compute_on regions
        # bound the host's transient working set (monolithic adamw at 7B
        # crashed the worker host); 0 restores the monolithic region
        chunk = 1.0 if args.chunk_gib is None else args.chunk_gib
        # the pipeline exists only over the chunk sequence: --chunk-gib 0
        # (monolithic region) means no pipeline ran, and the report must
        # say so or cross-round BENCH_*.json comparisons mislabel the runs
        pipelined = args.pipeline == "on" and bool(chunk)
        fsdp_plugin = FullyShardedDataParallelPlugin(
            cpu_offload=True, host_update_chunk_gib=chunk or None,
            host_update_pipeline=pipelined,
        )
        extra_report["host_update_chunk_gib"] = chunk or None
        extra_report["host_update_pipeline"] = pipelined
        if on_tpu and not args.skip_quiet_box:
            # the offloaded step is host-DRAM-bound: a loaded worker host
            # measures the load, not the code (VERDICT r5 weak #7).  Warn —
            # the bench still runs, but the report carries the evidence.
            from accelerate_tpu.utils.environment import quiet_box_gate

            gate = quiet_box_gate()
            extra_report["quiet_box"] = gate
            if not gate["ok"]:
                import sys as _sys

                for w in gate["warnings"]:
                    print(f"bench.py: QUIET-BOX WARNING: {w}", file=_sys.stderr)
    handlers = []
    # compute-width (bf16) grads by default: the fp32 grad tree never
    # materializes.  At 1b this is what lets the resident config keep
    # remat off (fp32 masters + bf16 lion momentum + bf16 grads); at 600m
    # it is a straight step-time win (63.1% vs 62.5% MFU measured, batch
    # 10) from halved grad-tree HBM traffic.  fp16 needs fp32 unscaling,
    # and the CPU smoke mode keeps plain fp32 grads.
    dcn_slices = max(1, args.dcn_slices)
    if args.grad_dtype != "fp32" and args.precision == "bf16" and on_tpu \
            and dcn_slices <= 1:
        # (skipped under --dcn-slices: the hierarchical sync reduces in fp32
        # — a grad_dtype knob would be silently ignored, so don't set one)
        from accelerate_tpu.utils.dataclasses import GradSyncKwargs

        handlers.append(GradSyncKwargs(grad_dtype="bf16"))
        extra_report["grad_dtype"] = "bf16"
    if dcn_slices > 1:
        # simulated multi-slice: dcn outer axis, params replicated across
        # slices (NO_SHARD — the hierarchical path is the DDP comm-hook
        # shape), dp_shard as the intra-slice ICI plane
        if n_dev % dcn_slices:
            raise SystemExit(
                f"--dcn-slices {dcn_slices} does not divide {n_dev} devices"
            )
        if args.offload:
            raise SystemExit("--dcn-slices is incompatible with --offload "
                             "(the hierarchical sync needs resident replicated params)")
        from accelerate_tpu.utils.dataclasses import (
            FullyShardedDataParallelPlugin, GradSyncKwargs, ShardingStrategy,
        )

        fsdp_plugin = FullyShardedDataParallelPlugin(
            sharding_strategy=ShardingStrategy.NO_SHARD
        )
        pcfg = ParallelismConfig(dcn_size=dcn_slices,
                                 dp_shard_size=n_dev // dcn_slices)
        if args.dcn_compress == "on":
            handlers.append(GradSyncKwargs(dcn_compression="powersgd"))
    else:
        if args.dcn_compress == "on":
            raise SystemExit("--dcn-compress on needs --dcn-slices > 1 "
                             "(no dcn mesh axis, nothing crosses DCN)")
        pcfg = ParallelismConfig(dp_shard_size=n_dev)
    from accelerate_tpu.utils.dataclasses import TelemetryPlugin

    telemetry_on = args.telemetry == "on"
    acc = Accelerator(
        parallelism_config=pcfg,
        mixed_precision=args.precision,
        fsdp_plugin=fsdp_plugin,
        kwargs_handlers=handlers,
        telemetry_plugin=TelemetryPlugin(
            enabled=telemetry_on, timeline=telemetry_on, trace_requests=False,
        ),
    )
    # ring collective-matmul mode: installed AFTER the accelerator so the
    # bench flag wins over the plugin/env default; trace-time — the train
    # step below compiles under it
    from accelerate_tpu.ops.collective_matmul import set_collective_matmul

    cm_mode = {"on": "ring", "off": "off", "bidir": "bidir"}[args.collective_matmul]
    set_collective_matmul(cm_mode)

    ids = jnp.ones((batch, seq), jnp.int32)
    if args.model == "7b":
        # Leaf-streamed init into pinned host memory: the monolithic flax
        # init executable would stage the whole 27GiB fp32 tree in HBM
        # before writing host outputs (measured OOM).  Real 7B flows stream
        # weights leaf-by-leaf from a checkpoint anyway; this mirrors that.
        from accelerate_tpu.big_modeling import init_params_leafwise

        # lion-sr keeps the stored params themselves in bf16 (stochastic
        # rounding replaces the fp32 master tree): 13.5GiB pinned instead
        # of 27, and half the per-step master read/write traffic
        params = init_params_leafwise(
            model, acc, ids[:, :8],
            dtype=jnp.bfloat16 if args.optimizer in SR_KINDS else None,
        )
    else:
        # init directly into the plan's shards (host shards under --offload)
        params = acc.init_params(model, jax.random.key(0), ids[:, :8])
    # bf16 first moment: halves Adam's m-state HBM traffic and footprint
    # (standard large-scale practice; second moment and master weights stay
    # fp32) — worth ~3 MFU points at this config
    if args.model == "7b":
        # inject_hyperparams turns the optimizer scalars into traced
        # host-state: XLA's host-compute lowering materializes *literal*
        # scalars as full-leaf-size fp32 broadcasts (6 x 500MiB at 7B —
        # measured OOM), while traced host scalars broadcast on the host
        # for free.
        if args.optimizer in SR_KINDS:
            # hyperparams already ride the state as traced scalars (the
            # transform's own inject_hyperparams analog), and the update is
            # per-leaf independent — chunked-host-region compatible.  The
            # -sr8 variants keep the moments int8-quantized in pinned host
            # memory (the host-byte floor: lion ~8, adamw ~10 B/param).
            tx = make_sr_tx(args.optimizer)
        elif args.optimizer == "adamw":
            tx = optax.inject_hyperparams(optax.adamw, static_args=("mu_dtype",))(
                learning_rate=3e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                mu_dtype=jnp.bfloat16,
            )
        else:
            # lion: momentum-only state (bf16-able) — host-side optimizer
            # state shrinks from ~54GiB (adam m+v) to ~13.5GiB, keeping the
            # whole host working set inside the TPU VM's RAM.  (adafactor's
            # internal `where`s mix host/device memory spaces under the
            # host-compute lowering; lion's sign-based update lowers clean.)
            tx = optax.inject_hyperparams(optax.lion, static_args=("mu_dtype",))(
                learning_rate=1e-4, b1=0.9, b2=0.99, weight_decay=0.0,
                mu_dtype=jnp.bfloat16,
            )
    elif args.model == "1b":
        # lion: momentum-only optimizer state (bf16-able) — fp32 masters
        # (5.4GiB) + bf16 momentum (2.7GiB) is the only optimizer budget
        # that leaves room for cheap remat at 1.3B on 16GiB (adamw's fp32
        # second moment alone adds 5.4GiB, measured OOM at every batch).
        # lion-sr drops the fp32 masters entirely (params stay bf16 with
        # stochastic rounding): ~8GiB freed for batch headroom.
        if args.optimizer in SR_KINDS:
            tx, params = sr_recipe(params, args.optimizer)
        else:
            tx = (optax.lion(1e-4, b1=0.9, b2=0.99, mu_dtype=jnp.bfloat16)
                  if args.optimizer == "lion"
                  else optax.adamw(3e-4, mu_dtype=jnp.bfloat16))
    else:
        # same choice logic on TPU and in the CPU smoke mode: the report
        # labels the run with args.optimizer, so the recipe must match
        if args.optimizer in SR_KINDS:
            tx, params = sr_recipe(params, args.optimizer)
        elif args.optimizer == "lion":
            tx = optax.lion(1e-4, b1=0.9, b2=0.99, mu_dtype=jnp.bfloat16)
        else:
            tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16 if on_tpu else None)
    state = acc.create_train_state(params, tx, apply_fn=model.apply)
    if args.offload and on_tpu:
        # the whole point of offload: moments live in pinned host memory
        kinds = {
            getattr(getattr(x, "sharding", None), "memory_kind", None)
            for x in jax.tree_util.tree_leaves(state.opt_state)
            if hasattr(x, "sharding")
        }
        assert kinds == {"pinned_host"}, f"offload storage not host-pinned: {kinds}"
        extra_report["offload"] = "pinned_host"
    # fused linear+CE keeps the [B,T,V] logits out of HBM, which is what lets
    # the cheaper "dots" remat policy fit on a 16G chip; 4 vocab chunks
    # measured best on v5e (vs 8: +1%, vs 16: +1.2%); long context needs the
    # per-chunk fp32 logits [B, T/chunks, V] bounded (~250MB at 128k/64)
    chunks = (max(16, seq // 2048) if seq > 4096 else 4) if on_tpu else None
    if args.ce_chunks:
        chunks = args.ce_chunks
    # global-norm clipping is an all-grads barrier; at 7B-on-one-chip the
    # full grad tree cannot be resident at once, so the 7B config trains
    # unclipped (per-leaf norm metric still reported).  The 1b/lion config
    # also runs unclipped: lion's sign update bounds every step at lr
    # regardless of grad magnitude, so the clip would change only the
    # momentum accumulation while costing a measured 9% step time (the
    # barrier blocks the update from overlapping the tail of backward).
    # The same argument applies to any lion-family optimizer at any scale
    # (incl. the long-context 600m configs, where the barrier also pins
    # the whole grad tree across the scanned stack).
    max_norm = (None if args.model in ("7b", "1b")
                or args.optimizer in ("lion", "lion-sr", "lion-sr8") else 1.0)
    if args.clip >= 0:
        max_norm = args.clip or None
    step = acc.prepare_train_step(
        make_llama_loss_fn(model, fused_vocab_chunks=chunks),
        max_grad_norm=max_norm,
    )

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    from jax.sharding import NamedSharding

    spec = acc._default_batch_spec()(tokens)
    make_batch = lambda arr: {
        "input_ids": jax.device_put(arr, NamedSharding(acc.mesh, spec)),
        "labels": jax.device_put(arr, NamedSharding(acc.mesh, spec)),
    }
    b = make_batch(tokens)

    # Warmup (compile + first run); the loss fetch forces full execution.
    for _ in range(2):
        state, metrics = step(state, b)
        float(metrics["loss"])

    if args.trace:
        # separate from the timed loop: tracing costs a few % and the
        # attribution wants clean shares, not a perturbed headline number
        jax.profiler.start_trace(args.trace)
        for _ in range(2):
            state, metrics = step(state, b)
        float(metrics["loss"])
        jax.profiler.stop_trace()
        from accelerate_tpu.utils.xplane import (
            op_class_breakdown, streaming_overlap_report, top_ops,
        )

        dev_substr = "TPU" if on_tpu else "CPU"
        extra_report["op_breakdown"] = op_class_breakdown(args.trace, dev_substr)
        extra_report["top_ops"] = [
            (name, round(ms, 2)) for name, ms in top_ops(args.trace, 12, dev_substr)
        ]
        # measured transfer-vs-compute occupancy (the predicted `streaming`
        # block's counterpart; under --offload the achieved overlap_frac of
        # the chunk pipeline is read off this table) — reuses the breakdown
        # just computed instead of re-aggregating the trace
        extra_report["streaming_measured"] = streaming_overlap_report(
            args.trace, dev_substr, breakdown=extra_report["op_breakdown"]
        )
        # measured ICI collective-vs-compute occupancy (the ring collective-
        # matmul's measured tp_overlap_frac; predicted twin under `tp_comm`)
        from accelerate_tpu.utils.xplane import ici_overlap_report

        extra_report["ici_measured"] = ici_overlap_report(
            args.trace, dev_substr, breakdown=extra_report["op_breakdown"]
        )

    compiles_before = acc.compile_events
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, b)
    float(metrics["loss"])  # host fetch: everything up to here has executed
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    # recompile guard twins (ALWAYS emitted): the warmup step above already
    # compiled the program, so the steady-state loop predicts exactly zero
    # compile events — a non-zero measured count is a re-keyed jit cache
    # (the GL304 promotion-drift shape) poisoning every number in this report
    compiles_measured = acc.compile_events - compiles_before

    toks_per_step = batch * seq
    toks_per_sec = toks_per_step * iters / dt
    per_chip = toks_per_sec / n_dev
    step_flops = flops_per_token(cfg, seq) * toks_per_step
    peak = _peak_flops(jax.devices()[0])
    mfu = round((step_flops * iters / dt) / (peak * n_dev), 4) if peak else None

    # Overlap accounting — ALWAYS emitted (overlap_frac/h2d_bytes/d2h_bytes)
    # so BENCH_*.json tracks the streaming fields across rounds; zeros when
    # nothing streams.  For offload runs the numbers come from the
    # predicted-overlap model in ops/streaming.py (exact bytes, rates from
    # the measured host-probe/PCIe figures); --pipeline off reports the
    # serialized baseline's zero overlap.
    from accelerate_tpu.ops.streaming import offload_transfer_accounting

    if args.offload:
        grad_wire_b = 2 if (args.precision == "bf16" and args.grad_dtype != "fp32"
                            and on_tpu) else 4
        # the H2D leg is the cast-to-compute param fetch, and every bench
        # precision (bf16/fp8) computes at bf16 width — unlike the grad
        # wire, which --grad-dtype fp32 widens to master width
        streaming = offload_transfer_accounting(
            count_params(state.params),
            optimizer=args.optimizer,
            grad_bytes_per_param=grad_wire_b,
            fetch_bytes_per_param=2,
            offload_params=True,
        )
        if not pipelined:
            streaming["overlap_frac"] = 0.0
            streaming["kind"] = "serialized-baseline"
        extra_report["streaming"] = streaming
        overlap_fields = {
            "overlap_frac": streaming["overlap_frac"],
            "h2d_bytes": streaming["h2d_bytes"],
            "d2h_bytes": streaming["d2h_bytes"],
        }
    else:
        overlap_fields = {"overlap_frac": 0.0, "h2d_bytes": 0, "d2h_bytes": 0}

    # ICI plane: tp_overlap_frac rides next to overlap_frac in EVERY report
    # (0.0 when the TP axis is trivial or the ring is off) so BENCH_*.json
    # tracks the collective-matmul fields across rounds.  Predicted numbers
    # from the ring model (ops/collective_matmul.tp_comm_accounting) at the
    # run's matmul shapes; --trace adds the measured twin (`ici_measured`).
    tp_size = int(acc.mesh.shape.get("tp", 1))
    tp_overlap = 0.0
    if cm_mode != "off" and tp_size > 1:
        from accelerate_tpu.ops.collective_matmul import tp_comm_accounting

        tp_comm = tp_comm_accounting(
            batch * seq, cfg.hidden_size, cfg.intermediate_size, tp_size,
            bidirectional=(cm_mode == "bidir"), peak_flops=peak,
        )
        tp_overlap = tp_comm["tp_overlap_frac"]
        extra_report["tp_comm"] = tp_comm
    overlap_fields["tp_overlap_frac"] = tp_overlap
    extra_report["collective_matmul"] = cm_mode

    # DCN plane: cross-slice gradient-sync accounting — dcn_bytes /
    # dcn_bytes_flat / dcn_overlap_frac are ALWAYS emitted (zeros on meshes
    # without a dcn axis) so BENCH_*.json tracks the multi-slice fields
    # across rounds.  dcn_bytes is the per-device cross-slice wire cost of
    # the path the step actually compiled (hierarchical slab — PowerSGD
    # factors under --dcn-compress on — or the flat fallback);
    # dcn_bytes_flat is the flat-reduce twin the hierarchical schedule is
    # judged against (parallel/hierarchical.dcn_comm_accounting).
    from accelerate_tpu.parallel.hierarchical import dcn_comm_accounting

    dcn_sync = acc.dcn_sync or {}
    step_s = dt / iters
    dcn_acct = acc.dcn_sync_accounting(state.params, step_compute_s=step_s)
    if dcn_sync.get("enabled"):
        dcn_bytes, dcn_overlap = dcn_acct["dcn_bytes"], dcn_acct["dcn_overlap_frac"]
    else:
        # flat path (no dcn axis, or hierarchical fell back): the active
        # schedule's DCN bytes ARE the flat bytes (ici_size=1 degenerates
        # the slab model to the full tree; zeros when dcn_size == 1)
        flat_acct = dcn_comm_accounting(
            state.params, ici_size=1, dcn_size=dcn_acct["dcn_size"],
            step_compute_s=step_s,
        )
        dcn_bytes, dcn_overlap = flat_acct["dcn_bytes"], flat_acct["dcn_overlap_frac"]
    overlap_fields["dcn_bytes"] = dcn_bytes
    overlap_fields["dcn_bytes_flat"] = dcn_acct["dcn_bytes_flat"]
    overlap_fields["dcn_overlap_frac"] = dcn_overlap
    extra_report["dcn_comm"] = {
        **dcn_acct, "hierarchical": bool(dcn_sync.get("enabled")),
        "fallback_reason": dcn_sync.get("why_not"),
    }

    # Resilience accounting — nan_skips/restarts/goodput_frac are ALWAYS
    # emitted so BENCH_*.json tracks fault handling across rounds: a clean
    # run reports zero skips/restarts and goodput_frac 1.0 (the measured
    # tracker on the accelerator; predicted twin:
    # resilience.goodput_accounting).  The full counter digest rides in
    # extra["goodput"].
    goodput = acc.goodput.report()
    resilience_fields = {
        "nan_skips": goodput["nan_skips"],
        "restarts": goodput["restarts"],
        "goodput_frac": goodput["goodput_frac"],
        "compiles_predicted": 0,
        "compiles_measured": compiles_measured,
    }
    extra_report["goodput"] = goodput
    # Recovery-ladder block — ALWAYS emitted, zeros-clean: a bench run never
    # walks the ladder (restore_path "none", zero bytes/seconds); when peer
    # snapshots are armed the snapshotter's captured bytes land here and the
    # recovery.peer_snapshot_bytes twin (tolerance 0 vs peer_ckpt_accounting)
    # carries the drift verdict.
    snap = acc.peer_snapshotter
    extra_report["recovery"] = {
        "restore_path": "none",
        "peer_snapshot_bytes": (
            snap.schema["snapshot_bytes"] if snap is not None else 0
        ),
        "restore_time_s": 0.0,
    }

    # Unified telemetry (telemetry/): schema_version + twins +
    # telemetry_overhead_frac are ALWAYS emitted — zeros-clean when nothing
    # recorded, measured when --telemetry on armed the training timeline.
    # The accounting calls above already recorded their predicted sides;
    # the twin registry renders them with per-twin rel_err/status.
    from accelerate_tpu.telemetry import twin_registry

    reg = twin_registry()
    reg.record("compiles.steady_state", predicted=0,
               measured=compiles_measured, source="bench.train steady-state")
    # clean-run goodput model: no faults injected in a bench run, predicted
    # retention is 1.0 (goodput_accounting covers cadence-model predictions)
    reg.record_predicted("goodput.goodput_frac", 1.0,
                         source="bench.train clean-run model")
    # ALWAYS emitted, zeros-clean: the delayed-scaling window when fp8 is
    # armed (the amax history riding TrainState.fp8_state), 0 otherwise
    from accelerate_tpu.ops.fp8 import amax_history_len as _amax_hist_len

    fp8_hist_len = (_amax_hist_len()
                    if getattr(state, "fp8_state", None) is not None else 0)
    telemetry_fields = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "telemetry_overhead_frac": (
            acc.timeline.overhead_frac(dt) if acc.timeline is not None else 0.0
        ),
        "twins": _twins_block(),
    }
    if acc.timeline is not None:
        extra_report["timeline"] = acc.timeline.summary()

    print(json.dumps({
        "metric": "llama_bf16_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4) if peak else None,
        "extra": {
            # grad_dtype defaults to the master width unless the bf16-grad
            # handler was installed (which sets the key above)
            "grad_dtype": extra_report.pop("grad_dtype", "fp32"),
            **overlap_fields,
            **resilience_fields,
            **telemetry_fields,
            **extra_report,
            "precision": args.precision,
            "fp8_amax_history_len": fp8_hist_len,
            "optimizer": args.optimizer,
            "mfu": mfu,
            "params": count_params(state.params),
            "batch": batch, "seq_len": seq,
            "step_time_ms": round(dt / iters * 1e3, 2),
            "loss": round(float(metrics["loss"]), 4),
            "backend": jax.default_backend(),
            "device": getattr(jax.devices()[0], "device_kind", "?"),
            "n_devices": n_dev,
        },
    }))


if __name__ == "__main__":
    main()
