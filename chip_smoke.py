"""chip_smoke.py — the standing proof that the main path starts on the chip.

    python chip_smoke.py              one TPU chip: a train phase, then a serve phase
    python chip_smoke.py --chips 4    four chips: ONLY the FSDP x TP sharded train step
                                      and the one-device steps it is compared with
    python chip_smoke.py --rehearse   CPU rehearsal (tiny shapes, interpret-mode
                                      kernels); combines with --chips 4 (virtual devices)

Everything runs in THIS process: a chip belongs to one process at a time, so
the script starts no children.  Without ``--rehearse`` it refuses to run
(non-zero exit, no result line) unless ``jax.devices()[0].platform == "tpu"``;
the rehearsal is chosen only by the option, never by noticing that no chip is
there.  A failing check raises — no phase's exception is caught and turned
into a field — so any failure is a non-zero exit.

Each phase prints one JSON line; the LAST stdout line is the contract's
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as jax reports it.  No MFU, no roofline: those are the benchmark's.

The model: Llama-2-7B at its published widths (hidden 4096, intermediate
11008, 32 heads x head_dim 128, MHA, vocab 32000 — ``bench.py:_7b_config``),
bfloat16, weights from a seed, DEPTH CUT to 6 layers.  Why 6: one layer is
202.4M parameters and embedding + head are 262.1M, so 6 layers are 1.476B.
The resident lion-sr recipe keeps 6 B/param (bf16 params that ARE the
masters + bf16 momentum + bf16 grads).  The AOT compile of the whole train
step for a described v5e chip (``compiled.memory_analysis()``, batch
4 x 2048, no remat, no offload) reads 5.50 GiB of arguments (params +
momentum) + 5.36 GiB of temporaries (grads + activations) = 10.86 GiB live at
6 layers, against 15.75 GiB of usable HBM; 8 layers read 13.92 GiB, which
leaves under 2 GiB for the allocator, the init transients and the reference
programs that share the process.  The engine's decode and prefill programs
read 4.4 GiB at 6 layers (params 2.75 + a 1.5 GiB KV pool).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

SEED = 0

# Llama-2-7B published widths; only depth is cut (see module docstring)
REAL = dict(
    model=dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
               num_hidden_layers=6, num_attention_heads=32, num_key_value_heads=32,
               max_position_embeddings=2048),
    batch=4, seq=2048, ce_chunks=4,
    # 16 decode lanes x 16 pages x 64 tokens (1024-token ceiling per
    # sequence), 256-page pool = 1 MiB/page/layer of bf16 K+V
    serve=dict(num_slots=16, page_size=64, pages_per_slot=16, num_pages=256,
               prefill_chunk=512, prefill_buckets=(128, 512)),
    requests=10, prompt_range=(64, 512), new_range=(32, 64),
)
# --rehearse: control flow at a size the CPU backend + Pallas interpreter run in seconds
TINY = dict(
    model=dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
               max_position_embeddings=256),
    batch=2, seq=128, ce_chunks=4,
    serve=dict(num_slots=4, page_size=8, pages_per_slot=8, num_pages=32,
               prefill_chunk=32, prefill_buckets=(16, 32)),
    requests=8, prompt_range=(8, 32), new_range=(4, 8),
)
TIMED_STEPS = 3

# Tolerances (bf16 activations, f32 accumulation).  Two programs that differ
# only in the attention implementation round intermediate activations to
# bf16 (2^-9 relative) in a different order; over ~10 roundings per layer the
# loss — a mean over thousands of tokens of a value near ln(vocab) ~ 10.4 —
# moves in its third decimal, and a single logit by ~1% of the logit scale.
LOSS_ATOL = 2e-2          # |loss_flash - loss_native|, first step
LOGIT_RTOL = 3e-2         # max|logits_engine - logits_native| / max|logits_native|
SHARDED_LOSS_ATOL = 2e-2  # per-step |loss_sharded - loss_one_device| (--chips 4)


def emit(**fields):
    print(json.dumps(fields), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(f"chip_smoke: {msg}")


def peak_bytes(device):
    stats = device.memory_stats()  # None on the CPU backend
    return stats.get("peak_bytes_in_use") if stats else None


def build_model(size, attn):
    import jax.numpy as jnp

    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(
        **size["model"], attn_implementation=attn, dtype=jnp.bfloat16))


def reset_singletons():
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def make_trainer(size, parallelism_config=None):
    """Accelerator -> create_train_state -> prepare_train_step over the
    flash-attention model with the fused CE and the resident lion-sr recipe
    (bf16 params ARE the masters; bf16 momentum; bf16 grads)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import make_llama_loss_fn
    from accelerate_tpu.utils.dataclasses import GradSyncKwargs

    acc = Accelerator(mixed_precision="bf16", parallelism_config=parallelism_config,
                      kwargs_handlers=[GradSyncKwargs(grad_dtype="bf16")])
    model = build_model(size, "flash")
    tokens = np.random.default_rng(SEED).integers(
        0, size["model"]["vocab_size"], (size["batch"], size["seq"])).astype(np.int32)
    params = acc.init_params(model, jax.random.key(SEED), jnp.asarray(tokens[:, :8]))
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    state = acc.create_train_state(params, "lion-sr", apply_fn=model.apply)
    step = acc.prepare_train_step(
        make_llama_loss_fn(model, fused_vocab_chunks=size["ce_chunks"]))
    sharding = NamedSharding(acc.mesh, acc._default_batch_spec()(tokens))
    batch = {"input_ids": jax.device_put(tokens, sharding),
             "labels": jax.device_put(tokens, sharding)}
    return acc, model, state, step, batch


def run_steps(acc, state, step, batch):
    """A warm-up step, then TIMED_STEPS steps on the same batch under the host
    clock, ending in block_until_ready.  Returns the final state and a dict
    (losses of all 1 + TIMED_STEPS steps, compile and step seconds)."""
    import jax

    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    losses = [float(metrics["loss"])]
    compile_s = time.perf_counter() - t0
    compiles_warm = acc.compile_events
    kept = []
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, metrics = step(state, batch)
        kept.append(metrics["loss"])
    jax.block_until_ready((state.params, kept))
    step_s = (time.perf_counter() - t0) / TIMED_STEPS
    losses += [float(x) for x in kept]
    compiles = acc.compile_events - compiles_warm
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}")
    check(compiles == 0, f"{compiles} compile(s) after the warm-up step")
    return state, dict(losses=losses, compile_s=compile_s, step_s=step_s,
                       compiles_after_warmup=compiles)


def train_phase(size, on_tpu):
    import jax
    import numpy as np

    from accelerate_tpu.models import make_llama_loss_fn

    acc, model, state, step, batch = make_trainer(size)
    dev = jax.devices()[0]

    # the same first-step loss under attn_implementation="native", on the
    # initial params, BEFORE the first step donates them; one row at a time
    # (every row has seq-1 targets, so the mean of row losses is the batch loss)
    native_loss = jax.jit(make_llama_loss_fn(
        build_model(size, "native"), fused_vocab_chunks=size["ce_chunks"]))
    rows = [native_loss(state.params, {k: v[i:i + 1] for k, v in batch.items()})
            for i in range(size["batch"])]
    loss_native = float(np.mean([float(r) for r in rows]))

    state, run = run_steps(acc, state, step, batch)
    loss_delta = abs(run["losses"][0] - loss_native)
    check(loss_delta <= LOSS_ATOL,
          f"first-step loss flash {run['losses'][0]} vs native {loss_native}: "
          f"|delta| {loss_delta} > {LOSS_ATOL}")
    kernel_in_program = None
    if on_tpu:  # interpret-mode kernels (the rehearsal) lower to plain XLA
        text = step._jitted.lower(state, batch).compile().as_text()
        kernel_in_program = "tpu_custom_call" in text
        check(kernel_in_program, "the compiled train step holds no tpu_custom_call")
    emit(phase="train", platform=dev.platform, device_kind=dev.device_kind,
         devices=len(jax.devices()), layers=size["model"]["num_hidden_layers"],
         batch=size["batch"], seq=size["seq"], attn="flash", fused_ce_chunks=size["ce_chunks"],
         optimizer="lion-sr", compile_s=round(run["compile_s"], 2),
         step_s=run["step_s"], tokens_per_s=size["batch"] * size["seq"] / run["step_s"],
         losses=run["losses"], loss_native_first_step=loss_native,
         loss_flash_vs_native_abs=loss_delta, loss_atol=LOSS_ATOL,
         compiles_after_warmup=run["compiles_after_warmup"],
         tpu_custom_call_in_step=kernel_in_program, peak_bytes=peak_bytes(dev))
    params = state.params
    del state, step, batch, acc
    gc.collect()
    return model, params


def serve_phase(size, model, params, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.generation import GenerationConfig, generate
    from accelerate_tpu.serving import Request, ServingEngine, verify_serving_invariants
    from accelerate_tpu.utils.dataclasses import ServingPlugin

    dev = jax.devices()[0]
    # the chip takes the engine's own "auto" resolution (must come out as
    # the paged Pallas kernels); the rehearsal has to ask for them by name
    plugin = ServingPlugin(**size["serve"], decode_kernel="auto" if on_tpu else "flash")
    gen = GenerationConfig(max_new_tokens=size["new_range"][1])
    engine = ServingEngine(model, params, plugin, gen)
    resolved = engine.model.config.attn_implementation
    check(resolved == "flash", f"decode_kernel resolved to {resolved!r}, not 'flash'")

    t0 = time.perf_counter()
    warm_compiles = engine.warmup()
    compile_s = time.perf_counter() - t0
    compiles_warm = engine.compile_events

    rng = np.random.default_rng(SEED + 1)
    reqs = []
    for uid in range(size["requests"]):
        n_prompt = int(rng.integers(size["prompt_range"][0], size["prompt_range"][1] + 1))
        n_new = int(rng.integers(size["new_range"][0], size["new_range"][1] + 1))
        prompt = tuple(int(t) for t in rng.integers(1, size["model"]["vocab_size"], n_prompt))
        reqs.append(Request(uid=uid, prompt=prompt, max_new_tokens=n_new))
    for r in reqs:
        engine.add_request(r)
    decode_ticks, prefill_ticks = [], []
    t_all = time.perf_counter()
    while not engine.idle():
        before = engine.metrics["decode_steps"]
        t0 = time.perf_counter()
        engine.step()  # blocks on the tick's sampled tokens
        dt = time.perf_counter() - t0
        (decode_ticks if engine.metrics["decode_steps"] > before else prefill_ticks).append(dt)
    wall = time.perf_counter() - t_all
    compiles = engine.compile_events - compiles_warm

    for r in reqs:
        got = engine.results.get(r.uid)
        check(got is not None and len(got) == r.max_new_tokens,
              f"request {r.uid} did not finish: {None if got is None else len(got)}"
              f"/{r.max_new_tokens} tokens")
    violations = verify_serving_invariants(engine)
    check(not violations, f"serving invariants violated: {violations}")
    check(compiles == 0, f"{compiles} compile(s) after warmup()")
    generated = sum(len(engine.results[r.uid]) for r in reqs)

    # reference: generate()'s native path on the same params (dense cache,
    # XLA attention) — greedy tokens compared as a printed share, never
    # demanded equal across two programs
    native = build_model(size, "native")
    width = size["serve"]["prefill_chunk"]
    ids = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        ids[i, :r.prompt_len] = r.prompt
    lens = np.asarray([r.prompt_len for r in reqs], np.int32)
    ref = np.asarray(generate(native, params, ids, gen, prompt_lengths=lens))
    agree = sum(int(a == b) for i, r in enumerate(reqs)
                for a, b in zip(engine.results[r.uid], ref[i, :r.max_new_tokens]))
    first_agree = sum(int(engine.results[r.uid][0] == ref[i, 0]) for i, r in enumerate(reqs))

    # prefill logits of request 0 through the engine's own (warmed) paged
    # prefill program vs the native uncached forward.  Runs on the idle
    # engine AFTER every check above; the engine is discarded afterwards.
    r0 = reqs[0]
    bucket = min(b for b in plugin.prefill_buckets if b >= r0.prompt_len)
    chunk = jnp.asarray(ids[0, :bucket])
    engine.cache, last = engine._run_prefill(
        jnp.asarray(0, jnp.int32), chunk, jnp.asarray(0, jnp.int32),
        jnp.asarray(r0.prompt_len, jnp.int32), jnp.asarray(0, jnp.int32))
    ref_last = jax.jit(lambda p, x: native.apply(p, x)[0])(params, chunk[None])[r0.prompt_len - 1]
    last, ref_last = np.asarray(last, np.float32), np.asarray(ref_last, np.float32)
    scale = float(np.max(np.abs(ref_last)))
    logit_err = float(np.max(np.abs(last - ref_last)))
    check(np.isfinite(last).all() and last.shape == (size["model"]["vocab_size"],),
          f"engine prefill logits not finite [vocab]: shape {last.shape}")
    check(logit_err <= LOGIT_RTOL * scale,
          f"prefill logits engine vs native: max|delta| {logit_err} > "
          f"{LOGIT_RTOL} * {scale}")

    med = lambda xs: float(np.median(xs)) if xs else None
    emit(phase="serve", platform=dev.platform, device_kind=dev.device_kind,
         devices=len(jax.devices()), layers=size["model"]["num_hidden_layers"],
         decode_kernel=resolved, page_size=plugin.page_size, num_slots=plugin.num_slots,
         num_pages=plugin.num_pages, requests=len(reqs), finished=len(engine.results),
         prompt_tokens=int(lens.sum()), generated_tokens=generated,
         compile_s=round(compile_s, 2), warmup_compiles=warm_compiles,
         decode_ticks=len(decode_ticks), decode_tick_s=med(decode_ticks),
         prefill_ticks=len(prefill_ticks), prefill_tick_s=med(prefill_ticks),
         tokens_per_s=generated / wall, invariant_violations=len(violations),
         compiles_after_warmup=compiles,
         prefill_logits_max_abs_err=logit_err, prefill_logits_scale=scale,
         logit_rtol=LOGIT_RTOL, greedy_token_agreement=agree / generated,
         first_token_agreement=first_agree / len(reqs), peak_bytes=peak_bytes(dev))


def leaf_by_path(tree, *needles):
    import jax

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        text = jax.tree_util.keystr(path)
        if all(n in text for n in needles) and getattr(leaf, "ndim", 0) == 2:
            return text, leaf
    raise AssertionError(f"chip_smoke: no 2-D leaf matching {needles}")


def shard_facts(name, leaf):
    shards = leaf.addressable_shards
    devices = sorted({s.device.id for s in shards})
    check(len(devices) == 4, f"{name}: shards on devices {devices}, not four distinct")
    fracs = [s.data.nbytes / leaf.nbytes for s in shards]
    check(all(abs(f - 0.25) < 0.01 for f in fracs),
          f"{name}: shard byte fractions {fracs}, not ~1/4 each")
    return dict(leaf=name, shape=list(leaf.shape), devices=devices, shard_fraction=fracs)


def sharded_phase(size, on_tpu):
    """--chips 4: the train step under FSDP x TP on a 2x2 mesh, and the same
    steps on a one-device mesh to compare with.  Nothing else runs."""
    import jax

    from accelerate_tpu.parallelism_config import ParallelismConfig

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs four devices, jax reports {len(devs)}")
    acc, _, state, step, batch = make_trainer(
        size, ParallelismConfig(dp_shard_size=2, tp_size=2))
    mesh_ids = sorted(d.id for d in acc.mesh.devices.flat)
    check(len(set(mesh_ids)) == 4, f"mesh devices {mesh_ids} are not four distinct")
    check(acc.mesh.shape["dp_shard"] == 2 and acc.mesh.shape["tp"] == 2,
          f"mesh shape {dict(acc.mesh.shape)}")
    facts = [shard_facts(*leaf_by_path(state.params, "q_proj", "kernel")),
             shard_facts(*leaf_by_path(state.opt_state, "q_proj", "kernel"))]
    state, run = run_steps(acc, state, step, batch)
    text = step._jitted.lower(state, batch).compile().as_text()
    collectives = sorted(op for op in ("all-gather", "all-reduce", "reduce-scatter",
                                       "all-to-all", "collective-permute") if op in text)
    check(collectives, "the compiled sharded step holds no collective")
    in_use = None
    if on_tpu:
        check("tpu_custom_call" in text, "the compiled sharded step holds no tpu_custom_call")
        in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
        check(max(in_use) <= 10 * min(in_use),
              f"per-device bytes_in_use not of one order: {in_use}")
    del state, step, batch, acc
    gc.collect()

    reset_singletons()
    acc1, _, state1, step1, batch1 = make_trainer(
        size, ParallelismConfig(dp_shard_size=1, devices=devs[:1]))
    state1, run1 = run_steps(acc1, state1, step1, batch1)
    deltas = [abs(a - b) for a, b in zip(run["losses"], run1["losses"])]
    check(max(deltas) <= SHARDED_LOSS_ATOL,
          f"sharded vs one-device losses {run['losses']} vs {run1['losses']}: "
          f"max |delta| {max(deltas)} > {SHARDED_LOSS_ATOL}")
    emit(phase="sharded_train", platform=devs[0].platform, device_kind=devs[0].device_kind,
         devices=len(devs), mesh={"dp_shard": 2, "tp": 2}, mesh_device_ids=mesh_ids,
         layers=size["model"]["num_hidden_layers"], batch=size["batch"], seq=size["seq"],
         shards=facts, collectives=collectives, bytes_in_use=in_use,
         compile_s=round(run["compile_s"], 2), step_s=run["step_s"],
         tokens_per_s=size["batch"] * size["seq"] / run["step_s"],
         losses=run["losses"], one_device_losses=run1["losses"],
         one_device_step_s=run1["step_s"], loss_max_abs_delta=max(deltas),
         loss_atol=SHARDED_LOSS_ATOL, compiles_after_warmup=run["compiles_after_warmup"],
         peak_bytes=[peak_bytes(d) for d in devs])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step and its one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny shapes, interpret-mode kernels, virtual devices")
    args = ap.parse_args()

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    # the framework's own device probe: under a multi-process launch it joins
    # the distributed runtime first, which a bare jax.devices() would preclude
    from accelerate_tpu.state import PartialState

    dev = PartialState().device
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit(f"chip_smoke: jax found no accelerator (platform {dev.platform!r}); "
                 "the CPU rehearsal runs only with --rehearse")

    from accelerate_tpu import native
    from accelerate_tpu.utils.compile_cache import enable_scoped_compilation_cache

    cache_dir = enable_scoped_compilation_cache("smoke")
    emit(phase="setup", platform=dev.platform, device_kind=dev.device_kind,
         devices=len(jax.devices()), jax=jax.__version__, compile_cache_dir=cache_dir,
         data_path="native-library" if native.is_available() else "python-fallback",
         rehearsal=args.rehearse)

    size = TINY if args.rehearse else REAL
    if args.chips == 4:
        sharded_phase(size, on_tpu)
    else:
        model, params = train_phase(size, on_tpu)
        serve_phase(size, model, params, on_tpu)
    emit(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())})


if __name__ == "__main__":
    main()
