"""Kind ``train``: the prepared train step, one program per step.

Set-up builds ONE object — the compiled step with its state — drives it from
the seed through its first three steps by the window's own call and feed, and
hands that same object to the window.  ``distinct_batches`` seeded batches
live on the device and are cycled, so no loss falls because a batch repeats.
The window keeps one step in flight ahead of the one it waits for, and closes
at the end of the step in flight when ``--seconds`` ran out: the rate is all
the steps over all of that time, never a whole-step count over a fixed
length (which would jump by one step's worth on a hair's difference)."""

from __future__ import annotations

import time

import numpy as np

from perfbench import window as W
from perfbench.traffic import train_batches

CHECKED_STEPS = 3      # the program's first steps, followed by the reference
IN_FLIGHT = 2          # steps dispatched ahead of the host's wait


def leaf_norms(tree: dict) -> dict:
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                           for k, v in t.items()})
    return {k: float(v) for k, v in f(tree).items()}


def change_norms(params: dict, shapes: dict, seed: int) -> dict:
    """Per leaf |p_now - p_seeded|, the seeded leaf made again inside the
    program (never a second copy of the weights on the device)."""
    import jax
    import jax.numpy as jnp

    from perfbench.weights import leaf_again, seed_key

    def f(t, key):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32) - leaf_again(shapes, k, key).astype(jnp.float32))))
            for k, v in t.items()}

    return {k: float(v) for k, v in jax.jit(f)(params, seed_key(seed)).items()}


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """max over leaves of |prog - ref| over the larger of the reference's norm
    of that leaf and of the median leaf (some gradients are all but zero)."""
    floor = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref)


def run_reference(ctx, spec, batches, seed, steps=CHECKED_STEPS, quant=None):
    """The plain reference through the same first steps.  Returns per-step
    losses, first-step gradient norms and per-leaf change after the steps."""
    from perfbench.weights import make_weights

    fam, hy = ctx.family, spec["optimizer_hyper"]
    weights = make_weights(fam.weight_shapes(ctx.cfg, ctx.layers), seed)
    ref = ctx.reference.TrainReference(weights, ctx.cfg, ctx.layers, hy["lr"], hy["b1"],
                                       hy["b2"], steps, quant=quant)
    del weights
    losses, grad_norms = [], None
    for i in range(steps):
        loss, norms = ref.step(batches[i % len(batches)])
        losses.append(loss)
        grad_norms = grad_norms or norms
    return losses, grad_norms, ref.change_norms()


def compare(program: dict, reference: tuple, limits: dict) -> list:
    ref_losses, ref_grads, ref_change = reference
    checks = [(f"loss_gap_step{i + 1}", abs(program["losses"][i] - ref_losses[i]),
               limits["loss_gap"]) for i in range(len(ref_losses))]
    checks.append(("grad_norm_worst_leaf_gap",
                   worst_leaf_gap(program["grad_norms"], ref_grads), limits["grad_norm_worst_leaf_gap"]))
    checks.append(("param_change_worst_leaf_gap",
                   worst_leaf_gap(program["change_norms"], ref_change), limits["param_change_worst_leaf_gap"]))
    return checks


def first_steps(ctx, spec, state, step, feed, seed=None):
    """The program's first CHECKED_STEPS steps and what the comparison reads
    from them: each loss, the first gradient as the optimizer got it (Lion's
    momentum after one step is (1 - b2) * g), the parameters' change."""
    fam, hy = ctx.family, spec["optimizer_hyper"]
    shapes = fam.weight_shapes(ctx.cfg, ctx.layers)
    out = {"losses": []}
    for i in range(CHECKED_STEPS):
        state, metrics = step(state, feed(i))
        out["losses"].append(float(metrics["loss"]))
        if i == 0:
            ctx.mark("first_step")
            out["grad_norms"] = {k: v / (1.0 - hy["b2"])
                                 for k, v in leaf_norms(fam.momentum_of(state)).items()}
        if i == spec["reference_steps"] - 1:   # where the reference stops
            out["change_norms"] = change_norms(fam.params_of(state), shapes,
                                               ctx.seed if seed is None else seed)
    return state, out


def make_feed(ctx, spec, acc, seed):
    """The seeded batches, resident on the device, and the feed that cycles them."""
    import jax

    tokens = train_batches(seed, spec["distinct_batches"], spec["batch"], spec["seq"],
                           ctx.cfg["vocab_size"])
    sharding = ctx.family.batch_sharding(acc, tokens[0])
    resident = [jax.device_put(t, sharding) for t in tokens]
    feed = lambda i: {"input_ids": resident[i % len(resident)],
                      "labels": resident[i % len(resident)]}
    return tokens, resident, feed


def run(ctx):
    import jax

    spec = ctx.sized(ctx.traffic)
    fam, seconds = ctx.family, ctx.seconds
    t0 = time.perf_counter()
    acc, step, new_state = fam.build_trainer(ctx.cfg, ctx.layers, spec)
    state = new_state(ctx.seed)
    jax.block_until_ready(state.params)
    ctx.mark("weights_and_state")
    tokens, resident, feed = make_feed(ctx, spec, acc, ctx.seed)
    state, program = first_steps(ctx, spec, state, step, feed)
    for i in range(CHECKED_STEPS, CHECKED_STEPS + 2):    # settle before the window
        state, metrics = step(state, feed(i))
    jax.block_until_ready(metrics["loss"])
    ctx.record["warmup_compile_s"] = time.perf_counter() - t0
    ctx.mark("warmup")
    compiles_before = acc.compile_events

    ctx.open_window()
    clock, t_open = time.perf_counter, ctx.t_open
    ends, losses, flying, i = [], [], [], CHECKED_STEPS + 2
    tracing, spans = False, []
    while True:
        if ctx.trace and not tracing and clock() - t_open >= seconds - spec["trace_seconds"]:
            ctx.tracer.start()
            tracing = True
        t0 = clock()
        state, metrics = step(state, feed(i))
        flying.append(metrics["loss"])
        i += 1
        t1 = clock()
        if len(flying) >= IN_FLIGHT:
            losses.append(jax.block_until_ready(flying.pop(0)))
            ends.append(clock() - t_open)
            if tracing:
                spans += [("dispatch", t0, t1), ("wait", t1, ends[-1] + t_open)]
            if ends[-1] >= seconds:
                break
    for loss in flying:
        losses.append(jax.block_until_ready(loss))
        ends.append(clock() - t_open)
    if tracing:
        ctx.tracer.stop()
    compiles = acc.compile_events - compiles_before
    ctx.read_memory_peak(at_least=fam.step_memory_bytes(step, state, feed(0)))
    tokens_per_step = spec["batch"] * spec["seq"]
    e2e = {"train_tokens_per_s": W.steps_tokens_per_s(ends, tokens_per_step)}
    host_losses = [float(x) for x in losses]
    par = spec["parallelism"]
    dp, tp = par.get("dp_shard_size", 1), par.get("tp_size", 1)
    ctx.record.update(
        step_ends=ends, tokens_per_step=tokens_per_step, chips=len(jax.devices()), spans=spans,
        seq=spec["seq"], flash_shard={"batch": spec["batch"] // dp,
                                      "heads": ctx.cfg["num_attention_heads"] // tp,
                                      "kv_heads": ctx.cfg["num_key_value_heads"] // tp})
    ctx.say(phase="window", steps=len(ends), window_s=ends[-1], first_losses=program["losses"],
            last_loss=host_losses[-1])

    del state, step, new_state, acc, resident, feed, metrics, flying, losses
    checks = [("compiles_in_window", compiles, 0),
              ("nonfinite_losses", sum(not np.isfinite(x) for x in host_losses), 0)]
    t0 = time.perf_counter()
    reference = run_reference(ctx, spec, tokens, ctx.seed, steps=spec["reference_steps"])
    ctx.say(phase="reference", steps=spec["reference_steps"], seconds=time.perf_counter() - t0,
            losses=reference[0])
    checks += compare(program, reference, ctx.limits)
    return dict(attempted=len(ends), failed=0, end_to_end=e2e, checks=checks)
