"""The activation layout the training path states
(``parallel/sharding.constrain_activation`` through ``models/llama._rows``):
rows over the batch axes, sequence over ``cp``/``sp``, MLP width and heads
over ``tp``.  Held here: the spec it builds on every mesh the suite runs, the
numbers of a tiny Llama with and without the pins, the programs that must not
change (one device, ``tp`` alone, every ``cache is not None`` serving
program).  Whether the chip's partitioner then moves weights and not
activations is asked at the four-chip cell's widths in
``tests/test_tpu_compile.py`` (at tiny shapes the CPU's chooses rows unaided)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.models import llama
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
from accelerate_tpu.parallel import sharding
from accelerate_tpu.parallel.sharding import constrain_activation, shard_params

B, T = 4, 16
# mesh -> (axis sizes, attention, what a [B, T, hidden] activation is pinned to)
MESHES = {
    "dp2_tp2": (dict(dp_shard_size=2, tp_size=2), "native", P(("dp_shard",), None, None)),
    "dp4": (dict(dp_shard_size=4), "native", P(("dp_shard",), None, None)),
    "hsdp2x2": (dict(dp_replicate_size=2, dp_shard_size=2), "native",
                P(("dp_replicate", "dp_shard"), None, None)),
    "dp2_cp2": (dict(dp_shard_size=2, cp_size=2), "ring", P(("dp_shard",), ("cp",), None)),
    "dp2_sp2": (dict(dp_shard_size=2, sp_size=2), "ulysses", P(("dp_shard",), ("sp",), None)),
    "tp2": (dict(tp_size=2), "native", None),
    "one_device": (dict(), "native", None),
}


def _accelerator(par):
    n = int(np.prod(list(par.values()) or [1]))
    return Accelerator(parallelism_config=ParallelismConfig(**par, devices=jax.devices()[:n]))


def _unpinned(monkeypatch):
    monkeypatch.setattr(llama, "_rows", lambda x, tp_dim=None: x)


def _constraints(fn, *args):
    """The PartitionSpecs of every sharding constraint in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sharding_constraint":
                found.append(eqn.params["sharding"].spec)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("mesh", list(MESHES))
def test_spec_follows_the_free_axes(mesh):
    """The helper names the batch and sequence axes that are wider than one
    device, ``tp`` on the dim it is told where ``tp`` divides it, and nothing
    when no batch axis is free or an axis does not divide its dim."""
    par, _, want = MESHES[mesh]
    _accelerator(par)
    x = jnp.zeros((B, T, 8))
    got = _constraints(lambda x: constrain_activation(x), x)
    assert got == ([want] if want is not None else [])
    if want is None:
        return
    tp = "tp" if par.get("tp_size", 1) > 1 else None
    assert _constraints(lambda x: constrain_activation(x, tp_dim=-1), x) == [P(*want[:2], tp)]
    heads = jnp.zeros((B, T, 2, 8))
    assert _constraints(lambda x: constrain_activation(x, tp_dim=2), heads) == [
        P(*want[:2], tp, None)]
    assert _constraints(lambda x: constrain_activation(x, tp_dim=-1), jnp.zeros((B, T, 7))) == [want]
    assert _constraints(lambda x: constrain_activation(x), jnp.zeros((3, T, 8))) == []
    assert _constraints(lambda x: constrain_activation(x), jnp.zeros((B, 15, 8))) == (
        [want] if want[1] is None else [])


def test_no_mesh_and_manual_regions_are_left_alone():
    """No Accelerator: ``x`` itself.  Inside a region already manual over the
    batch axis the helper names only what is still free: nothing there."""
    x = jnp.zeros((B, T, 8))
    assert constrain_activation(x) is x
    acc = _accelerator(dict(dp_shard_size=2, tp_size=2))
    inside = jax.shard_map(lambda x: constrain_activation(x, tp_dim=-1), mesh=acc.mesh,
                           in_specs=P("dp_shard"), out_specs=P("dp_shard"),
                           axis_names={"dp_shard"}, check_vma=False)
    assert _constraints(inside, x) == []
    staged = jax.shard_map(lambda x: constrain_activation(x, tp_dim=-1), mesh=acc.mesh,
                           in_specs=P(), out_specs=P(), axis_names={"pp"}, check_vma=False)
    assert _constraints(staged, x) == [P(("dp_shard",), None, "tp")]


def _tiny(attn, **kw):
    return LlamaForCausalLM(LlamaConfig.tiny(attn_implementation=attn, dtype=jnp.float32, **kw))


def _placed(acc, model):
    """Seeded parameters on the plan and a batch on the batch spec."""
    ids = jax.random.randint(jax.random.key(1), (B, T), 0, model.config.vocab_size)
    params = model.init(jax.random.key(0), ids)
    params = shard_params(params, acc._params_plan(params))
    put = lambda a: jax.device_put(a, NamedSharding(acc.mesh, acc._default_batch_spec()(a)))
    labels = jnp.concatenate([ids[:, 1:], jnp.full((B, 1), -100)], axis=1)
    return params, {"input_ids": put(ids), "shift_labels": put(labels)}


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("mesh", list(MESHES))
def test_loss_and_gradients_equal_the_unpinned_program(mesh, scan, monkeypatch):
    """A tiny float32 Llama under each mesh: loss and every gradient leaf of
    the pinned program against the same program with ``_rows`` taken out."""
    par, attn, want = MESHES[mesh]
    acc = _accelerator(par)
    model = _tiny(attn, scan_layers=scan)
    params, batch = _placed(acc, model)
    step = lambda: jax.jit(jax.value_and_grad(make_llama_loss_fn(model)))
    pins = _constraints(jax.value_and_grad(make_llama_loss_fn(model)), params, batch)
    assert bool(pins) == (want is not None)      # engaged exactly where a batch axis is free
    loss, grads = step()(params, batch)
    _unpinned(monkeypatch)
    assert _constraints(jax.value_and_grad(make_llama_loss_fn(model)), params, batch) == []
    loss0, grads0 = step()(params, batch)
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_allclose(g, g0, rtol=2e-5, atol=1e-6, err_msg=str(path))


def _train_step_text(par, fused):
    acc = _accelerator(par)
    model = _tiny("native")
    params, batch = _placed(acc, model)
    state = acc.create_train_state(params, "lion", apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model, fused_vocab_chunks=fused))
    return step._jitted.lower(state, batch).as_text()


@pytest.mark.parametrize("fused", [None, 2], ids=["logits", "fused_ce"])
@pytest.mark.parametrize("mesh", ["one_device", "tp2"])
def test_train_step_is_the_same_program_where_no_batch_axis_is_free(mesh, fused, monkeypatch):
    """One device, and ``tp`` alone: the lowered StableHLO of the whole train
    step with the call sites in place is, byte for byte, the one without."""
    from accelerate_tpu.state import AcceleratorState, GradientState

    with_pins = _train_step_text(MESHES[mesh][0], fused)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    _unpinned(monkeypatch)
    assert with_pins == _train_step_text(MESHES[mesh][0], fused)


@pytest.mark.parametrize("program", ["decode", "prefill", "verify", "dense_cache"])
def test_serving_programs_never_reach_the_helper(program, monkeypatch):
    """``cache is not None`` bypasses every pin: under a live dp_shard 2 x
    tp 2 mesh (where the training path's pins bind) the engine's programs and
    the dense-cache decode lower without one call of the helper, to the text
    they lower to with ``_rows`` taken out."""
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.serving.engine import fresh_engine_jits

    _accelerator(dict(dp_shard_size=2, tp_size=2))
    slots, page, pages, per_slot = 4, 8, 16, 4
    model = _tiny("native")
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)

    def lowered():
        if program == "dense_cache":
            cache = llama.init_cache(model.config, slots, 32)
            return jax.jit(lambda p, c, ids: model.apply(p, ids, cache=c)).lower(
                params, cache, i32(slots, 1)).as_text()
        cache = model.init_paged_cache(pages, page, slots, per_slot)
        gen = GenerationConfig(max_new_tokens=8, do_sample=False, eos_token_id=None)
        decode, prefill, _, _, verify = fresh_engine_jits(model, gen, page)
        rng, active = jnp.zeros((2,), jnp.uint32), jnp.ones((slots,), bool)
        if program == "decode":
            return decode.lower(params, cache, i32(slots), active, rng).as_text()
        if program == "prefill":
            return prefill.lower(params, cache, i32(), i32(16), i32(), i32()).as_text()
        return verify.lower(params, cache, i32(slots, 3), i32(slots), active, rng).as_text()

    def refuse(x, tp_dim=None):
        raise AssertionError("a serving program reached constrain_activation")

    monkeypatch.setattr(sharding, "constrain_activation", refuse)
    with_sites = lowered()
    _unpinned(monkeypatch)
    assert with_sites == lowered()
