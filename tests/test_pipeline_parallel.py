"""Pipeline parallelism (SURVEY §2.4 P7): GPipe schedule over the pp axis.

Parity model: reference prepare_pippy (inference.py:126) microbatch forward,
plus training-PP capability (reference reaches it only via Megatron).
Numerical ground truth: the plain (non-pipelined) model forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import ParallelismConfig
from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.models.llama import causal_lm_loss
from accelerate_tpu.parallel.pipeline_parallel import (
    PipelinedModel,
    pipeline_blocks,
    prepare_pipeline,
    stack_layer_params,
    unstack_layer_params,
)


def _tiny_model(num_layers=4, attn="native"):
    cfg = LlamaConfig.tiny(num_hidden_layers=num_layers, attn_implementation=attn)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
    params = model.init(jax.random.key(0), ids[:, :8])
    return cfg, model, params, ids


def _mesh(pp=4, **kw):
    return ParallelismConfig(pp_size=pp, **kw).build_device_mesh(jax.devices())


def test_stack_unstack_roundtrip():
    cfg, model, params, _ = _tiny_model()
    stacked, rest = stack_layer_params(dict(params["params"]), cfg.num_hidden_layers)
    for leaf in jax.tree.leaves(stacked):
        assert leaf.shape[0] == cfg.num_hidden_layers
    rebuilt = unstack_layer_params(stacked, rest)
    orig, new = jax.tree.leaves(params["params"]), jax.tree.leaves(rebuilt)
    assert all(np.allclose(a, b) for a, b in zip(orig, new))


@pytest.mark.parametrize("num_microbatches", [2, 4, 8])
def test_pipeline_matches_plain_forward(num_microbatches):
    cfg, model, params, ids = _tiny_model(num_layers=4)
    mesh = _mesh(pp=4, dp_shard_size=2)
    expected = model.apply(params, ids)
    pmodel = prepare_pipeline(model, params, mesh, num_microbatches=num_microbatches)
    got = pmodel(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-2, rtol=2e-2)


@pytest.mark.slow
def test_pipeline_two_stages_with_tp():
    cfg, model, params, ids = _tiny_model(num_layers=4)
    mesh = _mesh(pp=2, tp_size=2, dp_shard_size=2)
    expected = model.apply(params, ids)
    pmodel = prepare_pipeline(model, params, mesh, num_microbatches=4)
    got = pmodel(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-2, rtol=2e-2)


def test_pipeline_blocks_differentiable():
    """grad through the GPipe schedule == grad through the plain layer stack."""
    cfg, model, params, ids = _tiny_model(num_layers=4)
    mesh = _mesh(pp=4, dp_shard_size=2)
    stacked, rest = stack_layer_params(dict(params["params"]), cfg.num_hidden_layers)
    block = LlamaForCausalLM.block_cls(cfg)
    b, t = 4, 16
    positions = jnp.broadcast_to(jnp.arange(t), (b // 2, t))
    x = jax.random.normal(jax.random.key(1), (b, t, cfg.hidden_size), cfg.dtype)

    def block_fn(lp, h):
        return block.apply({"params": lp}, h, positions)

    def piped_loss(stacked):
        out = pipeline_blocks(stacked, x, block_fn, mesh, num_microbatches=2)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    def plain_loss(stacked):
        h = x
        for i in range(cfg.num_hidden_layers):
            lp = jax.tree.map(lambda a, i=i: a[i], stacked)
            mbs = jnp.split(h, 2, axis=0)
            h = jnp.concatenate(
                [block.apply({"params": lp}, mb, positions) for mb in mbs], axis=0
            )
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    g_pipe = jax.jit(jax.grad(piped_loss))(stacked)
    g_plain = jax.jit(jax.grad(plain_loss))(stacked)
    for a, b_ in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=3e-2, rtol=3e-2)


@pytest.mark.slow
def test_pipeline_training_step_improves_loss():
    """End-to-end pipelined TRAINING: loss decreases over a few adamw steps."""
    cfg, model, params, ids = _tiny_model(num_layers=2)
    mesh = _mesh(pp=2, dp_shard_size=4)
    pmodel = PipelinedModel(model, params, mesh, num_microbatches=2)
    labels = ids

    tx = optax.adamw(1e-2)
    opt_state = tx.init((pmodel.stacked, pmodel.rest))

    @jax.jit
    def step(stacked, rest, opt_state):
        def loss_fn(stacked, rest):
            logits = pmodel._forward(stacked, rest, ids)
            return causal_lm_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(stacked, rest)
        updates, opt_state = tx.update(grads, opt_state, (stacked, rest))
        stacked, rest = optax.apply_updates((stacked, rest), updates)
        return stacked, rest, opt_state, loss

    stacked, rest = pmodel.stacked, pmodel.rest
    losses = []
    for _ in range(5):
        stacked, rest, opt_state, loss = step(stacked, rest, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_pipeline_validates_divisibility():
    cfg, model, params, ids = _tiny_model(num_layers=4)
    mesh = _mesh(pp=4, dp_shard_size=2)
    pmodel = prepare_pipeline(model, params, mesh, num_microbatches=3)
    with pytest.raises(ValueError, match="divisible"):
        pmodel(ids)  # batch 8 % 3 != 0


def test_parallelism_config_pp_axis():
    cfg = ParallelismConfig(pp_size=2, dp_shard_size=-1, tp_size=2)
    mesh = cfg.build_device_mesh(jax.devices())
    assert cfg.dp_shard_size == 2
    assert mesh.shape["pp"] == 2
    assert cfg.non_data_parallel_size == 4  # tp * pp
    env = cfg.to_env()
    assert env["PARALLELISM_CONFIG_PP_SIZE"] == "2"


def test_parallelism_config_pp_env_roundtrip(monkeypatch):
    for k, v in ParallelismConfig(pp_size=4, dp_shard_size=2).to_env().items():
        monkeypatch.setenv(k, v)
    restored = ParallelismConfig.from_env()
    assert restored.pp_size == 4 and restored.dp_shard_size == 2
