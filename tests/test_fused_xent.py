"""The fused linear + cross-entropy (``ops/fused_xent.py``) under a mesh: the
vocabulary-parallel path against the single-device function and against
``causal_lm_loss`` on full logits, the fall-backs that must stay the bare
call, and the compiled train step of the four-chip benchmark cell at its
rehearsal shapes — no logits-sized array among the collectives of the loss."""

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.models.llama import causal_lm_loss
from accelerate_tpu.ops.fused_xent import fused_causal_lm_loss

REPO = Path(__file__).resolve().parents[1]
B, T, H, CHUNKS = 4, 12, 32, 4
MESHES = {"tp2": dict(tp_size=2), "dp2_tp2": dict(dp_shard_size=2, tp_size=2),
          "dp2_tp4": dict(dp_shard_size=2, tp_size=4)}


def _accelerator(par):
    n = int(np.prod(list(par.values())))
    return Accelerator(parallelism_config=ParallelismConfig(**par, devices=jax.devices()[:n]))


def _labels(scenario, vocab, tp, key):
    lab = jax.random.randint(key, (B, T), 0, vocab)
    if scenario == "ignore_index":   # whole rows and scattered positions carry no loss
        lab = lab.at[1].set(-100).at[:, 3::4].set(-100)
    if scenario == "shard_edges":    # first and last column of every shard's slice
        edges = np.array([[s * (vocab // tp), (s + 1) * (vocab // tp) - 1] for s in range(tp)])
        lab = jnp.asarray(np.resize(edges.reshape(-1), (B, T)), jnp.int32)
    return lab


def _loss_and_grads(loss, hidden, weight):
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(hidden, weight)


@pytest.mark.parametrize("scenario", ["padded_slice", "ignore_index", "shard_edges", "shifted"])
@pytest.mark.parametrize("vocab_major", [False, True], ids=["head_HV", "tied_VH"])
@pytest.mark.parametrize("mesh", list(MESHES))
def test_vocab_parallel_matches_single_device_and_full_logits(mesh, vocab_major, scenario):
    tp = MESHES[mesh]["tp_size"]
    # padded_slice: 18 or 9 columns a shard, which 4 chunks do not divide
    vocab = 36 if scenario == "padded_slice" else 64
    k = jax.random.split(jax.random.key(7), 3)
    hidden = jax.random.normal(k[0], (B, T, H), jnp.float32)
    weight = 0.3 * jax.random.normal(k[1], (vocab, H) if vocab_major else (H, vocab), jnp.float32)
    labels = _labels(scenario, vocab, tp, k[2])
    shifted = scenario == "shifted"

    def fused(h, w):
        return fused_causal_lm_loss(h, w, labels, vocab_major=vocab_major, num_chunks=CHUNKS,
                                    shifted=shifted)

    def full(h, w):
        logits = jnp.einsum("bth,vh->btv" if vocab_major else "bth,hv->btv", h, w)
        return causal_lm_loss(logits, labels, shifted=shifted)

    single = _loss_and_grads(fused, hidden, weight)        # no Accelerator yet: the bare call
    assert "shard_map" not in str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden, weight))
    reference = _loss_and_grads(full, hidden, weight)

    acc = _accelerator(MESHES[mesh])
    dp = "dp_shard" if "dp_shard_size" in MESHES[mesh] else None
    w_spec = P("tp", dp) if vocab_major else P(dp, "tp")
    hidden_s = jax.device_put(hidden, NamedSharding(acc.mesh, P(dp)))
    weight_s = jax.device_put(weight, NamedSharding(acc.mesh, w_spec))
    jaxpr = str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden_s, weight_s))
    assert jaxpr.count("shard_map") == 2    # forward and backward
    sharded = _loss_and_grads(fused, hidden_s, weight_s)

    for other in (single, reference):
        np.testing.assert_allclose(sharded[0], other[0], rtol=1e-5, atol=1e-6)
        for got, want in zip(sharded[1], other[1]):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert sharded[1][1].sharding.spec == (P("tp") if vocab_major else P(None, "tp"))


@pytest.mark.parametrize("par,vocab", [
    pytest.param(dict(dp_shard_size=1), 64, id="one_device"),
    pytest.param(dict(dp_shard_size=4), 64, id="fsdp_only"),
    pytest.param(dict(dp_shard_size=2, tp_size=4), 66, id="vocab_tp_does_not_divide"),
])
def test_fallbacks_trace_no_shard_map(par, vocab):
    """Where no tp axis can take a slice of the vocabulary the function of
    before runs: the jaxpr holds no shard_map, and the loss is the reference's."""
    acc = _accelerator(par)
    k = jax.random.split(jax.random.key(3), 3)
    hidden = jax.random.normal(k[0], (B, T, H), jnp.float32)
    weight = 0.3 * jax.random.normal(k[1], (H, vocab), jnp.float32)
    labels = jax.random.randint(k[2], (B, T), 0, vocab)
    fused = lambda h, w: fused_causal_lm_loss(h, w, labels, vocab_major=False, num_chunks=CHUNKS)
    assert "shard_map" not in str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden, weight))
    rows = "dp_shard" if acc.mesh.shape["dp_shard"] > 1 else None
    loss = jax.jit(fused)(jax.device_put(hidden, NamedSharding(acc.mesh, P(rows))), weight)
    np.testing.assert_allclose(
        loss, causal_lm_loss(jnp.einsum("bth,hv->btv", hidden, weight), labels), rtol=1e-5)


_COLLECTIVE = re.compile(
    r"= \(?(\w+)\[([0-9,]*)\][^ ]* (all-reduce|all-gather|reduce-scatter)(-start)?\(")


def test_train_step_hlo_moves_no_logits_sized_array_for_the_loss():
    """The four-chip benchmark cell's step (dp_shard 2 x tp 2) at the traffic
    file's rehearsal shapes, compiled: under the ``fused_xent`` scope no
    all-reduce, all-gather or reduce-scatter is as large as a chunk of the
    logits (rows of a dp group x columns of a chunk), except the one [N, H]
    all-reduce of ``dh``; and the ops carry ``fused_xent/.../vocab_shard``."""
    sys.path.insert(0, str(REPO))
    from perfbench.families import llama as family

    traffic = json.loads((REPO / "perfbench/traffic/train_fsdp2_tp2.json").read_text())
    small = traffic["rehearse"]
    cfg = {**json.loads((REPO / "perfbench/configs/yi-1.5-34b.json").read_text()), **small["config"]}
    recipe = {**traffic, "batch": small["batch"], "seq": small["seq"],
              "parallelism": {**traffic["parallelism"], "devices": jax.devices()[:4]}}
    acc, step, new_state = family.build_trainer(cfg, small["layers"], recipe)
    tokens = jnp.zeros((recipe["batch"], recipe["seq"]), jnp.int32)
    tokens = jax.device_put(tokens, family.batch_sharding(acc, tokens))
    hlo = step._jitted.lower(new_state(0), {"input_ids": tokens, "labels": tokens}).compile().as_text()

    par = traffic["parallelism"]
    rows = recipe["batch"] // par["dp_shard_size"] * (recipe["seq"] - 1)      # N of a dp group
    chunk = -(-(cfg["vocab_size"] // par["tp_size"]) // traffic["ce_chunks"])
    scoped = [line for line in hlo.splitlines() if "fused_xent" in line]
    assert any("vocab_shard" in line for line in scoped)
    large = []
    for line in scoped:
        m = _COLLECTIVE.search(line)
        if m and int(np.prod([int(d) for d in m.group(2).split(",") if d])) >= rows * chunk:
            large.append((m.group(3), m.group(2)))
    assert large == [("all-reduce", f"{rows},{cfg['hidden_size']}")], large
