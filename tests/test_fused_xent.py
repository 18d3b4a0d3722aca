"""The fused linear + cross-entropy (``ops/fused_xent.py``) under a mesh: the
vocabulary-parallel path against the single-device function and against
``causal_lm_loss`` on full logits, the fall-backs that must stay the bare
call, the one loop that builds a chunk's logits once (and no gradient where
none is asked), and the compiled train step of the four-chip benchmark cell at
its rehearsal shapes — no logits-sized array among the collectives of the
loss, the head gathered once and ``dw`` reduced once."""

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


def _labels(scenario, vocab, tp, key, t=T):
    lab = jax.random.randint(key, (B, t), 0, vocab)
    if scenario == "ignore_index":   # whole rows and scattered positions carry no loss
        lab = lab.at[1].set(-100).at[:, 3::4].set(-100)
    if scenario == "shard_edges":    # first and last column of every shard's slice
        edges = np.array([[s * (vocab // tp), (s + 1) * (vocab // tp) - 1] for s in range(tp)])
        lab = jnp.asarray(np.resize(edges.reshape(-1), (B, t)), jnp.int32)
    return lab


def _loss_and_grads(loss, hidden, weight):
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(hidden, weight)


@pytest.mark.parametrize("scenario", ["padded_slice", "ignore_index", "shard_edges", "shifted",
                                      "tail_chunk", "cotangent"])
@pytest.mark.parametrize("vocab_major", [False, True], ids=["head_HV", "tied_VH"])
@pytest.mark.parametrize("mesh", list(MESHES))
def test_vocab_parallel_matches_single_device_and_full_logits(mesh, vocab_major, scenario):
    tp = MESHES[mesh]["tp_size"]
    # padded_slice: 18 or 9 columns a shard, no multiple of a tile or of the chunk count
    vocab = 36 if scenario == "padded_slice" else 64
    # tail_chunk: 4 chunks of 4 rows for 14 positions, the last chunk half masked padding
    t = 14 if scenario == "tail_chunk" else T
    # cotangent: neither 1 nor a power of two, so a gradient scaled after its cast would show
    scale = 0.3 if scenario == "cotangent" else 1.0
    k = jax.random.split(jax.random.key(7), 3)
    hidden = jax.random.normal(k[0], (B, t, H), jnp.float32)
    weight = 0.3 * jax.random.normal(k[1], (vocab, H) if vocab_major else (H, vocab), jnp.float32)
    labels = _labels(scenario, vocab, tp, k[2], t)
    shifted = scenario == "shifted"

    def fused(h, w):
        return scale * fused_causal_lm_loss(h, w, labels, vocab_major=vocab_major,
                                            num_chunks=CHUNKS, shifted=shifted)

    def full(h, w):
        logits = jnp.einsum("bth,vh->btv" if vocab_major else "bth,hv->btv", h, w)
        return scale * causal_lm_loss(logits, labels, shifted=shifted)

    single = _loss_and_grads(fused, hidden, weight)        # no Accelerator yet: the bare call
    assert "shard_map" not in str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden, weight))
    reference = _loss_and_grads(full, hidden, weight)

    acc = _accelerator(MESHES[mesh])
    dp = "dp_shard" if "dp_shard_size" in MESHES[mesh] else None
    w_spec = P("tp", dp) if vocab_major else P(dp, "tp")
    hidden_s = jax.device_put(hidden, NamedSharding(acc.mesh, P(dp)))
    weight_s = jax.device_put(weight, NamedSharding(acc.mesh, w_spec))
    jaxpr = str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden_s, weight_s))
    assert jaxpr.count("shard_map") == 1    # the one loop, in the forward rule
    sharded = _loss_and_grads(fused, hidden_s, weight_s)

    for other in (single, reference):
        np.testing.assert_allclose(sharded[0], other[0], rtol=1e-5, atol=1e-6)
        for got, want in zip(sharded[1], other[1]):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert sharded[1][1].sharding.spec == (P("tp") if vocab_major else P(None, "tp"))


@pytest.mark.parametrize("par,vocab", [
    pytest.param(dict(dp_shard_size=1), 64, id="one_device"),
    pytest.param(dict(dp_shard_size=4), 64, id="fsdp_only"),
    pytest.param(dict(dp_shard_size=2, cp_size=2), 64, id="fsdp_x_cp"),
    pytest.param(dict(dp_shard_size=2, tp_size=4), 66, id="vocab_tp_does_not_divide"),
])
def test_fallbacks_trace_no_shard_map(par, vocab):
    """Where no tp axis can take a slice of the vocabulary the bare loop runs:
    the jaxpr holds no shard_map, and loss and gradients are the reference's —
    rows split over ``dp_shard``, the sequence over ``cp`` (a chunk is a piece
    of what each device holds), the head's hidden dim over FSDP."""
    acc = _accelerator(par)
    k = jax.random.split(jax.random.key(3), 3)
    hidden = jax.random.normal(k[0], (B, T, H), jnp.float32)
    weight = 0.3 * jax.random.normal(k[1], (H, vocab), jnp.float32)
    labels = jax.random.randint(k[2], (B, T), 0, vocab)
    shifted = "cp_size" in par      # the context-parallel contract
    fused = lambda h, w: fused_causal_lm_loss(h, w, labels, vocab_major=False, num_chunks=CHUNKS,
                                              shifted=shifted)
    full = lambda h, w: causal_lm_loss(jnp.einsum("bth,hv->btv", h, w), labels, shifted=shifted)
    assert "shard_map" not in str(jax.make_jaxpr(jax.grad(fused, argnums=(0, 1)))(hidden, weight))
    rows = "dp_shard" if acc.mesh.shape["dp_shard"] > 1 else None
    hidden_s = jax.device_put(hidden, NamedSharding(acc.mesh, P(rows, "cp" if shifted else None)))
    weight_s = jax.device_put(weight, NamedSharding(acc.mesh, P(rows)))
    np.testing.assert_allclose(jax.jit(fused)(hidden_s, weight), full(hidden, weight), rtol=1e-5)
    got, want = _loss_and_grads(fused, hidden_s, weight_s), _loss_and_grads(full, hidden, weight)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, with the
    primitives that enclose it (``("jit", "scan")``: inside the loop)."""
    for eqn in jaxpr.eqns:
        yield (), eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for path, inner in _eqns(sub):
                yield (eqn.primitive.name,) + path, inner


def _traced(fn, *args, primitive):
    """(enclosing primitives, equation) of every ``primitive`` that ``fn`` traces."""
    return [(path, eqn) for path, eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == primitive]


def _case(par):
    if par:
        _accelerator(par)
    k = jax.random.split(jax.random.key(11), 3)
    hidden = jax.random.normal(k[0], (B, T, H), jnp.float32)
    weight = 0.3 * jax.random.normal(k[1], (H, 64), jnp.float32)
    labels = jax.random.randint(k[2], (B, T), 0, 64)
    fused = lambda h, w: fused_causal_lm_loss(h, w, labels, vocab_major=False, num_chunks=CHUNKS)
    return fused, hidden, weight, labels


REGIONS = [pytest.param({}, id="bare"), pytest.param(dict(tp_size=2), id="vocab_shard")]


@pytest.mark.parametrize("par", REGIONS)
def test_gradient_is_one_loop_of_three_matmuls(par):
    """``jax.grad`` of the loss traces ONE loop, and in it a chunk's logits
    once, its ``dh`` rows and its term of ``dw``; no matmul outside the loop,
    so nothing rebuilds the logits for the backward pass."""
    fused, hidden, weight, _ = _case(par)
    tp = par.get("tp_size", 1)
    grad = jax.grad(fused, argnums=(0, 1))
    dots = _traced(grad, hidden, weight, primitive="dot_general")
    assert all(path.count("scan") == 1 for path, _ in dots), dots
    rows = (1, B, 1, T // CHUNKS)   # [batch groups, B/g, sequence groups, a chunk of T/g]
    assert sorted(eqn.outvars[0].aval.shape for _, eqn in dots) == sorted(
        [rows + (64 // tp,), rows + (H,), (1, 1, H, 64 // tp)])
    loops = _traced(grad, hidden, weight, primitive="scan")
    assert len(loops) == 1 and loops[0][1].params["length"] == CHUNKS
    assert not _traced(grad, hidden, weight, primitive="while")


@pytest.mark.parametrize("par", REGIONS)
def test_value_only_call_computes_no_gradient(par):
    """A call that is not differentiated runs the loop for the loss alone:
    the logits matmul and nothing shaped like ``dh`` or ``dw``."""
    fused, hidden, weight, labels = _case(par)
    dots = _traced(fused, hidden, weight, primitive="dot_general")
    assert [eqn.outvars[0].aval.shape for _, eqn in dots] == [
        (1, B, 1, T // CHUNKS, 64 // par.get("tp_size", 1))]
    np.testing.assert_allclose(
        jax.jit(fused)(hidden, weight),
        causal_lm_loss(jnp.einsum("bth,hv->btv", hidden, weight), labels), rtol=1e-5)


_COLLECTIVE = re.compile(r" = (.*?) (all-reduce|all-gather|reduce-scatter)(-start)?\(")
_SHAPE = re.compile(r"\w+\[([0-9,]*)\]")


def _collectives(lines):
    """(op, element count, in the loop, one of several) of every array an
    all-reduce, all-gather or reduce-scatter in ``lines`` produces.  The
    compiler may combine the reductions of several gradients into one op that
    produces a tuple and keeps the first one's name."""
    for line in lines:
        m = _COLLECTIVE.search(line)
        if m:
            shapes = _SHAPE.findall(m.group(1))
            for dims in shapes:
                yield (m.group(2), int(np.prod([int(d) for d in dims.split(",") if d])),
                       "while/body" in line, len(shapes) > 1)


def test_train_step_hlo_moves_no_logits_sized_array_for_the_loss():
    """The four-chip benchmark cell's step (dp_shard 2 x tp 2) at the traffic
    file's rehearsal shapes, compiled: under the ``fused_xent`` scope no
    all-reduce, all-gather or reduce-scatter is as large as a chunk of the
    logits (a row chunk of a dp group x the columns of a shard), except the
    one [N, H] all-reduce of ``dh``, the one gather of the shard's head slice
    over ``dp_shard`` and the one sum of its ``dw`` over ``dp_shard``; and the
    ops carry ``fused_xent/.../vocab_shard``."""
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
    rows = recipe["batch"] // par["dp_shard_size"] * recipe["seq"]            # N of a dp group
    columns = cfg["vocab_size"] // par["tp_size"]                             # of a tp shard
    hidden = cfg["hidden_size"]
    scoped = [line for line in hlo.splitlines() if "fused_xent" in line]
    assert any("vocab_shard" in line for line in scoped)
    chunk, head = rows // traffic["ce_chunks"] * columns, hidden * columns
    found = list(_collectives(scoped))
    assert all(n <= rows // traffic["ce_chunks"] for _, n, in_loop, _ in found if in_loop), found
    # of a combined reduction only the head slice's own gradient is the loss's
    large = sorted((op, n) for op, n, _, combined in found if n >= chunk and (not combined or n == head))
    assert large == [("all-gather", head),                 # the head slice over dp_shard, once
                     ("all-reduce", head),                 # its dw over dp_shard, once, after the loop
                     ("all-reduce", rows * hidden)], large  # dh over tp
