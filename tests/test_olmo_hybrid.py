"""Olmo-Hybrid on the training path (``models/olmo_hybrid.py``, the
differentiable chunked rule of ``ops/gated_delta.py``) against the plain
reference (``perfbench/reference/olmo_hybrid.py``: float32, the rule as the
TOKEN recurrence), on seeded weights at tiny widths that keep ``Dk != Dv``,
one period ``LLLG``.  Each tolerance is written with its reason."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from accelerate_tpu.models import (OlmoHybridConfig, OlmoHybridForCausalLM,  # noqa: E402
                                   hf_olmo_hybrid_key_map, load_hf_olmo_hybrid, make_olmo_hybrid_loss_fn)
from accelerate_tpu.ops import gated_delta as gd  # noqa: E402
from perfbench.families import olmo_hybrid as family  # noqa: E402
from perfbench.reference import olmo_hybrid as reference  # noqa: E402
from perfbench.weights import make_weights  # noqa: E402

LAYERS = 4
BASE = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=LAYERS,
    num_attention_heads=2, num_key_value_heads=2,
    layer_types=["linear_attention"] * 3 + ["full_attention"], linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, max_position_embeddings=512,
    rms_norm_eps=1e-6, tie_word_embeddings=False, rope_parameters={"rope_theta": None},
    assumed={"weight_scales": {"conv": 1.0, "A_log": 1.0, "A_log_mean": -4.0, "dt_bias": 0.5}})
f32 = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _ids(seed, batch, seq, vocab=BASE["vocab_size"]):
    return np.asarray(jax.random.randint(jax.random.key(seed), (batch, seq), 0, vocab), np.int32)


def _float32_pair(cfg=BASE, seed=5, remat=False):
    """The float32 model with its params, and the float32 weights the reference reads."""
    weights = f32(make_weights(family.weight_shapes(cfg, LAYERS), seed))
    model = family.build_model(cfg, LAYERS, remat=remat)
    model = OlmoHybridForCausalLM(dataclasses.replace(model.config, dtype=jnp.float32))
    return model, family.to_program(weights, cfg), weights


def _reference_loss(weights, cfg, ids):
    """The reference's loss as a function of the flat weight dict (``A_log`` with its mean)."""
    c = dict(reference.cfg_key(cfg))
    x = weights["embed"][ids]
    for i, kind in enumerate(reference.kinds(cfg, LAYERS)):
        x = reference.block(x, {k: weights[f"layers.{i}.{k}"] for k in reference.KEYS[kind]},
                            reference.NO_FAULT, kind, c)
    return reference.head_loss(x, weights["final_norm"], weights["head"], jnp.asarray(ids), c)


# -- 1. the forward --------------------------------------------------------------------


@pytest.mark.parametrize("seq", [100, 64])
def test_the_float32_models_logits_are_the_references(seq):
    """Both sides float32; the program runs the chunked form (blocks of 64, a
    padded block at 100 positions) and the flash kernel, the reference the
    token recurrence and a softmax: 2e-4 of logits of size ~4 is their
    summation orders (read: 4e-5)."""
    model, params, weights = _float32_pair()
    ids = _ids(1, 2, seq)
    want = reference.forward_logits(weights, BASE, LAYERS, ids)
    np.testing.assert_allclose(model.apply(params, ids), want, atol=2e-4, rtol=0)


def test_the_bf16_models_logits_follow_the_references():
    """bf16 weights and matmul operands at hidden 64: a logit moves by up to
    ~0.3 of ~4 (read: 0.23-0.31 over seeds 5-7); 0.6 is no rounding - a
    planted fault of ``reference.FAULTS`` moves one by 2.5-6."""
    weights = make_weights(family.weight_shapes(BASE, LAYERS), 5)
    ids = _ids(1, 2, 100)
    got = family.build_model(BASE, LAYERS).apply(family.to_program(weights, BASE), ids)
    want = reference.forward_logits(weights, BASE, LAYERS, ids)
    assert float(jnp.max(jnp.abs(got - want))) < 0.6
    for fault in reference.FAULTS:
        faulty = reference.forward_logits(weights, BASE, LAYERS, ids, quant=fault)
        assert float(jnp.max(jnp.abs(faulty - want))) > 1.0, fault


def test_a_packed_row_and_a_rotary_are_refused():
    model, params, _ = _float32_pair()
    with pytest.raises(NotImplementedError, match="one document a row"):
        model.apply(params, _ids(1, 1, 8), segment_ids=jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="rope_theta"):
        OlmoHybridConfig.tiny(rope_theta=10000.0)
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig.tiny(layer_types=("linear_attention",))
    assert OlmoHybridConfig.tiny().kinds == ("linear_attention",) * 3 + ("full_attention",)
    assert OlmoHybridConfig.olmo_hybrid_7b().kinds.count("full_attention") == 8


# -- 2. the loss and every leaf's gradient ------------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_the_loss_and_every_leafs_gradient_are_the_references(remat):
    """Float32 on both sides, the fused CE in two chunks against the
    reference's plain log-softmax, ``jax.grad`` through the chunked rule's
    own backward pass against ``jax.grad`` of the token recurrence.  A leaf's
    gradient agrees to 2e-4 of its largest element (read: up to 3e-5; the
    leaves include ``A_log``, ``dt_bias``, the convs' taps and the ``b`` / ``a``
    projections, which only the rule's backward reaches)."""
    model, params, weights = _float32_pair(remat=remat)
    ids = _ids(2, 2, 100)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}
    loss, grads = jax.value_and_grad(make_olmo_hybrid_loss_fn(model, fused_vocab_chunks=2))(params, batch)
    seeded = reference.seeded(weights, BASE)
    want_loss, want = jax.value_and_grad(_reference_loss)(seeded, BASE, ids)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    got = family.from_program(grads)
    assert sorted(got) == sorted(want) and len(got) == 3 * 15 + 11 + 3
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale, rtol=0, err_msg=name)


# -- 3. the rule's own gradients ---------------------------------------------------------------


def _recurrence(q, k, v, g, beta, state):
    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        written = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t, precision="highest"))
        s = s + k_t[:, :, None] * written[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def _rule_inputs(seed, t, heads, dk, dv, neg_eigval, aligned=0.0):
    """q, k normed as the model norms them; ``aligned`` mixes a common
    direction into every key (silu's positive mean does that in the model),
    which is what made the product form of the inverse lose its digits."""
    keys = jax.random.split(jax.random.key(seed), 7)
    common = jax.random.normal(keys[6], (heads, dk))
    q = gd.l2norm(jax.random.normal(keys[0], (t, heads, dk))) / np.sqrt(dk)
    k = gd.l2norm(jax.random.normal(keys[1], (t, heads, dk)) + aligned * common)
    v = jax.random.normal(keys[2], (t, heads, dv))
    g = -jnp.exp(-4 + jax.random.normal(keys[3], (heads,))) * jax.nn.softplus(
        jax.random.normal(keys[4], (t, heads)))
    write = jax.nn.sigmoid(jax.random.normal(keys[5], (t, heads)))
    beta = 1.0 + write if neg_eigval else write           # (1, 2): every transition has a negative eigenvalue
    return q, k, v, g, beta, 0.3 * jax.random.normal(keys[6], (heads, dk, dv))


@pytest.mark.parametrize("t,aligned,neg_eigval", [
    (64, 0.0, True), (100, 0.0, True), (100, 0.0, False), (192, 1.0, True), (192, 1.0, False),
    (64 * (gd.SEGMENT + 2) + 5, 0.5, True)])
def test_the_chunked_rules_gradients_are_the_token_recurrences(t, aligned, neg_eigval):
    """o, the last state and the gradients of q, k, v, g, beta and the initial
    state, at lengths that are and are not multiples of 64 and past one
    segment, with ``beta`` in (1, 2) and in (0, 1), ``Dk != Dv``, keys up to
    half aligned: 2e-5 of the largest element, float32 summation orders
    (read: up to 3e-6)."""
    args = _rule_inputs(t, t, 2, 8, 12, neg_eigval, aligned)
    w_o = jax.random.normal(jax.random.key(1), (t, 2, 12))
    w_s = jax.random.normal(jax.random.key(2), (2, 8, 12))
    scalar = lambda f: lambda *a: (lambda o, s: jnp.sum(o * w_o) + jnp.sum(s * w_s))(*f(*a))
    for got, want in zip(gd.gated_delta_chunk(*args), _recurrence(*args)):
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))), rtol=0)
    got = jax.grad(scalar(gd.gated_delta_chunk), argnums=range(6))(*args)
    want = jax.grad(scalar(_recurrence), argnums=range(6))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))), rtol=0, err_msg=name)


def _batched_recurrence(*args):
    return jax.vmap(_recurrence)(*args) if args[0].ndim == 4 else _recurrence(*args)


@pytest.mark.parametrize("t,rows,heads,dk,dv,from_zero,state_read,shards", [
    (130, 0, 1, 96, 192, False, True, 0),           # the cell's Dk x Dv, one head: a head group of one
    (64 * (gd.SEGMENT + 1), 0, 2, 8, 16, False, True, 0),   # two calls: a whole segment, then one block
    (64 * 2 * gd.SEGMENT + 70, 0, 3, 8, 12, False, True, 0),    # a scan over two segments and a ragged tail
    (100, 2, 3, 8, 12, False, True, 0),             # a batch axis: two rows of three heads are six heads
    (200, 3, 1, 16, 8, False, True, 0),             # three rows of one head, Dk > Dv
    (150, 0, 4, 8, 12, True, True, 0),              # from a zero state, as the model calls it
    (150, 0, 4, 8, 12, False, False, 0),            # no cotangent on the last state: d(last) only through O
    (150, 2, 2, 8, 12, True, False, 0),             # the model's own call: rows, from zero, O alone
    (100, 2, 3, 8, 12, False, True, 2),             # ``dp_shard`` 2: each device walks its own row's heads
    (100, 3, 2, 8, 12, False, True, 2),             # three rows on two devices: a row's heads on both
    (100, 1, 3, 8, 12, False, True, 2),             # three heads on two devices: every device walks them all
], ids=["cell_widths", "segment_and_block", "two_segments_ragged", "rows", "rows_of_one_head",
        "from_zero", "o_alone", "as_the_model_calls_it", "two_shards", "two_shards_across_rows",
        "two_shards_replicated"])
def test_the_hand_written_rule_is_the_token_recurrences(t, rows, heads, dk, dv, from_zero, state_read, shards):
    """What ``jax.grad`` runs of the rule - ``_blocks_fwd`` and ``_blocks_bwd``,
    both walks as kernels (interpreted here), the parts' gradient by hand -
    against ``jax.grad`` of the token recurrence: o, the last state, and the
    gradients of all six inputs (the incoming state's among them, with and
    without a cotangent on the last state), ``beta`` in (1, 2), keys a third
    aligned: 2e-5 of the largest element, as the cases above (read: up to 4e-6).
    With ``shards`` the rows lie over ``dp_shard`` of an Accelerator's mesh and
    the call is jitted: each walk then goes through ``_walk``'s ``shard_map``
    (the interpreted kernels take the same specs as the compiled ones), the
    folded rows-of-heads axis split where the devices divide it and seen whole
    where they do not."""
    one = lambda seed: _rule_inputs(seed, t, heads, dk, dv, True, aligned=0.3)
    args = tuple(jnp.stack(both) for both in zip(*(one(t + r) for r in range(rows)))) if rows else one(t)
    if from_zero:
        args = args[:5] + (jnp.zeros_like(args[5]),)
    w_o = jax.random.normal(jax.random.key(1), args[2].shape)
    w_s = jax.random.normal(jax.random.key(2), args[5].shape) * state_read
    scalar = lambda f: lambda *a: (lambda o, s: jnp.sum(o * w_o) + jnp.sum(s * w_s))(*f(*a))
    want_out = _batched_recurrence(*args)
    want = jax.grad(scalar(_batched_recurrence), argnums=range(6))(*args)
    chunk, pulled = gd.gated_delta_chunk, lambda *a: jax.vjp(gd.gated_delta_chunk, *a[:6])[1](a[6:])
    if shards:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from accelerate_tpu import Accelerator
        from accelerate_tpu.parallelism_config import ParallelismConfig

        acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=shards, devices=jax.devices()[:shards]))
        over_rows = NamedSharding(acc.mesh, P("dp_shard") if rows % shards == 0 else P())
        args, w_o, w_s = jax.device_put((args, w_o, w_s), over_rows)
        chunk, pulled = jax.jit(chunk), jax.jit(pulled)
    for got, wanted in zip(chunk(*args), want_out):
        np.testing.assert_allclose(got, wanted, atol=2e-5 * float(jnp.max(jnp.abs(wanted))), rtol=0)
    (o, last), _ = jax.vjp(chunk, *args)                            # the forward rule's own outputs
    for got, wanted in zip((o, last), want_out):
        np.testing.assert_allclose(got, wanted, atol=2e-5 * float(jnp.max(jnp.abs(wanted))), rtol=0)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "state"), pulled(*args, w_o, w_s), want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))), rtol=0, err_msg=name)


@pytest.mark.parametrize("aligned,dk,dv", [(0.0, 8, 12), (0.6, 96, 192)])
def test_the_inverses_cotangent_by_hand_is_autodiffs(aligned, dk, dv):
    """``T = (I - A)^-1`` alone: ``dA = T^T dT T^T`` below the diagonal is what
    ``jax.vjp(_inverse_of_one_minus)`` returns there (autodiff through the six
    levels), and with ``dT = dU b^T + dW c^T`` it is ``_cotangent_of_a``'s
    ``(T^T dU) U^T + (T^T dW) W^T``, which never forms ``dT``: 1e-5 of the
    largest element at ``beta`` 2 and keys 0.6 aligned (read: 2e-6)."""
    _, k, v, _, beta, _ = _rule_inputs(7, 64, 3, dk, dv, True, aligned=aligned)
    k, v, beta = (jnp.moveaxis(x, 1, 0)[:, None] for x in (k, v, beta))             # [Hv, 1, C, ..]
    mm = lambda eq, *xs: jnp.einsum(eq, *xs, precision="highest")
    a = -jnp.tril(mm("hnid,hnjd->hnij", k * beta[..., None], k), -1)
    b, c = v * beta[..., None], k * beta[..., None]
    inv, pull = jax.vjp(gd._inverse_of_one_minus, a)
    d_u, d_w = (jax.random.normal(jax.random.key(i), x.shape) for i, x in enumerate((b, c)))
    d_inv = mm("hnid,hnjd->hnij", d_u, b) + mm("hnid,hnjd->hnij", d_w, c)
    want = jnp.tril(pull(d_inv)[0], -1)
    by_hand = jnp.tril(mm("hnji,hnjk,hnlk->hnil", inv, d_inv, inv), -1)
    t_du, t_dw, fused = gd._cotangent_of_a(inv, mm("hnij,hnjd->hnid", inv, b), mm("hnij,hnjd->hnid", inv, c), d_u, d_w)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(by_hand, want, atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(fused, want, atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(t_du, mm("hnji,hnjd->hnid", inv, d_u), atol=1e-5 * float(jnp.max(jnp.abs(t_du))), rtol=0)
    np.testing.assert_allclose(t_dw, mm("hnji,hnjd->hnid", inv, d_w), atol=1e-5 * float(jnp.max(jnp.abs(t_dw))), rtol=0)


def _eqns(jaxpr):
    """Every equation in order, through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _primitives(jaxpr):
    return [eqn.primitive.name for eqn in _eqns(jaxpr)]


def _qwen3_next_prefill_layer(t):
    """The jaxpr of ONE Gated DeltaNet layer of ``models/qwen3_next.py`` as a
    prefill chunk of ``t`` tokens traces it: in-projections, the conv over the
    slot's window, silu, the L2 norms, the chunked rule from the slot's state,
    the gated norm, the out-projection."""
    from accelerate_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextGatedDeltaNet

    cfg = Qwen3NextConfig.tiny()
    layer = Qwen3NextGatedDeltaNet(cfg)
    slots, vh, dk, dv = 3, cfg.linear_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    cache = {"slots": jnp.asarray([1], jnp.int32), "state": jnp.zeros((slots, vh, dk, dv), jnp.float32),
             "conv": jnp.zeros((slots, cfg.linear_conv_kernel_dim - 1, cfg.conv_channels), cfg.dtype)}
    x, positions = jnp.zeros((1, t, cfg.hidden_size), jnp.float32), jnp.arange(t, dtype=jnp.int32)[None]
    live = jnp.ones((1, t), bool)
    params = jax.eval_shape(layer.init, jax.random.key(0), x, positions, cache, live)
    return jax.make_jaxpr(layer.apply)(params, x, positions, cache, live).jaxpr


@pytest.mark.parametrize("traced,t,rows,digest", [
    ("rule", 200, 0, "bdcc9bbf7ba86d06"), ("rule", 64 * (gd.SEGMENT + 2), 2, "aea06d3a8a028ce3"),
    ("qwen3_next_prefill_layer", 200, 0, "fa2cdfa8a2e50705")],
    ids=["a_prefill_chunk", "rows_past_a_segment", "qwen3_next_prefill_layer"])
def test_only_a_differentiated_call_traces_the_kernels(traced, t, rows, digest):
    """The split between the rule's two users falls on the call itself: an
    undifferentiated ``gated_delta_chunk`` (Qwen3-Next's prefill) traces the
    plain-XLA primal alone - no ``pallas_call``, and the primitive list of
    the commit before the kernels, pinned by its digest - so a serving
    program lowers what it always lowered; under ``jax.grad`` the two walks
    are there, one ``linear_chunk_fwd`` and one ``linear_chunk_bwd`` a call of
    ``_blocks``, and no scan over the blocks.  The third case pins the same of
    the WHOLE linear layer of ``models/qwen3_next.py`` as a prefill chunk
    traces it (the conv, silu, the norms and the gated norm around the rule
    too: ``gd.causal_conv_chunk``, ``gd.l2norm``, plain ``jax.numpy``): the
    primitive list of the commit before the training mixer's fused passes
    (``ops/delta_mixer.py``), no ``pallas_call``, and the one ``custom_vjp_call``
    that is the rule's own."""
    import hashlib
    if traced == "qwen3_next_prefill_layer":
        primal = _primitives(_qwen3_next_prefill_layer(t))
        assert "pallas_call" not in primal and primal.count("custom_vjp_call") == 1
        assert hashlib.sha256(",".join(primal).encode()).hexdigest()[:16] == digest, ",".join(primal)
        return
    one = lambda seed: _rule_inputs(seed, t, 2, 8, 12, True)
    args = tuple(jnp.stack(both) for both in zip(one(1), one(2))) if rows else one(1)
    primal = _primitives(jax.make_jaxpr(gd.gated_delta_chunk)(*args).jaxpr)
    assert "pallas_call" not in primal and primal.count("scan") >= 1
    assert hashlib.sha256(",".join(primal).encode()).hexdigest()[:16] == digest, ",".join(primal)
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gd.gated_delta_chunk(*a)[0]), argnums=range(6)))(*args)
    kernels = [eqn.params["name"] for eqn in _eqns(grad.jaxpr) if eqn.primitive.name == "pallas_call"]
    calls = 2 if rows else 1                        # whole segments under one scan, the blocks behind them
    assert sorted(kernels) == ["linear_chunk_bwd"] * calls + ["linear_chunk_fwd"] * calls
    assert _primitives(grad.jaxpr).count("scan") == (2 if rows else 0)    # ``_chunk``'s, forwards and backwards


def test_remat_makes_the_rules_inverse_once_a_layer():
    """The model's ``remat`` saves ``T`` and ``A`` by name and nothing else: of
    the rule's products with a ``[C, C]`` result a linear layer's first forward
    makes 14 (``A``, twelve of the inverse, ``Q K^T``), the recomputed forward
    1 (``Q K^T`` alone) and the backward 3 (``Q K^T`` once more and ``dA``'s
    two); with everything made again (``nothing_saveable``) the recomputed
    forward makes all 14, so 31 a layer for 18."""
    def square_products(apply):
        loss = lambda p: jnp.sum(jnp.square(apply(p, ids)))
        return sum(eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[1:] == (2, 64, 64)
                   for eqn in _eqns(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))

    model, params, _ = _float32_pair(remat=True)
    ids = jnp.asarray(_ids(3, 1, 100))                              # two blocks a head
    assert square_products(model.apply) == 3 * 18
    plain = OlmoHybridForCausalLM(dataclasses.replace(model.config, remat=False))
    again = jax.checkpoint(plain.apply, policy=jax.checkpoint_policies.nothing_saveable)
    assert square_products(again) == 3 * 31


# -- 3b. the two passes around the rule, each against the plain composition it replaces ---------------


def _plain_conv_pass(q, k, v, q_taps, k_taps, v_taps, heads):
    """``conved`` as the model wrote it before the fused pass, and the L2 norms: ``gd.causal_conv_chunk``
    from a zero window, ``jax.nn.silu``, ``gd.l2norm`` per head, q's ``Dk^-0.5``."""
    def conved(a, taps):
        window = jnp.zeros((taps.shape[0] - 1, a.shape[-1]), jnp.float32)
        return jax.nn.silu(jax.vmap(lambda row: gd.causal_conv_chunk(row, window, taps, a.shape[1])[0])(a))

    dk = q.shape[-1] // heads
    per_head = lambda a: gd.l2norm(a.reshape(a.shape[:2] + (heads, dk))).reshape(a.shape)
    return per_head(conved(q, q_taps)) * dk ** -0.5, per_head(conved(k, k_taps)), conved(v, v_taps)


def _plain_gated_norm(o, z, weight, eps, dtype, heads):
    from accelerate_tpu.models.llama import RMSNorm

    split = lambda a: a.reshape(a.shape[:2] + (heads, -1))
    normed = RMSNorm(eps, jnp.float32).apply({"params": {"scale": weight}}, split(o))
    return (normed * jax.nn.silu(split(z))).reshape(o.shape).astype(dtype)


def _conv_pass_inputs(batch, t, heads, dk, dv, seed=0):
    keys = jax.random.split(jax.random.key(seed), 9)
    normal = lambda i, *shape: jax.random.normal(keys[i], shape, jnp.float32)
    x = (normal(0, batch, t, heads * dk), normal(1, batch, t, heads * dk), normal(2, batch, t, heads * dv))
    taps = tuple(normal(3 + i, 4, a.shape[-1]) * 0.5 for i, a in enumerate(x))
    cotangents = tuple(normal(6 + i, *a.shape) for i, a in enumerate(x))
    return x + taps, cotangents


def _close(got, want, limit, name=""):
    np.testing.assert_allclose(got, want, atol=limit * max(float(jnp.max(jnp.abs(want))), 1e-30), rtol=0, err_msg=name)


CONV_LEAVES = ("q", "k", "v", "q_taps", "k_taps", "v_taps")


@pytest.mark.parametrize("batch,t,heads,dk,dv", [
    (2, 37, 4, 8, 16),          # the tiny heads, two rows, T no multiple of anything: one block a lane-part wide
    (1, 3, 4, 8, 16),           # the first three rows: nothing but the zero history
    (1, 300, 4, 96, 192),       # one block of the published heads (four: 384, 384 and 768 lanes), three blocks of rows
    (1, 140, 6, 96, 192),       # a block and a half of them: the last block hangs over each array's edge
], ids=["tiny_heads_two_rows", "first_three_rows", "published_heads", "published_heads_over_the_edge"])
def test_the_conv_pass_is_the_plain_composition(batch, t, heads, dk, dv):
    """``delta_mixer.conv_silu_l2norm`` (its kernels interpreted) against
    ``gd.causal_conv_chunk`` + ``jax.nn.silu`` + ``gd.l2norm``: the three
    outputs and ``jax.vjp``'s six gradients, float32.  Row 5 of q's first
    sequence and the three before it are zero, so that row's conv is zero and
    its norm is the eps alone: finite, and the gradient there too.  5e-6 of
    the largest element (read: up to 1e-6; the taps' sums over the rows 3e-7)."""
    from accelerate_tpu.ops import delta_mixer

    args, cotangents = _conv_pass_inputs(batch, t, heads, dk, dv)
    if t > 5:
        args = (args[0].at[0, 2:6].set(0.0),) + args[1:]
    got, pull = jax.vjp(lambda *a: delta_mixer.conv_silu_l2norm(*a, heads), *args)
    want, pull_plain = jax.vjp(lambda *a: _plain_conv_pass(*a, heads), *args)
    for name, a, b in zip("qkv", got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a, b, 5e-6, name)
    if t > 5:
        np.testing.assert_array_equal(got[0][0, 5], 0.0)
    for name, a, b in zip(CONV_LEAVES, pull(cotangents), pull_plain(cotangents)):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a, b, 5e-6, name)


@pytest.mark.parametrize("leaf", [3, 4, 5], ids=CONV_LEAVES[3:])
def test_each_taps_gradient_alone_is_the_plain_compositions(leaf):
    """``jax.grad`` in ONE of the three conv weights (the others' cotangents
    are then symbolic zeros inside jax, the kernel's own sums still all made):
    a loss that reads all three outputs, at the published heads over two rows."""
    from accelerate_tpu.ops import delta_mixer

    args, weights = _conv_pass_inputs(2, 70, 4, 96, 192, seed=leaf)
    loss = lambda f: lambda *a: sum(jnp.sum(jnp.square(out) * w) for out, w in zip(f(*a, 4), weights))
    got = jax.grad(loss(delta_mixer.conv_silu_l2norm), argnums=leaf)(*args)
    _close(got, jax.grad(loss(_plain_conv_pass), argnums=leaf)(*args), 5e-6)


@pytest.mark.parametrize("batch,t,heads,dv,dtype", [
    (2, 37, 4, 16, jnp.float32), (1, 300, 2, 192, jnp.float32), (1, 300, 3, 192, jnp.bfloat16),
], ids=["tiny_heads_two_rows", "published_heads", "published_heads_over_the_edge_bf16"])
def test_the_gated_norm_pass_is_the_plain_composition(batch, t, heads, dv, dtype):
    """``delta_mixer.gated_rmsnorm`` against ``RMSNorm`` x ``silu(z)`` and
    the cast: the value (to one rounding of ``dtype``) and the gradients of
    ``o``, ``z`` and the norm's ``scale`` (a sum over rows and heads), with a
    cotangent in ``dtype`` as the projection behind it hands back.  A row of
    ``o`` is zero: the eps keeps its norm finite."""
    from accelerate_tpu.ops import delta_mixer

    keys = jax.random.split(jax.random.key(t), 4)
    o, z, ct = (jax.random.normal(k, (batch, t, heads * dv), jnp.float32) for k in keys[:3])
    o, weight = o.at[0, 1].set(0.0), 1.0 + 0.1 * jax.random.normal(keys[3], (dv,), jnp.float32)
    got, pull = jax.vjp(lambda *a: delta_mixer.gated_rmsnorm(*a, 1e-6, dtype), o, z, weight)
    want, pull_plain = jax.vjp(lambda *a: _plain_gated_norm(*a, 1e-6, dtype, heads), o, z, weight)
    assert got.dtype == dtype and bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    _close(got.astype(jnp.float32), want.astype(jnp.float32), 5e-6 if dtype == jnp.float32 else 2 ** -8)
    for name, a, b in zip(("o", "z", "scale"), pull(ct.astype(dtype)), pull_plain(ct.astype(dtype))):
        assert bool(jnp.all(jnp.isfinite(a))), name
        _close(a, b, 5e-6, name)


def test_the_two_passes_gradients_under_two_shards_are_one_devices():
    """``dp_shard`` 2 on the CPU mesh, two rows, jitted: each pass's launch
    then goes through ``per_shard``'s ``shard_map`` (a device runs the kernel
    on its own row), and what is summed over the rows - the taps' gradients,
    the norm's scale's - leaves the call as one partial a row and is added up
    outside.  Against the same call with no mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accelerate_tpu import Accelerator
    from accelerate_tpu.ops import delta_mixer
    from accelerate_tpu.parallelism_config import ParallelismConfig

    args, cotangents = _conv_pass_inputs(2, 70, 4, 8, 16)
    o, z, ct = cotangents[2], args[2], cotangents[2] * 0.5
    weight = 1.0 + 0.1 * jnp.arange(16, dtype=jnp.float32)
    conv = lambda *a: jax.vjp(lambda *x: delta_mixer.conv_silu_l2norm(*x, 4), *a[:6])[1](a[6:])
    gate = lambda o, z, w, ct: jax.vjp(lambda *x: delta_mixer.gated_rmsnorm(*x, 1e-6, jnp.float32), o, z, w)[1](ct)
    want = conv(*args, *cotangents) + gate(o, z, weight, ct)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=2, devices=jax.devices()[:2]))
    rows, whole = NamedSharding(acc.mesh, P("dp_shard")), NamedSharding(acc.mesh, P())
    put = lambda arrays: tuple(jax.device_put(a, rows if a.ndim == 3 else whole) for a in arrays)
    got = jax.jit(conv)(*put(args + cotangents)) + jax.jit(gate)(*put((o, z, weight, ct)))
    assert "dp_shard" in str(got[0].sharding.spec)                  # the rows stayed where they were
    for name, a, b in zip(CONV_LEAVES + ("o", "z", "scale"), got, want):
        _close(a, b, 2e-6, name)


def test_a_batch_of_rows_is_each_row_alone():
    rows = [_rule_inputs(seed, 100, 3, 8, 12, True) for seed in (1, 2)]
    o, last = gd.gated_delta_chunk(*(jnp.stack(pair) for pair in zip(*rows)))
    for i, row in enumerate(rows):
        o_i, last_i = gd.gated_delta_chunk(*row)
        np.testing.assert_array_equal(o[i], o_i)
        np.testing.assert_array_equal(last[i], last_i)


def test_the_triangular_inverse_keeps_its_digits_where_the_product_form_lost_them():
    """``(I - A)^-1`` for a block of 64 keys a quarter aligned written at
    ``beta`` 2 (what layers 1 and 2 of the cell see): by halves it equals the
    float64 inverse to float32's rounding; the product ``(I + A)(I + A^2)..``
    that the serving-only code used does not."""
    k = np.asarray(_rule_inputs(3, 64, 1, 96, 8, True, aligned=0.6)[1][:, 0], np.float64)
    a = -np.tril(2.0 * (k @ k.T), -1)
    want = np.linalg.inv(np.eye(64) - a)
    got = gd._inverse_of_one_minus(jnp.asarray(a, jnp.float32)[None])[0]
    assert float(np.max(np.abs(got - want))) < 1e-5 * float(np.max(np.abs(want)))
    product, power = np.eye(64, dtype=np.float32) + a.astype(np.float32), a.astype(np.float32)
    for _ in range(5):
        power = power @ power
        product = product + product @ power
    assert float(np.max(np.abs(product - want))) > 1e-3 * float(np.max(np.abs(want)))


# -- 4. the normal path: Accelerator -> create_train_state -> prepare_train_step ------------------

RECIPE = {"optimizer": "lion-sr", "ce_chunks": 2, "parallelism": {},
          "optimizer_hyper": {"lr": 0.004, "b1": 0.9, "b2": 0.99, "weight_decay": 0.0}}
WIDER = dict(BASE, vocab_size=512, hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=32)


@pytest.mark.parametrize("model_dtype,loss_limits,norm_limit,change_limit", [
    ("bfloat16", (0.05, 0.05, 0.1), 0.3, 0.6), ("float32", (1e-4, 0.01, 0.05), 0.01, 0.1)])
def test_three_prepared_steps_with_lion_sr_follow_the_train_reference(model_dtype, loss_limits, norm_limit,
                                                                      change_limit):
    """The rehearsal's widths through the entry points the cell uses (the
    fused CE, lion-sr, every block recomputed, so the rule's hand-written
    backward under ``remat``) against three float32 Lion steps of
    ``TrainReference``: each loss, the first gradient's norm leaf by leaf
    (within ``norm_limit`` of the larger of that leaf's and the median leaf's)
    and every leaf's change after the three (1.0 is a frozen step's).

    ``float32`` (the rehearsal's program: float32 compute over bf16 leaves)
    holds the PATH tightly: the first loss to 1e-4 (read: 1e-5), the second
    to 0.01 (0.0019-0.0063 over seeds 1-4), the third to 0.05 (0.001-0.041:
    parameters rounded stochastically to bf16 and Lion's sign of a bf16
    gradient), norms to 0.01 (read: 0.0008), changes to 0.1 (0.04).

    ``bfloat16`` (the chip's program) holds it as far as bf16 at hidden 128
    lets it: the first loss within 0.05 (read: up to 0.003), the second
    within 0.05 (0.012-0.020 on this seed), norms within 0.3: bf16 operands at
    these widths move a q / k leaf's gradient through the rule by 0.005-0.02
    on rows whose tokens seldom repeat (the rehearsal's) and by 0.15 here,
    0.19-0.28 where every token repeats (a repeated token is a repeated KEY
    at layer 0, written again at ``beta`` up to 2).  The THIRD loss is held to
    0.1, not 0.05: what it reads is a draw, not a distance.  A change of two
    float32 ulps anywhere in the forward is rounded up to bf16's own 0.4%
    within a few layers (two such programs' first gradients differ by 1% in
    every leaf, 0.3% of Lion's signs), and two lion-sr steps of lr 4e-3 turn
    that into 0.02-0.06 of loss.  On this seed the third loss reads 0.023
    with the scan-based rule of PR 42, 0.062 and 0.054 with THAT rule's output
    times ``1 + 2**-22`` and ``1 - 2**-22``; 0.062 with the kernels of PR 44
    and 0.020 with their output times ``1 + 2**-22``; over seeds 1-4 and those
    five programs 0.005-0.075, mean 0.035, neither rule the lower (my CPU
    runs, PR 44: ``_chunk``'s ``o`` scaled in a copy of each tree); 0.022 with
    the fused passes of PR 46 around the rule, and 0.140 - over the limit - while
    their conv summed its taps newest first and their norm met q's scale in
    another order than the plain composition's: the same draw (my CPU runs, PR
    46; seeds 1-4 then 0.016-0.061 against 0.015-0.074 on the tree before)."""
    seed, hy = 1, RECIPE["optimizer_hyper"]
    acc, step, new_state = family.build_trainer(WIDER, LAYERS, dict(RECIPE, model_dtype=model_dtype))
    state = new_state(seed)
    ids = [_ids(10 + i, 1, 128, WIDER["vocab_size"]) for i in range(3)]
    ref = reference.TrainReference(make_weights(family.weight_shapes(WIDER, LAYERS), seed), WIDER,
                                   LAYERS, hy["lr"], hy["b1"], hy["b2"], 3)
    floor = None
    for i, batch in enumerate(ids):
        state, metrics = step(state, {"input_ids": jnp.asarray(batch), "labels": jnp.asarray(batch)})
        want_loss, want_norms = ref.step(batch)
        assert abs(float(metrics["loss"]) - want_loss) < loss_limits[i], i
        if i == 0:
            got = {k: float(jnp.linalg.norm(v.astype(jnp.float32))) / (1.0 - hy["b2"])
                   for k, v in family.momentum_of(state).items()}
            floor = float(np.median(list(want_norms.values())))
            gaps = {k: abs(got[k] - want_norms[k]) / max(want_norms[k], floor) for k in want_norms}
            assert max(gaps.values()) < norm_limit, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]
    assert acc.compile_events >= 1
    moved = family.params_of(state)
    seeded = make_weights(family.weight_shapes(WIDER, LAYERS), seed)
    changes = {k: float(jnp.linalg.norm(moved[k].astype(jnp.float32) - seeded[k].astype(jnp.float32)))
               for k in seeded}
    want = ref.change_norms()
    floor = float(np.median(list(want.values())))
    assert max(abs(changes[k] - want[k]) / max(want[k], floor) for k in want) < change_limit


def test_the_plan_shards_the_new_leaves_and_two_shards_compute_the_one_devices_loss():
    """``dp_shard_size=2`` on the suite's CPU mesh: the parameter plan puts
    every matrix of the new family (the Gated DeltaNet mixer's seven
    projections among them) over ``dp_shard``, and the loss of a batch of two
    rows is the one device's to bf16's summation order (1e-3 of ~5.5)."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.parallelism_config import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState, GradientState

    weights = make_weights(family.weight_shapes(WIDER, LAYERS), 3)
    ids = jnp.asarray(_ids(4, 2, 128, WIDER["vocab_size"]))
    batch = {"input_ids": ids, "labels": ids}
    model = family.build_model(WIDER, LAYERS, remat=True)
    loss_fn = make_olmo_hybrid_loss_fn(model, fused_vocab_chunks=2)
    alone = float(jax.jit(loss_fn)(family.to_program(weights, WIDER), batch))

    acc = Accelerator(mixed_precision="bf16",
                      parallelism_config=ParallelismConfig(dp_shard_size=2, devices=jax.devices()[:2]))
    plan = family.param_shardings(acc, WIDER, LAYERS)
    sharded = {name for name, s in plan.items() if "dp_shard" in jax.tree_util.tree_leaves(tuple(s.spec))}
    matrices = {name for name, (shape, _) in family.weight_shapes(WIDER, LAYERS).items()
                if len(shape) == 2 and min(shape) >= 4 and shape[0] * shape[1] >= 2 ** 12}
    assert matrices - {f"layers.{i}.{leaf}" for i in range(3) for leaf in ("ba", "conv")} <= sharded
    assert {"layers.0.lq", "layers.0.lz", "layers.1.lo", "layers.3.q"} <= sharded
    whole = acc._params_plan(jax.eval_shape(lambda: family.to_program(weights, WIDER)))
    for name in ("q_proj", "k_proj", "v_proj", "g_proj", "o_proj"):     # by the program's own names too
        spec = whole["params"]["layers_0"]["linear_attn"][name]["kernel"].spec
        assert "dp_shard" in jax.tree_util.tree_leaves(tuple(spec)), name
    state = acc.create_train_state(family.to_program(make_weights(
        family.weight_shapes(WIDER, LAYERS), 3, plan), WIDER), "lion-sr", apply_fn=model.apply)
    step = acc.prepare_train_step(loss_fn)
    _, metrics = step(state, {k: jax.device_put(v, family.batch_sharding(acc, v)) for k, v in batch.items()})
    assert abs(float(metrics["loss"]) - alone) < 1e-3 * alone
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


# -- 5. the published checkpoint's names -----------------------------------------------------


def test_hf_names_load_into_the_tree_the_benchmark_builds():
    """``load_hf_olmo_hybrid``: torch ``[out, in]`` tensors under ``model.``,
    the three depthwise convs' ``[C, 1, 4]``, the bare ``A_log`` / ``dt_bias``,
    the OLMo 2 norm names; rotary buffers are skipped, and every name maps
    back (the round trip)."""
    whole = f32(make_weights(family.weight_shapes(BASE, LAYERS), seed=5))
    want_tree = family.to_program(whole, BASE)
    block = {**{f"l{n}": f"linear_attn.{p}_proj.weight" for n, p in zip("qkvzo", "qkvgo")},
             "A_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias",
             "o_norm": "linear_attn.o_norm.weight",
             **{n: f"self_attn.{n}_proj.weight" for n in "qkvo"},
             "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
             "mixer_norm": "post_attention_layernorm.weight", "mlp_norm": "post_feedforward_layernorm.weight",
             "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight", "down": "mlp.down_proj.weight"}
    pairs = [("lm_head.weight", np.asarray(whole["head"]).T),
             ("model.embed_tokens.weight", np.asarray(whole["embed"])),
             ("model.norm.weight", np.asarray(whole["final_norm"])),
             ("model.layers.3.self_attn.rotary_emb.inv_freq", np.zeros((4,), np.float32))]
    for name, arr in whole.items():
        if name.startswith("layers."):
            _, i, leaf = name.split(".")
            arr, at = np.asarray(arr), f"model.layers.{i}"
            if leaf in ("conv", "ba"):          # one benchmark leaf, several tensors of the checkpoint
                cuts = np.split(arr, np.cumsum(family.cut_widths(BASE, leaf))[:-1], axis=-1)
                names = [f"{n}_conv1d" for n in "qkv"] if leaf == "conv" else ["b_proj", "a_proj"]
                pairs += [(f"{at}.linear_attn.{n}.weight", c.T[:, None, :] if leaf == "conv" else c.T)
                          for n, c in zip(names, cuts)]
                continue
            if leaf == "A_log":
                arr = arr + BASE["assumed"]["weight_scales"]["A_log_mean"]
            pairs.append((f"{at}.{block[leaf]}", arr.T if arr.ndim == 2 else arr))
    model = OlmoHybridForCausalLM(dataclasses.replace(family.build_model(BASE, LAYERS).config,
                                                      dtype=jnp.float32))
    params, _ = load_hf_olmo_hybrid(model, pairs, dtype=jnp.float32)
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(params), flat(want_tree)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    mapped = {hf_olmo_hybrid_key_map(name) for name, _ in pairs} - {None}
    assert len(mapped) == len(pairs) - 1                     # one name a leaf, the rotary buffer none
    assert hf_olmo_hybrid_key_map("model.layers.2.linear_attn.g_proj.weight") == \
        "params.layers_2.linear_attn.g_proj.kernel"
    assert hf_olmo_hybrid_key_map("model.layers.9.unknown.weight") == "model.layers.9.unknown.weight"
