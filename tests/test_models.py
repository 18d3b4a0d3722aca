"""Model-family tests: forward shapes, loss decrease, TP-rule alignment."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator, ParallelismConfig
from accelerate_tpu.models import (
    BertConfig,
    BertForSequenceClassification,
    LlamaConfig,
    LlamaForCausalLM,
    ResNet,
    ResNetConfig,
    causal_lm_loss,
    make_bert_loss_fn,
    make_llama_loss_fn,
)


def test_llama_forward_shapes():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_gqa_and_causality():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.randint(0, 255, (1, 12)), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    logits_full = model.apply(params, ids)
    # causality: changing a future token must not change past logits
    ids2 = ids.at[0, 8].set((ids[0, 8] + 1) % 255)
    logits_mod = model.apply(params, ids2)
    np.testing.assert_allclose(
        np.asarray(logits_full[0, :8]), np.asarray(logits_mod[0, :8]), rtol=2e-2, atol=2e-3
    )
    assert not np.allclose(np.asarray(logits_full[0, 8:]), np.asarray(logits_mod[0, 8:]), atol=1e-3)


def test_llama_trains_under_accelerator():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=8))
    ids = jnp.ones((8, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    state = acc.create_train_state(params, optax.adamw(1e-3), apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
    rng = np.random.default_rng(0)
    batch_np = rng.integers(0, 255, (8, 16))
    from accelerate_tpu.ops import host_local_to_global
    from jax.sharding import PartitionSpec as P

    batch = host_local_to_global(
        {"input_ids": batch_np.astype(np.int32), "labels": batch_np.astype(np.int32)},
        acc.mesh, P(("dp_shard",), None),
    )
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_llama_tp_sharding_applied():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
    ids = jnp.ones((4, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    state = acc.create_train_state(params, optax.sgd(1e-3))
    q_kernel = state.params["params"]["layers_0"]["self_attn"]["q_proj"]["kernel"]
    assert "tp" in str(q_kernel.sharding.spec)
    logits = model.apply(state.params, ids)  # still computes correctly sharded
    assert logits.shape == (4, 16, cfg.vocab_size)


def test_causal_lm_loss_ignore_index():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[1, 2, -100, 3]])
    loss = causal_lm_loss(logits, labels)
    assert np.isclose(float(loss), np.log(8), rtol=1e-5)


def test_bert_forward_and_train():
    cfg = BertConfig.tiny()
    model = BertForSequenceClassification(cfg)
    ids = jnp.ones((4, 16), jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids, mask)
    logits = model.apply(params, ids, mask)
    assert logits.shape == (4, cfg.num_labels)

    acc = Accelerator()
    state = acc.create_train_state(params, optax.adamw(1e-3))
    step = acc.prepare_train_step(make_bert_loss_fn(model))
    batch = {
        "input_ids": jnp.asarray(np.random.randint(0, 500, (8, 16)), jnp.int32),
        "attention_mask": jnp.ones((8, 16), jnp.int32),
        "labels": jnp.asarray(np.random.randint(0, 2, (8,)), jnp.int32),
    }
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_resnet_forward():
    cfg = ResNetConfig.tiny()
    model = ResNet(cfg)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x)
    logits, updates = model.apply(variables, x, mutable=["batch_stats"])
    assert logits.shape == (2, 10)
    assert "batch_stats" in updates


def test_flops_per_token_positive():
    from accelerate_tpu.models import flops_per_token

    cfg = LlamaConfig.llama2_7b()
    f = flops_per_token(cfg, 4096)
    # 6*6.7e9 ~ 4e10 plus attention term
    assert 3.5e10 < f < 6e10


# ---------------------------------------------------------------------------
# T5 encoder-decoder (reference Megatron T5TrainStep megatron_lm.py:718)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_t5_forward_shapes():
    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    enc_ids = jnp.ones((2, 12), jnp.int32)
    dec_ids = jnp.ones((2, 8), jnp.int32)
    params = model.init(jax.random.key(0), enc_ids, dec_ids)
    logits = model.apply(params, enc_ids, dec_ids)
    assert logits.shape == (2, 8, cfg.vocab_size)


@pytest.mark.slow
def test_t5_decoder_is_causal():
    """Changing a future decoder token must not change earlier logits."""
    import numpy as np

    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny(dtype=jnp.float32)
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.ones((1, 8), jnp.int32)
    dec = jnp.arange(8, dtype=jnp.int32)[None] % cfg.vocab_size
    params = model.init(jax.random.key(0), enc, dec)
    base = model.apply(params, enc, dec)
    dec2 = dec.at[0, -1].set((int(dec[0, -1]) + 1) % cfg.vocab_size)
    pert = model.apply(params, enc, dec2)
    np.testing.assert_allclose(np.asarray(base[0, :-1]), np.asarray(pert[0, :-1]), atol=1e-5)


@pytest.mark.slow
def test_t5_encoder_mask_blocks_attention():
    import numpy as np

    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny(dtype=jnp.float32)
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    dec = jnp.ones((1, 4), jnp.int32)
    params = model.init(jax.random.key(0), enc, dec)
    mask = jnp.asarray([[True, True, False, False]])
    masked = model.apply(params, enc, dec, attention_mask=mask)
    # tokens behind the mask must not influence the output
    enc2 = enc.at[0, 2].set(99)
    masked2 = model.apply(params, enc2, dec, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(masked2), atol=1e-5)


@pytest.mark.slow
def test_t5_training_converges_sharded():
    """Seq2seq copy task improves under dp_shard x tp sharding."""
    import optax

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration, make_t5_loss_fn

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2),
        mixed_precision="bf16",
    )
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(2, cfg.vocab_size, (8, 12)), jnp.int32)
    batch = {"input_ids": src, "labels": src}  # copy task

    params = model.init(jax.random.key(0), src[:, :4], src[:, :4])
    state = acc.create_train_state(params, optax.adamw(3e-3), apply_fn=model.apply)
    step = acc.prepare_train_step(make_t5_loss_fn(model), max_grad_norm=1.0)

    first = None
    for _ in range(8):
        state, metrics = step(state, batch)
        first = first or float(metrics["loss"])
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))


@pytest.mark.slow
def test_t5_ffn_kernels_are_tensor_parallel_sharded():
    """Regression: wi_gate/wi_up must match the TP rule table so the d_model x
    d_ff FFN matrices actually shard over tp (not silently replicate)."""
    from accelerate_tpu import ParallelismConfig
    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration
    from accelerate_tpu.parallel.sharding import TRANSFORMER_TP_RULES, make_sharding_plan

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.ones((1, 4), jnp.int32)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), enc, enc))
    pcfg = ParallelismConfig(dp_shard_size=4, tp_size=2)
    plan = make_sharding_plan(abstract, pcfg.build_device_mesh(), pcfg, tp_rules=TRANSFORMER_TP_RULES)
    mlp = plan["params"]["enc_layers_0"]["mlp"]
    assert mlp["wi_gate"]["kernel"].spec[-1] == "tp", mlp["wi_gate"]["kernel"].spec
    assert mlp["wi_up"]["kernel"].spec[-1] == "tp", mlp["wi_up"]["kernel"].spec
    assert mlp["wo_mlp"]["kernel"].spec[0] == "tp", mlp["wo_mlp"]["kernel"].spec


@pytest.mark.slow
def test_llama_remat_policy_dots_compiles():
    """remat_policy='dots' (save matmul outputs) must trace/execute like 'full'."""
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn

    cfg = LlamaConfig.tiny(remat=True, remat_policy="dots")
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    acc = Accelerator()
    params = model.init(jax.random.key(0), ids)
    state = acc.create_train_state(params, optax.sgd(0.1), apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model))
    state, metrics = step(state, {"input_ids": ids, "labels": ids})
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
@pytest.mark.parametrize("tied_cases", [(False,), (True,)])
def test_fused_linear_xent_matches_logits_path(tied_cases):
    """Chunked fused linear+CE (ops/fused_xent.py) == logits path: loss and
    every gradient leaf, tied and untied heads, with ignore_index masking.
    Whole-model compiles x2 put both cases in the slow tier; the fast tier
    keeps the op-level grads check (test_fused_linear_xent_non_divisible_
    vocab) and the on-chip bench selftest exercises the kernel for real."""
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn

    for tied in tied_cases:
        cfg = LlamaConfig.tiny(dtype=jnp.float32, tie_word_embeddings=tied)
        model = LlamaForCausalLM(cfg)
        ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 24)), jnp.int32)
        labels = ids.at[0, :5].set(-100)  # exercise the mask
        params = model.init(jax.random.key(0), ids)
        batch = {"input_ids": ids, "labels": labels}

        base = make_llama_loss_fn(model)
        fused = make_llama_loss_fn(model, fused_vocab_chunks=4)
        l0, g0 = jax.value_and_grad(base)(params, batch)
        l1, g1 = jax.value_and_grad(fused)(params, batch)
        assert abs(float(l0) - float(l1)) < 1e-4, (tied, float(l0), float(l1))
        flat1 = {jax.tree_util.keystr(p): v for p, v in jax.tree_util.tree_flatten_with_path(g1)[0]}
        for p, v in jax.tree_util.tree_flatten_with_path(g0)[0]:
            key = jax.tree_util.keystr(p)
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(flat1[key]), atol=2e-4, err_msg=f"tied={tied} {key}"
            )


@pytest.mark.slow
def test_fused_linear_xent_non_divisible_vocab():
    """Neither the vocabulary (10 columns) nor the rows (6 positions in 4 or 7
    chunks: a padded, masked tail) a multiple of anything: loss and grads
    must still match the reference exactly."""
    from accelerate_tpu.ops.fused_xent import fused_linear_xent

    rng = np.random.default_rng(1)
    N, H, V = 6, 8, 10
    h = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    mask = jnp.asarray([True] * 5 + [False])

    def ref(h, w):
        logits = h @ w.T
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum((lse - ll) * mask) / jnp.sum(mask)

    l_r, g_r = jax.value_and_grad(ref, argnums=(0, 1))(h, w)
    for nc in (3, 4, 7):
        l_f, g_f = jax.value_and_grad(   # one sequence of N positions
            lambda h, w: fused_linear_xent(h[None], w, labels[None], mask[None], nc, True), argnums=(0, 1)
        )(h, w)
        assert abs(float(l_f) - float(l_r)) < 1e-5, (nc, float(l_f), float(l_r))
        np.testing.assert_allclose(np.asarray(g_f[0]), np.asarray(g_r[0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g_f[1]), np.asarray(g_r[1]), atol=1e-5)


@pytest.mark.slow
def test_t5_remat_matches_plain():
    """remat=True changes memory, not math: same logits and grads."""
    import numpy as np

    from accelerate_tpu.models import T5Config, T5ForConditionalGeneration
    from accelerate_tpu.models.t5 import make_t5_loss_fn

    enc = jnp.ones((1, 8), jnp.int32)
    dec = jnp.arange(8, dtype=jnp.int32)[None] % 256
    plain = T5ForConditionalGeneration(T5Config.tiny(dtype=jnp.float32))
    remat = T5ForConditionalGeneration(T5Config.tiny(dtype=jnp.float32, remat=True))
    params = plain.init(jax.random.key(0), enc, dec)
    np.testing.assert_allclose(
        np.asarray(remat.apply(params, enc, dec)),
        np.asarray(plain.apply(params, enc, dec)), atol=1e-5,
    )
    batch = {"input_ids": enc, "labels": dec}
    g1 = jax.grad(make_t5_loss_fn(plain))(params, batch)
    g2 = jax.grad(make_t5_loss_fn(remat))(params, batch)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_offload_remat_policy_degrades_and_trains(monkeypatch):
    """remat_policy="offload" (activation boundaries in pinned host memory
    on TPU) keeps param paths and numerics; on the CPU mesh it degrades to
    full remat, so this pins structure + gradient flow + loss parity — and
    then forces the real _stack branch (host_offload_supported patched
    True) to pin its param-path parity too."""
    from accelerate_tpu.models import make_llama_loss_fn

    cfg = LlamaConfig.tiny(remat=True, remat_policy="offload")
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    assert "layers_0" in params["params"] and "layers_1" in params["params"]
    loss_fn = make_llama_loss_fn(model)
    loss, grads = jax.value_and_grad(loss_fn)(params, {"input_ids": ids, "labels": ids})
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree_util.tree_leaves(grads))
    ref_cfg = LlamaConfig.tiny(remat=True, remat_policy="full")
    ref = make_llama_loss_fn(LlamaForCausalLM(ref_cfg))(params, {"input_ids": ids, "labels": ids})
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    # the real offload branch (the nn.remat'd _stack function) must produce
    # the same param structure — a scoping regression would otherwise only
    # surface on TPU hardware at checkpoint load
    monkeypatch.setattr(
        "accelerate_tpu.parallel.sharding.host_offload_supported", lambda: True
    )
    params_stack = model.init(jax.random.PRNGKey(0), ids)
    assert jax.tree_util.tree_structure(params_stack) == jax.tree_util.tree_structure(params)
    loss_stack = loss_fn(params, {"input_ids": ids, "labels": ids})
    np.testing.assert_allclose(float(loss_stack), float(ref), rtol=1e-5)


@pytest.mark.slow
def test_scan_layers_matches_unrolled():
    """scan_layers=True computes the same function as the unrolled stack:
    init the unrolled model, stack its per-layer params into the scan
    layout, and require identical logits + loss gradients (remat on, the
    131k-config shape: remat_policy degrades to full on CPU)."""
    from accelerate_tpu.models.llama import stack_layer_params, unstack_layer_params

    cfg = LlamaConfig.tiny(remat=True, remat_policy="offload", dtype=jnp.float32)
    scan_cfg = LlamaConfig.tiny(remat=True, remat_policy="offload", scan_layers=True,
                                dtype=jnp.float32)
    model, scan_model = LlamaForCausalLM(cfg), LlamaForCausalLM(scan_cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    stacked = stack_layer_params(params)
    k = stacked["params"]["layers_scan"]["block"]["self_attn"]["q_proj"]["kernel"]
    assert k.shape[0] == cfg.num_hidden_layers

    np.testing.assert_allclose(
        np.asarray(model.apply(params, ids)),
        np.asarray(scan_model.apply(stacked, ids)), rtol=2e-5, atol=2e-5)

    loss_fn = make_llama_loss_fn(model)
    scan_loss_fn = make_llama_loss_fn(scan_model)
    batch = {"input_ids": ids, "labels": ids}
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    s_loss, s_grads = jax.value_and_grad(scan_loss_fn)(stacked, batch)
    np.testing.assert_allclose(float(loss), float(s_loss), rtol=1e-5)
    # grads in the scan layout unstack back to the unrolled layout
    for (pa, ga), (pb, gb) in zip(
        sorted(jax.tree_util.tree_flatten_with_path(grads)[0], key=lambda t: str(t[0])),
        sorted(jax.tree_util.tree_flatten_with_path(unstack_layer_params(s_grads))[0],
               key=lambda t: str(t[0])),
    ):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=5e-3, atol=2e-4,
                                   err_msg=str(pa))

    # round-trip
    rt = unstack_layer_params(stacked)
    assert jax.tree_util.tree_structure(rt) == jax.tree_util.tree_structure(params)


def test_boundary_offload_fraction_is_identity_math():
    """The hybrid boundary-residency split (boundary_offload_fraction < 1,
    docs/long_context.md) is slice+concat inside the scan body — pure
    placement, so logits and grads must match the frac=1.0 scan model
    exactly.  (On the bench rig the split measurably did NOT move the
    T>=131,072 crash wall — the knob is kept for hosts where pinned is the
    genuine binding pool; this pins that it can never change numerics.)"""
    from accelerate_tpu.models.llama import stack_layer_params

    base = LlamaConfig.tiny(remat=True, remat_policy="offload", scan_layers=True,
                            dtype=jnp.float32)
    split = LlamaConfig.tiny(remat=True, remat_policy="offload", scan_layers=True,
                             boundary_offload_fraction=0.5, dtype=jnp.float32)
    m_base, m_split = LlamaForCausalLM(base), LlamaForCausalLM(split)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 255, (2, 16)), jnp.int32)
    unrolled = LlamaForCausalLM(LlamaConfig.tiny(dtype=jnp.float32))
    stacked = stack_layer_params(unrolled.init(jax.random.PRNGKey(0), ids))

    np.testing.assert_array_equal(
        np.asarray(m_base.apply(stacked, ids)), np.asarray(m_split.apply(stacked, ids)))
    batch = {"input_ids": ids, "labels": ids}
    l_a, g_a = jax.value_and_grad(make_llama_loss_fn(m_base))(stacked, batch)
    l_b, g_b = jax.value_and_grad(make_llama_loss_fn(m_split))(stacked, batch)
    assert float(l_a) == float(l_b)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        g_a, g_b)


def test_boundary_offload_fraction_validation():
    with pytest.raises(ValueError, match="boundary_offload_fraction"):
        LlamaConfig.tiny(boundary_offload_fraction=0.0)
    with pytest.raises(ValueError, match="boundary_offload_fraction"):
        LlamaConfig.tiny(boundary_offload_fraction=1.5)


@pytest.mark.slow
def test_scan_layers_init_and_tp_sharding():
    """Direct init in the scan layout + the sharding planner's shifted TP
    rules: the stacked q_proj kernel [L, H, H'] shards 'tp' on its LAST dim."""
    cfg = LlamaConfig.tiny(scan_layers=True)
    model = LlamaForCausalLM(cfg)
    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
    ids = jnp.ones((4, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    import optax as _optax

    state = acc.create_train_state(params, _optax.sgd(1e-3))
    k = state.params["params"]["layers_scan"]["block"]["self_attn"]["q_proj"]["kernel"]
    assert k.ndim == 3
    assert "tp" in str(k.sharding.spec)
    assert k.sharding.spec[2] == "tp" or k.sharding.spec[-1] == "tp"
    logits = model.apply(state.params, ids)
    assert logits.shape == (4, 16, cfg.vocab_size)


def test_scan_layers_cached_decode_raises():
    """scan_layers has no cached-decode path; the error must say how to
    convert (unstack + scan_layers=False) instead of a scope lookup crash."""
    from accelerate_tpu.models.llama import init_cache

    cfg = LlamaConfig.tiny(scan_layers=True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), ids)
    cache = init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="unstack_layer_params"):
        model.apply(params, ids, cache=cache)


@pytest.mark.slow
def test_scan_block_size_matches_unrolled():
    """scan_block_size=2 (pair iterations, halved offload boundaries)
    computes the same function as the unrolled stack; converters map
    global layer i to (iteration i//bs, slot i%bs) and round-trip."""
    from accelerate_tpu.models.llama import stack_layer_params, unstack_layer_params

    cfg = LlamaConfig.tiny(num_hidden_layers=4, remat=True, remat_policy="offload",
                           dtype=jnp.float32)
    scan_cfg = LlamaConfig.tiny(num_hidden_layers=4, remat=True, remat_policy="offload",
                                scan_layers=True, scan_block_size=2, dtype=jnp.float32)
    model, scan_model = LlamaForCausalLM(cfg), LlamaForCausalLM(scan_cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    stacked = stack_layer_params(params, scan_block_size=2)
    blk = stacked["params"]["layers_scan"]
    assert set(blk) == {"block_0", "block_1"}
    assert blk["block_0"]["self_attn"]["q_proj"]["kernel"].shape[0] == 2

    np.testing.assert_allclose(
        np.asarray(model.apply(params, ids)),
        np.asarray(scan_model.apply(stacked, ids)), rtol=2e-5, atol=2e-5)

    loss_fn, s_loss_fn = make_llama_loss_fn(model), make_llama_loss_fn(scan_model)
    batch = {"input_ids": ids, "labels": ids}
    loss = loss_fn(params, batch)
    s_loss, s_grads = jax.value_and_grad(s_loss_fn)(stacked, batch)
    np.testing.assert_allclose(float(loss), float(s_loss), rtol=1e-5)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree_util.tree_leaves(s_grads))

    rt = unstack_layer_params(stacked)
    assert jax.tree_util.tree_structure(rt) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(rt), jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    with pytest.raises(ValueError, match="scan_block_size"):
        LlamaConfig.tiny(num_hidden_layers=4, scan_layers=True, scan_block_size=3)
    with pytest.raises(ValueError, match="requires scan_layers"):
        LlamaConfig.tiny(num_hidden_layers=4, scan_block_size=2)


@pytest.mark.slow
def test_mixtral_scan_layers_parity():
    """scan_layers composes with the MoE block family (MixtralConfig
    subclasses LlamaConfig; blocks are homogeneous so the stacked scan
    applies unchanged)."""
    from accelerate_tpu.models import MixtralConfig, MixtralForCausalLM
    from accelerate_tpu.models.llama import stack_layer_params

    cfg = MixtralConfig.tiny(dtype=jnp.float32)
    scfg = MixtralConfig.tiny(dtype=jnp.float32, scan_layers=True)
    m, sm = MixtralForCausalLM(cfg), MixtralForCausalLM(scfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 16)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)
    np.testing.assert_allclose(
        np.asarray(m.apply(params, ids)),
        np.asarray(sm.apply(stack_layer_params(params), ids)),
        rtol=2e-5, atol=2e-5)
