"""JoyAI-LLM-Flash's language model (``models/joyai_flash.py``: latent
attention over one pool of latent rows a layer, read by an absorbed decode
walk and an expanded prefill walk; a dense first layer, sigmoid-routed experts
beside a shared expert; the chip's share of heads, experts and vocabulary)
against its plain reference (``perfbench/reference/joyai_flash.py``: float32
``jax.numpy``, the expanded equations, no cache), at tiny sizes on seeded
weights.  LOGITS are compared, never tokens alone: with random weights the
largest logit changes on rounding."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from accelerate_tpu.generation import GenerationConfig  # noqa: E402
from accelerate_tpu.models import JoyAIFlashConfig, JoyAIFlashForCausalLM  # noqa: E402
from accelerate_tpu.models.joyai_flash import JoyAIFlashAttention, deinterleave_rope  # noqa: E402
from accelerate_tpu.models.k_exaone import KExaoneSparseMoE  # noqa: E402
from accelerate_tpu.ops import page_walk as pw  # noqa: E402
from accelerate_tpu.serving import (Request, ServingEngine, cache_accounting,  # noqa: E402
                                    verify_serving_invariants)
from accelerate_tpu.utils.dataclasses import ServingPlugin  # noqa: E402
from perfbench.families import joyai_flash as family  # noqa: E402
from perfbench.reference import joyai_flash as reference  # noqa: E402
from perfbench.weights import make_weights  # noqa: E402

BASE = {   # the published config's keys at test scale, held whole
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_routed_experts": 16,
    "num_experts_per_tok": 4, "n_shared_experts": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 1,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6, "rope_theta": 32000000,
    "rope_interleave": True, "rope_scaling": None, "tie_word_embeddings": False,
    "assumed": {"weight_scales": {"embed": 2.0, "router": 2.0, "router_bias": 0.2, "q_b": 3.0,
                                  "kv_a_norm": 1.0}},
}
# rank 0 of two chips that share each layer: half the heads, experts and vocabulary
CFG = {**BASE, "vocab_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2,
       "n_routed_experts": 8,
       "published": {"num_hidden_layers": 3, "n_routed_experts": 16, "num_attention_heads": 4,
                     "num_key_value_heads": 4, "vocab_size": 256},
       "share": {"chips_per_layer": 2, "rank": 0, "experts_held": list(range(8))}}
LAYERS = 3
ROW = 128                            # [c (32) ; kr (8)] padded to whole 128-lane tiles
TOL = dict(rtol=2e-4, atol=2e-4)     # float32 both sides; the orders of summation differ


def f32(made):
    """Seeded bf16 values held in float32, so program and reference read the same numbers."""
    return {k: jnp.asarray(v, jnp.float32) for k, v in made.items()}


@pytest.fixture(scope="module")
def weights():
    return f32(make_weights(family.weight_shapes(CFG, LAYERS, mtp=True), seed=7))


@pytest.fixture(scope="module")
def model():
    return family.build_model(CFG, LAYERS, dtype=jnp.float32)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(1, CFG["vocab_size"], n).astype(np.int32)


# -- 1. the forward with no cache ------------------------------------------------------


def test_the_model_builds_exactly_the_weights_it_holds(model, weights):
    shapes = jax.tree.map(lambda x: x.shape, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32), output_mtp=True))["params"])
    attn = shapes["layers_1"]["self_attn"]
    assert attn["q_a_proj"]["kernel"] == (64, 48) and attn["q_a_layernorm"]["scale"] == (48,)   # whole
    assert attn["kv_a_proj_with_mqa"]["kernel"] == (64, 32 + 8) and attn["kv_a_layernorm"]["scale"] == (32,)
    assert attn["q_b_proj"]["kernel"] == (48, 2 * 24)                                # 2 of 4 heads
    assert attn["kv_b_proj"] == (32, 2 * 32) and attn["o_proj"]["kernel"] == (2 * 16, 64)
    assert shapes["layers_1"]["mlp"]["experts_gate_proj"] == (8, 64, 32)            # 8 of 16 experts
    assert shapes["layers_1"]["mlp"]["gate"]["kernel"] == (64, 16)                  # the router: all 16
    assert shapes["layers_1"]["mlp"]["e_score_correction_bias"] == (16,)
    assert shapes["layers_0"]["mlp"]["gate_proj"]["kernel"] == (64, 96)             # the dense layer
    assert shapes["lm_head"]["kernel"] == (64, 128) and shapes["embed_tokens"]["embedding"] == (128, 64)
    given = jax.tree.map(lambda x: x.shape, family.to_program(weights, CFG)["params"])
    assert given == shapes                       # the benchmark makes exactly these, MTP included


@pytest.mark.parametrize("length", [6, 40], ids=["short", "forty"])
def test_forward_matches_the_reference(model, weights, length):
    """The latent attention (the program rotates halves over de-interleaved
    columns, the reference the published pairs), the dense first layer, the
    share - and the MTP module's logits."""
    ids = ids_of(length, length)
    logits, mtp = model.apply(family.to_program(weights, CFG), jnp.asarray(ids[None]), output_mtp=True)
    np.testing.assert_allclose(logits[0], reference.row_logits(weights, CFG, LAYERS, ids), **TOL)
    np.testing.assert_allclose(mtp[0], reference.mtp_logits(weights, CFG, LAYERS, ids), **TOL)
    assert mtp.shape == (1, length - 1, CFG["vocab_size"])


def test_the_rotary_pairing_is_a_fixed_permutation():
    """De-interleaving is the permutation that makes rotate-half pair the
    published dims (2i, 2i + 1): ``to_program`` applies it to the last 8
    columns of ``kv_a`` and of every head of ``q_b`` and to nothing else."""
    assert deinterleave_rope(8).tolist() == [0, 2, 4, 6, 1, 3, 5, 7]
    kv_a = jnp.arange(40, dtype=jnp.float32)[None]                   # [1, 32 + 8]
    q_b = jnp.arange(48, dtype=jnp.float32)[None]                    # 2 heads x (16 + 8)
    got = family.to_program({"layers.1.kv_a": kv_a, "layers.1.q_b": q_b, "layers.1.kv_b": kv_a},
                            CFG)["params"]["layers_1"]["self_attn"]
    assert got["kv_a_proj_with_mqa"]["kernel"][0, 32:].tolist() == [32, 34, 36, 38, 33, 35, 37, 39]
    assert got["q_b_proj"]["kernel"][0, 16:24].tolist() == [16, 18, 20, 22, 17, 19, 21, 23]
    assert got["q_b_proj"]["kernel"][0, 40:].tolist() == [40, 42, 44, 46, 41, 43, 45, 47]
    assert got["q_b_proj"]["kernel"][0, :16].tolist() == list(range(16))
    assert got["kv_b_proj"] is kv_a                                  # any other leaf: the same array


# -- 2. two walks over one pool ------------------------------------------------------------


def attention_params(weights, leaf="layers.1"):
    flat = {k: weights[f"{leaf}.{k}"] for k in family.ATTN if family.ATTN[k][0] == "self_attn"}
    return family.to_program({f"layers.1.{k}": v for k, v in flat.items()},
                             CFG)["params"]["layers_1"]["self_attn"]


def test_the_absorbed_and_the_expanded_walk_agree_on_the_same_rows(model, weights):
    """One layer's attention over the same latent pages: a chunk [1, 16]
    fills them (expanded, its own rows included); then the token at position
    16 is attended ONCE as a decode step [1, 1] (absorbed: the row is the key
    and, in its first 32 values, the value) and ONCE as a one-page chunk
    [1, 8] from a page boundary (expanded: the cached block up-projected
    first).  Both equal the cache-free layer over all 17 tokens."""
    layer = JoyAIFlashAttention(model.config)
    params = {"params": attention_params(weights)}
    x = jax.random.normal(jax.random.key(1), (1, 24, 64))
    page, tables = 8, jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    pool = jnp.zeros((6, page, ROW))
    view = lambda pool: {"latent_pages": pool, "block_tables": tables, "slots": jnp.zeros((1,), jnp.int32)}
    whole = layer.apply(params, x[:, :17], jnp.arange(17)[None])[0]
    first, state, counts = layer.apply(params, x[:, :16], jnp.arange(16)[None], view(pool),
                                       jnp.ones((1, 16), bool))
    np.testing.assert_allclose(first, whole[:, :16], **TOL)
    assert counts.tolist() == [0, 0, 512]             # one block (64 pages: the table padded to a step) up-projected
    absorbed, after, counts = layer.apply(params, x[:, 16:17], jnp.asarray([[16]]),
                                          view(state["latent_pages"]), jnp.ones((1, 1), bool))
    assert counts.tolist() == [17, 24, 0]             # 17 keys visible, the slot's own 3 pages of 8 read
    live = jnp.arange(8)[None] < 1
    expanded, again, _ = layer.apply(params, x[:, 16:24], 16 + jnp.arange(8)[None],
                                     view(state["latent_pages"]), live)
    np.testing.assert_allclose(absorbed[0, 0], whole[0, 16], **TOL)
    np.testing.assert_allclose(expanded[0, 0], whole[0, 16], **TOL)
    np.testing.assert_array_equal(after["latent_pages"], again["latent_pages"])     # one row landed, the same
    rows = np.asarray(after["latent_pages"])
    assert not rows[:, :, 40:].any() and rows[4, 0, :40].any()        # [c ; kr] then zeros; page 4 = logical 2
    assert not rows[[2, 5]].any() and not rows[0].any()               # no page outside the sequence touched


def test_one_walk_serves_every_kind_of_row():
    """``paged_masked_attention``: two pools of one head width (as before), one
    pool whose row is key and value, and an expansion of the gathered rows
    all come to the attention a dense softmax gives."""
    b, t, h, d, page, n = 2, 1, 4, 16, 8, 4
    q = jax.random.normal(jax.random.key(0), (b, t, h, d))
    pool = jax.random.normal(jax.random.key(1), (16, page, d))
    tables = jnp.asarray(np.random.default_rng(0).permutation(16)[:b * n].reshape(b, n), jnp.int32)
    padded = pw.pad_block_tables(tables, pw.block_pages_for(b, t, h, page))      # one 64-page step
    q_pos = jnp.asarray([[21], [9]], jnp.int32)
    rows = pool[tables].reshape(b, n * page, d)
    seen = jnp.arange(n * page)[None, None] <= q_pos[:, :, None]

    def dense(keys, values, scale):           # keys/values [b, S, h, *]
        s = jnp.einsum("bthd,bshd->bhts", q, keys) * scale
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, values)

    shared = jnp.broadcast_to(rows[:, :, None], (b, n * page, h, d))
    walk = lambda *a, **kw: pw.paged_masked_attention(*a, padded, jnp.max(q_pos) + 1,
                                                      pw.causal_mask(q_pos), **kw)
    np.testing.assert_allclose(walk(q, pool, pool), dense(shared, shared, 0.25), **TOL)
    got = walk(q, pool, None, scale=0.1, value_width=12)
    assert got.shape == (b, t, h, 12)
    np.testing.assert_allclose(got, dense(shared, shared[..., :12], 0.1), **TOL)
    w = jax.random.normal(jax.random.key(2), (d, h, d + 6)) / 4
    expand = lambda r: (jnp.einsum("bsr,rhd->bshd", r, w[..., :d]), jnp.einsum("bsr,rhd->bshd", r, w[..., d:]))
    got = walk(q, pool, None, value_width=6, expand=expand)
    assert got.shape == (b, t, h, 6)
    np.testing.assert_allclose(got, dense(*expand(rows), 0.25), **TOL)
    with pytest.raises(ValueError, match="not both"):
        walk(q, pool, pool, value_width=12)


# -- 3. through the engine: latent pages under the one block table ---------------------------

PLUGIN = dict(num_slots=2, page_size=8, pages_per_slot=12, num_pages=24, prefill_chunk=32,
              prefill_buckets=(16, 32), decode_kernel="native")
GEN = GenerationConfig(max_new_tokens=24, do_sample=False, eos_token_id=None)


def serve(model, weights, prompts, new=12, **over):
    served = {k: v for k, v in weights.items() if not k.startswith("mtp.")}
    eng = ServingEngine(model, family.to_program(served, CFG), ServingPlugin(**{**PLUGIN, **over}),
                        dataclasses.replace(GEN, max_new_tokens=new))
    eng.warmup()
    before = eng.compile_events
    for uid, prompt in prompts.items():
        eng.add_request(Request(uid=uid, prompt=tuple(int(t) for t in prompt), max_new_tokens=new))
    while not eng.idle():
        eng.step()
    assert eng.compile_events == before
    assert verify_serving_invariants(eng) == []
    return eng


class Probe(JoyAIFlashForCausalLM):
    """The model with its paged calls' LOGITS copied out to the host as the
    engine's compiled programs run (the engine itself hands back tokens)."""

    seen = []

    def apply(self, *args, **kwargs):
        out = super().apply(*args, **kwargs)
        if kwargs.get("cache") is not None:
            jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), out[0], ordered=True)
        return out


@pytest.fixture(scope="module")
def probed(weights):
    """One engine with one slot (so a call's logits line up with the row),
    warmed once: its requests reuse the slot back to back."""
    return serve(Probe(family.build_model(CFG, LAYERS, dtype=jnp.float32).config), weights, {},
                 new=20, num_slots=1)


@pytest.mark.parametrize("prompt_len", [5, 8, 9, 32, 33, 37, 64],
                         ids=lambda n: f"prompt_{n}")
def test_paged_programs_give_the_references_logits(probed, weights, prompt_len):
    """Prefill in chunks (the expanded walk), then decode (the absorbed walk),
    through ``ServingEngine`` against the reference's ONE full forward, logits
    compared: prompts that end inside a page, AT a page boundary (8), AT a
    chunk boundary (32, 64), one past either, and several chunks long; every
    decode crosses page boundaries, and every request takes the slot and the
    pages its predecessor left uncleared."""
    prompt = ids_of(100 + prompt_len, prompt_len)
    jax.effects_barrier()
    Probe.seen.clear()
    probed.add_request(Request(uid=prompt_len, prompt=tuple(int(t) for t in prompt), max_new_tokens=20))
    while not probed.idle():
        probed.step()
    jax.effects_barrier()
    assert verify_serving_invariants(probed) == []
    tokens = np.asarray(probed.results[prompt_len])
    row = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    want = reference.row_logits(weights, CFG, LAYERS, row)
    decode = np.concatenate([x[:, 0] for x in Probe.seen if x.shape[1] == 1])      # [19, V]
    np.testing.assert_allclose(decode, want[prompt_len:], **TOL)
    chunks = [x[0] for x in Probe.seen if x.shape[1] > 1]
    at = 0
    for chunk in chunks:            # every REAL position of every prefill chunk
        real = min(chunk.shape[0], prompt_len - at)
        np.testing.assert_allclose(chunk[:real], want[at:at + real], **TOL)
        at += real
    assert at == prompt_len and len(chunks) == -(-prompt_len // 32)


def served_gap(weights, prompt, tokens):
    row = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    logits = reference.row_logits(weights, CFG, LAYERS, row)[len(prompt) - 1:]
    at = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(logits, axis=-1) - at))


def test_a_slot_is_handed_on_without_being_cleared(model, weights):
    """Six requests through two slots, back to back: the pages go round with
    the last tenant's rows in them (nothing is cleared; a row is read only
    below its reader's position, which the reader wrote), and what is served
    is what the reference puts first."""
    prompts = {u: ids_of(40 + u, n) for u, n in enumerate([50, 3, 24, 9, 41, 8])}
    eng = serve(model, weights, prompts, new=18)
    assert eng.metrics["evictions"] == 0 and int(eng.cache["free_top"]) == PLUGIN["num_pages"]
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(eng.results[uid])) < 1e-3
    alone = serve(model, weights, {1: prompts[1]}, new=18)
    assert alone.results[1] == eng.results[1]


def test_eviction_and_readmission_rebuild_the_latent_pages(model, weights):
    """A pool too small for the requests at once: a sequence is evicted and
    readmitted (its prompt and tokens prefilled again into other pages), and
    the tokens stay the reference's."""
    prompts = {u: ids_of(60 + u, 50 + 7 * u) for u in range(3)}
    tight = serve(model, weights, prompts, new=20, num_pages=13)
    assert tight.metrics["evictions"] > 0 and tight.free_page_mirror_in_sync()
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(tight.results[uid])) < 1e-3


def test_the_cache_is_one_latent_pool_a_layer_and_the_accounting_counts_it(model, weights):
    eng = serve(model, weights, {0: ids_of(1, 20)}, new=4)
    kinds = [{k: v.shape for k, v in layer.items()} for layer in eng.cache["layers"]]
    assert kinds == [{"latent_pages": (24, 8, ROW)}] * LAYERS        # [P, page, row]: no head axis
    acct = cache_accounting(model, num_pages=24, page_size=8, num_slots=2, pages_per_slot=12)
    assert acct["paged_layers"] == LAYERS and acct["slot_state_bytes"] == 0
    assert acct["bytes_per_page"] == LAYERS * 8 * ROW * 4             # one row a token-layer (float32 here)
    held = sum(int(a.nbytes) for layer in eng.cache["layers"] for a in layer.values())
    assert acct["pool_bytes"] == held                                 # what the engine really holds
    # at the published sizes: [c (512) ; kr (64)] in 640 lanes, 1,280 B a token-layer in bf16,
    # whatever the number of heads held (a latent has no head axis to divide)
    full = JoyAIFlashForCausalLM(JoyAIFlashConfig(num_hidden_layers=8, attention_heads_held=4,
                                                  experts_held=tuple(range(32)), vocab_held=16160))
    published = cache_accounting(full, num_pages=12672, page_size=64, num_slots=48, pages_per_slot=264)
    assert full.config.latent_row == 640
    assert published["bytes_per_page"] == 8 * 64 * 640 * 2
    assert published["pool_bytes"] == 8 * 12672 * 64 * 1280 == 8_304_721_920


def test_invariants_name_a_layer_that_is_neither_kind(model, weights):
    eng = serve(model, weights, {0: ids_of(1, 20)}, new=4)
    assert verify_serving_invariants(eng) == []                       # a latent pool is a paged kind
    eng.cache["layers"][0]["latent_pages"] = jnp.zeros((3, 8, ROW))   # not [pages, ..], not [slots, ..]
    assert any("latent_pages" in p for p in verify_serving_invariants(eng))


@pytest.mark.parametrize("feature,kwargs", [
    ("LoRA adapters", dict(adapters=object())),
    ("int8/fp8 KV state", dict(plugin=dict(kv_dtype="int8"))),
    ("speculative decode", dict(plugin=dict(speculate="ngram"))),
    ("page transfer", dict(hold_finished=True)),
])
def test_the_engine_refuses_what_it_cannot_do_for_this_family(model, weights, feature, kwargs):
    plugin = ServingPlugin(**{**PLUGIN, **kwargs.pop("plugin", {})})
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, None, plugin, GEN, **kwargs)


def test_the_prefix_cache_changes_where_rows_come_from_never_the_tokens(model, weights):
    """Latent pages are this family's only per-token state and a page's
    identity is its token chain: requests that share a 24-token prefix, served
    with the prefix cache off and on, give the same tokens; with it on the
    later requests' chunks start at the hit boundary and their expanded walk
    reads the shared pages another request wrote."""
    pre = ids_of(5, 24)
    prompts = {u: np.concatenate([pre, ids_of(70 + u, n)]) for u, n in enumerate([9, 3, 17, 6])}
    off = serve(model, weights, prompts, new=10, num_slots=1)
    on = serve(model, weights, prompts, new=10, num_slots=1, prefix_cache="on")
    assert on.results == off.results
    assert on.prefix.stats["prefill_tokens_skipped"] >= 3 * 24        # three pages a later request
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(on.results[uid])) < 1e-3


def test_the_model_reports_its_counters(model, weights):
    eng = serve(model, weights, {u: ids_of(u, 20) for u in range(2)}, new=10)
    m = eng.metrics
    assert m["moe_ticks"] == 2 * m["decode_steps"]                 # two sparse layers a decode tick
    assert 0 < m["moe_rows_held"] <= m["moe_rows_computed"]
    assert len(m["expert_tokens"]) == 8 and m["expert_tokens"].sum() >= m["moe_rows_held"]
    # 2 slots at contexts 21..29 in each of the 3 layers: visible keys are the contexts, and the
    # kernel reads each slot's own whole pages of 8 rows (3 pages up to context 24, then 4)
    assert m["latent_walked_sum"] == 3 * 2 * (4 * 24 + 5 * 32)
    assert m["latent_visible_sum"] == 3 * 2 * sum(range(21, 30))   # each slot: 9 steps at contexts 21..29
    assert m["latent_expanded_sum"] == 2 * 3 * 512                 # one chunk a prompt, one block a layer


# -- 4. the share: all the shares' parts add up to the uncut layer -----------------------------


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """Two chips share each layer: the held heads' output-projection partials
    and the held experts' partials of BOTH shares, with the shared expert, the
    residual, the norms and the latent projections (whole on every chip)
    counted once, add up to what the uncut reference gives for the whole
    layer; and the held vocabulary rows are those rows of the full logits."""
    whole = f32(make_weights(family.weight_shapes(BASE, 2), seed=11))
    full = family.build_model(BASE, 2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(3), (1, 24, 64)) * 2.0
    pos = jnp.arange(24)[None]
    key = reference.cfg_key(BASE)
    ang = reference.angles(np.arange(24), 8, 32e6)
    want, _ = reference.layer(whole, "layers.1", x[0], ang, 24, key, None, reference.NO_FAULT,
                              sparse=True)
    lw = {k: whole[f"layers.1.{k}"] for k in family.LEAVES if f"layers.1.{k}" in whole}
    ordered = family.to_program({f"layers.1.{k}": lw[k] for k in ("q_b", "kv_a")},
                                BASE)["params"]["layers_1"]["self_attn"]
    q_b, kv_a = ordered["q_b_proj"]["kernel"], ordered["kv_a_proj_with_mqa"]["kernel"]
    norm = lambda v, w: reference.rms_norm(v, w, 1e-6)
    attn_parts, moe_parts = [], []
    for rank in range(2):
        cfg = dataclasses.replace(full.config, attention_heads_held=2)
        heads = lambda per: slice(rank * 2 * per, (rank + 1) * 2 * per)
        attn = JoyAIFlashAttention(cfg).apply(
            {"params": {"q_a_proj": {"kernel": lw["q_a"]}, "q_a_layernorm": {"scale": lw["q_a_norm"]},
                        "kv_a_proj_with_mqa": {"kernel": kv_a}, "kv_a_layernorm": {"scale": lw["kv_a_norm"]},
                        "q_b_proj": {"kernel": q_b[:, heads(24)]}, "kv_b_proj": lw["kv_b"][:, heads(32)],
                        "o_proj": {"kernel": lw["o"][heads(16)]}}},
            norm(x, lw["attn_norm"]), pos)[0]
        attn_parts.append(attn)
    h = x + sum(attn_parts)                                   # the residual once, the partials joined
    n = norm(h, lw["mlp_norm"])
    for rank in range(2):
        held = tuple(range(8 * rank, 8 * rank + 8))
        cfg = dataclasses.replace(full.config, experts_held=held, n_shared_experts=0)
        y, _, _ = KExaoneSparseMoE(cfg).apply(
            {"params": {"gate": {"kernel": lw["router"]}, "e_score_correction_bias": lw["router_bias"],
                        **{f"experts_{k}_proj": lw[k][jnp.asarray(held)] for k in ("gate", "up", "down")}}},
            n)
        moe_parts.append(y)
    shared = reference.kx.swiglu(n[0], lw["shared_gate"], lw["shared_up"], lw["shared_down"], key, None)
    np.testing.assert_allclose((h + sum(moe_parts))[0] + shared, want, rtol=1e-4, atol=1e-4)
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in attn_parts + moe_parts)
    # the vocabulary: a chip that holds rows 0-127 of 256 computes those columns of the full logits
    ids = ids_of(9, 12)
    rows = lambda w, cols: {**w, "embed": w["embed"][cols], "head": w["head"][:, cols]}
    half = {**BASE, "vocab_size": 128, "published": {**CFG["published"], "num_hidden_layers": 2}}
    got = family.build_model(half, 2, dtype=jnp.float32).apply(
        family.to_program(rows(whole, slice(0, 128)), half), jnp.asarray(ids[None]))
    np.testing.assert_allclose(got[0], reference.row_logits(whole, BASE, 2, ids)[:, :128], **TOL)


# -- 5. the published checkpoint's names -----------------------------------------------------


def test_hf_names_load_into_the_tree_the_benchmark_builds():
    """``load_hf_joyai_flash`` (DeepSeek-V3's tensor names, assumed): torch
    ``[out, in]`` tensors under ``model.``, ``kv_b_proj`` as one tensor (rows
    head-major, ``[W_UK,h ; W_UV,h]``), the selection bias under the router,
    one tensor per expert, and the rotary columns of ``q_b_proj`` and
    ``kv_a_proj_with_mqa`` de-interleaved on the way in - the tree
    ``to_program`` builds from the same published-order weights."""
    from accelerate_tpu.models import hf_joyai_flash_key_map, load_hf_joyai_flash

    layers = 2
    whole = f32(make_weights(family.weight_shapes(BASE, layers), seed=5))
    block = {"q_a": "self_attn.q_a_proj", "q_a_norm": "self_attn.q_a_layernorm",
             "q_b": "self_attn.q_b_proj", "kv_a": "self_attn.kv_a_proj_with_mqa",
             "kv_a_norm": "self_attn.kv_a_layernorm", "kv_b": "self_attn.kv_b_proj",
             "o": "self_attn.o_proj", "attn_norm": "input_layernorm",
             "mlp_norm": "post_attention_layernorm", "router": "mlp.gate",
             "mlp_gate": "mlp.gate_proj", "mlp_up": "mlp.up_proj", "mlp_down": "mlp.down_proj",
             "shared_gate": "mlp.shared_experts.gate_proj", "shared_up": "mlp.shared_experts.up_proj",
             "shared_down": "mlp.shared_experts.down_proj"}
    top = {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"}
    pairs = [("lm_head.weight", np.asarray(whole["head"]).T),
             ("model.layers.0.self_attn.rotary_emb.inv_freq", np.zeros((4,), np.float32))]
    for name, arr in whole.items():
        arr = np.asarray(arr)
        if name in top:
            pairs.append((top[name], arr))
        elif name.startswith("layers."):
            _, i, leaf = name.split(".")
            at = f"model.layers.{i}"
            if leaf == "router_bias":
                pairs.append((f"{at}.mlp.gate.e_score_correction_bias", arr))
            elif leaf in block:
                pairs.append((f"{at}.{block[leaf]}.weight", arr.T if arr.ndim == 2 else arr))
            else:
                pairs += [(f"{at}.mlp.experts.{e}.{leaf}_proj.weight", arr[e].T)
                          for e in range(arr.shape[0])]
    model = family.build_model(BASE, layers, dtype=jnp.float32)
    params, _ = load_hf_joyai_flash(model, pairs, dtype=jnp.float32)
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(params), flat(family.to_program(whole, BASE))
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert hf_joyai_flash_key_map("model.layers.3.self_attn.kv_b_proj.weight") == \
        "params.layers_3.self_attn.kv_b_proj"
    assert hf_joyai_flash_key_map("model.mtp.block.mlp.experts_stacked.up_proj") == \
        "params.mtp.block.mlp.experts_up_proj"
    assert hf_joyai_flash_key_map("model.mtp.eh_proj.weight") == "params.mtp.eh_proj.kernel"
