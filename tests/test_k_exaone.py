"""K-EXAONE's language model (``models/k_exaone.py``: window and
full-attention layers in one cache, sigmoid-routed experts beside a shared
expert, a dense first layer, the chip's share of heads, experts and
vocabulary) against its plain reference (``perfbench/reference/k_exaone.py``:
float32 ``jax.numpy``, no kernel, no cache), at tiny sizes on seeded weights.
LOGITS are compared, never tokens alone: with random weights the largest
logit changes on rounding."""

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
from accelerate_tpu.models import KExaoneConfig, KExaoneForCausalLM  # noqa: E402
from accelerate_tpu.models.k_exaone import KExaoneAttention, KExaoneSparseMoE  # noqa: E402
from accelerate_tpu.parallel.expert_parallel import (grouped_ffn, held_row_block,  # noqa: E402
                                                     held_rows_fed, route_dropless)
from accelerate_tpu.serving import (Request, ServingEngine, cache_accounting,  # noqa: E402
                                    verify_serving_invariants)
from accelerate_tpu.utils.dataclasses import ServingPlugin  # noqa: E402
from perfbench.families import k_exaone as family  # noqa: E402
from perfbench.reference import k_exaone as reference  # noqa: E402
from perfbench.weights import make_weights  # noqa: E402

WINDOW = 8
LLLG = ["sliding_attention"] * 3 + ["full_attention"]
BASE = {   # the published config's keys at test scale, held whole
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "layer_types": LLLG * 2, "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "first_k_dense_replace": 1, "sliding_window": WINDOW, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "num_nextn_predict_layers": 1, "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False,
    "assumed": {"weight_scales": {"embed": 2.0, "router": 2.0, "router_bias": 0.2}},
}
# rank 0 of two chips that share each layer: half the heads, experts and vocabulary
CFG = {**BASE, "vocab_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
       "num_experts": 8,
       "published": {"num_hidden_layers": 8, "num_experts": 16, "num_attention_heads": 4,
                     "num_key_value_heads": 2, "vocab_size": 256},
       "share": {"chips_per_layer": 2, "rank": 0, "experts_held": list(range(8))}}
LAYERS = 8
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
    assert shapes["layers_1"]["self_attn"]["q_proj"]["kernel"] == (64, 2 * 32)      # 2 of 4 heads
    assert shapes["layers_1"]["self_attn"]["k_proj"]["kernel"] == (64, 1 * 32)      # 1 of 2 KV heads
    assert shapes["layers_1"]["mlp"]["experts_gate_proj"] == (8, 64, 32)            # 8 of 16 experts
    assert shapes["layers_1"]["mlp"]["gate"]["kernel"] == (64, 16)                  # the router: all 16
    assert shapes["layers_1"]["mlp"]["e_score_correction_bias"] == (16,)
    assert shapes["layers_0"]["mlp"]["gate_proj"]["kernel"] == (64, 96)             # the dense layer
    assert shapes["lm_head"]["kernel"] == (64, 128) and shapes["embed_tokens"]["embedding"] == (128, 64)
    given = jax.tree.map(lambda x: x.shape, family.to_program(weights)["params"])
    assert given == shapes                       # the benchmark makes exactly these, MTP included


@pytest.mark.parametrize("length", [6, 40], ids=["inside_the_window", "five_windows"])
def test_forward_matches_the_reference(model, weights, length):
    """Both layer kinds, the dense first layer, the share — and the MTP
    module's logits — with the window smaller than the sequence."""
    ids = ids_of(length, length)
    logits, mtp = model.apply(family.to_program(weights), jnp.asarray(ids[None]), output_mtp=True)
    np.testing.assert_allclose(logits[0], reference.row_logits(weights, CFG, LAYERS, ids), **TOL)
    np.testing.assert_allclose(mtp[0], reference.mtp_logits(weights, CFG, LAYERS, ids), **TOL)
    assert mtp.shape == (1, length - 1, CFG["vocab_size"])


def test_the_window_and_the_missing_rotary_are_what_the_layer_kinds_say(model, weights):
    """A token 3 windows back cannot move a window layer's output and does
    move a full-attention layer's; a full-attention layer's output does not
    change when every position is shifted (no rotary), a window layer's
    scores depend only on position differences (rotary)."""
    cfg = model.config
    x = jax.random.normal(jax.random.key(1), (1, 32, 64))
    far = x.at[0, 2].add(1.0)
    pos = jnp.arange(32)[None]
    for kind, leaf in (("sliding_attention", "layers.1"), ("full_attention", "layers.3")):
        params = {"params": {path[1]: {path[2]: weights[f"{leaf}.{short}"]}
                             for short, path in family.ATTN.items() if path[0] == "self_attn"}}
        layer = KExaoneAttention(cfg, kind)
        out = lambda inp, p=pos: layer.apply(params, inp, p)[0][0]
        moved = float(jnp.max(jnp.abs(out(far)[-1] - out(x)[-1])))
        assert (moved == 0.0) if kind == "sliding_attention" else (moved > 1e-3)
        np.testing.assert_allclose(out(x, pos + 100), out(x), **TOL)   # both: shift-invariant


# -- 2. through the engine: pages for the global layers, a ring a slot for the window layers --

PLUGIN = dict(num_slots=2, page_size=8, pages_per_slot=12, num_pages=24, prefill_chunk=32,
              prefill_buckets=(16, 32), decode_kernel="native")
GEN = GenerationConfig(max_new_tokens=24, do_sample=False, eos_token_id=None)


def serve(model, weights, prompts, new=12, **over):
    served = {k: v for k, v in weights.items() if not k.startswith("mtp.")}
    eng = ServingEngine(model, family.to_program(served), ServingPlugin(**{**PLUGIN, **over}),
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


class Probe(KExaoneForCausalLM):
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


@pytest.mark.parametrize("prompt_len", [5, 8, 9, 16, 17, 37, 64],
                         ids=lambda n: f"prompt_{n}")
def test_paged_programs_give_the_references_logits(probed, weights, prompt_len):
    """Prefill in chunks, then decode, through ``ServingEngine`` against the
    reference's ONE full forward, logits compared: prompts that end inside
    the first window, AT a ring wrap (8, 16, 64), one past it, and several
    chunks long; every context passes the ring's length (8) many times, and
    every request takes the slot its predecessor left uncleared."""
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


def test_a_slot_is_reused_without_being_cleared(model, weights):
    """Six requests through two slots, back to back: a ring row left by the
    slot's last tenant is never read (its position lies outside ``(t -
    window, t]`` of the request that owns the slot now), the pages go round,
    and what is served is what the reference puts first."""
    prompts = {u: ids_of(40 + u, n) for u, n in enumerate([50, 3, 24, 9, 41, 8])}
    eng = serve(model, weights, prompts, new=18)
    assert eng.metrics["evictions"] == 0 and int(eng.cache["free_top"]) == PLUGIN["num_pages"]
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(eng.results[uid])) < 1e-3
    # the short tenant after the long one in the same slot saw none of its rows
    alone = serve(model, weights, {1: prompts[1]}, new=18)
    assert alone.results[1] == eng.results[1]


def test_eviction_and_readmission_rebuild_the_rings(model, weights):
    """A pool too small for the requests at once: a sequence is evicted and
    readmitted (its prompt and tokens prefilled again, so its rings are
    rebuilt by the chunks), and the tokens stay the reference's."""
    prompts = {u: ids_of(60 + u, 50 + 7 * u) for u in range(3)}
    tight = serve(model, weights, prompts, new=20, num_pages=13)
    assert tight.metrics["evictions"] > 0 and tight.free_page_mirror_in_sync()
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(tight.results[uid])) < 1e-3


def test_the_cache_holds_two_kinds_of_state_and_the_accounting_counts_both(model, weights):
    eng = serve(model, weights, {0: ids_of(1, 20)}, new=4)
    kinds = [{k: v.shape for k, v in layer.items()} for layer in eng.cache["layers"]]
    ring = {"k_ring": (2, WINDOW, 32), "v_ring": (2, WINDOW, 32)}        # [slots, window, Hkv * D]
    pages = {"k_pages": (24, 8, 32), "v_pages": (24, 8, 32)}             # [P, page, Hkv * D]
    assert kinds == [ring, ring, ring, pages] * 2
    acct = cache_accounting(model, num_pages=24, page_size=8, num_slots=2, pages_per_slot=12)
    assert acct["paged_layers"] == 2
    assert acct["bytes_per_page"] == 2 * 2 * 8 * 32 * 4          # K + V, the 2 paged layers only (float32 here)
    assert acct["slot_state_bytes"] == 2 * (2 * 6 * WINDOW * 32 * 4)     # 6 window layers' rings
    held = sum(int(a.nbytes) for layer in eng.cache["layers"] for a in layer.values())
    assert acct["pool_bytes"] + acct["slot_state_bytes"] == held          # what the engine really holds
    # a window layer's bytes per slot do not grow with the context: no page count in them
    assert cache_accounting(model, 240, 8, 2, 120)["slot_state_bytes"] == acct["slot_state_bytes"]


def test_invariants_name_a_layer_that_is_neither_kind(model, weights):
    eng = serve(model, weights, {0: ids_of(1, 20)}, new=4)
    eng.cache["layers"][0]["k_ring"] = jnp.zeros((3, WINDOW, 32))        # not [slots, ..], not [pages, ..]
    assert any("k_ring" in p for p in verify_serving_invariants(eng))


@pytest.mark.parametrize("feature,kwargs", [
    ("LoRA adapters", dict(adapters=object())),
    ("int8/fp8 KV state", dict(plugin=dict(kv_dtype="int8"))),
    ("speculative decode", dict(plugin=dict(speculate="ngram"))),
    ("prefix-cache hashing", dict(plugin=dict(prefix_cache="on"))),
    ("page transfer", dict(hold_finished=True)),
])
def test_the_engine_refuses_what_it_cannot_do_for_this_family(model, weights, feature, kwargs):
    plugin = ServingPlugin(**{**PLUGIN, **kwargs.pop("plugin", {})})
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, None, plugin, GEN, **kwargs)


# -- 3. the share: all the shares' parts add up to the uncut layer -----------------------------


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """Two chips share each layer: the attention heads' output-projection
    partials and the experts' partials of BOTH shares, with the shared
    expert, the residual and the norms counted once, add up to what the
    uncut reference gives for the whole layer."""
    whole = f32(make_weights(family.weight_shapes(BASE, 2), seed=11))
    full_cfg = family.build_model(BASE, 2, dtype=jnp.float32).config
    x = jax.random.normal(jax.random.key(3), (1, 24, 64)) * 2.0
    pos = jnp.arange(24)[None]
    key = reference.cfg_key(BASE)
    ang = reference.angles(np.arange(24), 32, 1e6)
    want, _ = reference.layer(whole, "layers.1", x[0], ang, 24, key, None, reference.NO_FAULT,
                              sliding=True, sparse=True)
    d, lw = 32, {k: whole[f"layers.1.{k}"] for k in family.LEAVES if f"layers.1.{k}" in whole}
    norm = lambda v, w: reference.rms_norm(v, w, 1e-5)
    attn_parts, moe_parts = [], []
    for rank in range(2):
        cfg = dataclasses.replace(full_cfg, attention_heads_held=2, key_value_heads_held=1,
                                  experts_held=tuple(range(8 * rank, 8 * rank + 8)))
        q_cols = slice(rank * 2 * d, (rank + 1) * 2 * d)
        kv_cols = slice(rank * d, (rank + 1) * d)
        attn = KExaoneAttention(cfg, "sliding_attention").apply(
            {"params": {"q_proj": {"kernel": lw["q"][:, q_cols]}, "k_proj": {"kernel": lw["k"][:, kv_cols]},
                        "v_proj": {"kernel": lw["v"][:, kv_cols]}, "o_proj": {"kernel": lw["o"][q_cols]},
                        "q_norm": {"scale": lw["q_norm"]}, "k_norm": {"scale": lw["k_norm"]}}},
            norm(x, lw["attn_norm"]), pos)[0]
        attn_parts.append(attn)
    h = x + sum(attn_parts)                                   # the residual once, the partials joined
    n = norm(h, lw["mlp_norm"])
    for rank in range(2):
        held = tuple(range(8 * rank, 8 * rank + 8))
        cfg = dataclasses.replace(full_cfg, experts_held=held, num_shared_experts=0)
        y, counts, _ = KExaoneSparseMoE(cfg).apply(
            {"params": {"gate": {"kernel": lw["router"]}, "e_score_correction_bias": lw["router_bias"],
                        **{f"experts_{k}_proj": lw[k][jnp.asarray(held)] for k in ("gate", "up", "down")}}},
            n)
        moe_parts.append(y)
    shared = reference.swiglu(n[0], lw["shared_gate"], lw["shared_up"], lw["shared_down"], key, None)
    np.testing.assert_allclose((h + sum(moe_parts))[0] + shared, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.max(jnp.abs(moe_parts[0]))) > 0 and float(jnp.max(jnp.abs(moe_parts[1]))) > 0


# -- 4. the router -------------------------------------------------------------------------------


def test_sigmoid_scores_a_selection_bias_and_scaled_gates():
    logits = jax.random.normal(jax.random.key(5), (64, 16)) * 2.0
    bias = jnp.zeros((16,)).at[3].set(5.0).at[11].set(-5.0)         # 3 always chosen, 11 never
    r = route_dropless(logits, 4, scoring="sigmoid", select_bias=bias, gate_scale=2.5)
    plain = route_dropless(logits, 4, scoring="sigmoid", gate_scale=2.5)
    s = jax.nn.sigmoid(logits)
    assert bool(jnp.all(jnp.any(r.experts == 3, axis=-1))) and not bool(jnp.any(r.experts == 11))
    assert bool(jnp.any(jnp.sort(r.experts) != jnp.sort(plain.experts)))   # by score + bias, not by score
    chosen = jnp.take_along_axis(s, r.experts, axis=-1)                     # gates: the UNBIASED scores
    np.testing.assert_allclose(r.weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(r.weights.sum(-1), 2.5, rtol=1e-6)
    _, top = jax.lax.top_k(s + bias, 4)
    assert bool(jnp.all(jnp.sort(top) == jnp.sort(r.experts)))
    with pytest.raises(ValueError, match="scoring"):
        route_dropless(logits, 4, scoring="tanh")


def test_the_default_route_is_the_softmax_top_k_it_always_was():
    """``keye-vl2.serve_long``'s programs must not change: the defaults trace
    the same operations (softmax -> top_k -> renormalise), and a call that
    spells them out traces them too."""
    logits = jax.ShapeDtypeStruct((24, 16), jnp.float32)
    default = jax.make_jaxpr(lambda l: route_dropless(l, 4))(logits)
    spelled = jax.make_jaxpr(lambda l: route_dropless(
        l, 4, scoring="softmax", select_bias=None, gate_scale=1.0))(logits)
    assert str(default) == str(spelled)
    names = [e.primitive.name for e in default.jaxpr.eqns]
    assert "logistic" not in names and names.count("top_k") == 1
    x = jax.random.normal(jax.random.key(0), (24, 16))
    r = route_dropless(x, 4)
    gate, experts = jax.lax.top_k(jax.nn.softmax(x, axis=-1), 4)
    np.testing.assert_array_equal(r.experts, experts)
    np.testing.assert_allclose(r.weights, gate / gate.sum(-1, keepdims=True), rtol=1e-6)


# -- 5. the grouped matmul with a share ----------------------------------------------------------


def expert_loop(x, routing, held, wg, wu, wd):
    """The held experts' part, an expert at a time (the test's own spelling)."""
    y = jnp.zeros((x.shape[0], wd.shape[-1]))
    for slot, e in enumerate(held):
        g = jnp.sum(jnp.where(routing.experts == e, routing.weights, 0.0), axis=-1)
        y = y + g[:, None] * ((jax.nn.silu(x @ wg[slot]) * (x @ wu[slot])) @ wd[slot])
    return y


@pytest.mark.parametrize("routing_kind", ["even", "all_to_the_held", "none_to_the_held"])
def test_a_share_computes_no_row_of_an_absent_expert(routing_kind):
    """2 of 16 experts held: the gather and the matmuls walk the held rows
    only — ``moe_rows_computed`` is ``moe_rows_held`` up to one block's
    padding — whatever the routing, and no token is dropped."""
    n, k, e, h, f, held = 256, 4, 16, 32, 16, (5, 9)
    x = jax.random.normal(jax.random.key(1), (n, h))
    wg, wu = (jax.random.normal(jax.random.key(i), (2, h, f)) / 6 for i in (2, 3))
    wd = jax.random.normal(jax.random.key(4), (2, f, h)) / 4
    logits = jax.random.normal(jax.random.key(6), (n, e))
    if routing_kind == "all_to_the_held":
        logits = logits.at[:, jnp.asarray(held)].add(20.0)
    if routing_kind == "none_to_the_held":
        logits = logits.at[:, jnp.asarray(held)].add(-20.0)
    routing = route_dropless(logits, k, held, scoring="sigmoid", gate_scale=2.5)
    y, computed = grouped_ffn(x, routing, wg, wu, wd), held_rows_fed(routing)
    np.testing.assert_allclose(y, expert_loop(x, routing, held, wg, wu, wd), rtol=2e-4, atol=2e-4)
    rows_held, block = int(routing.group_sizes.sum()), held_row_block(routing)
    assert block == 256 and block < n * k                        # 128 expected, a quarter over, in tiles
    assert rows_held <= int(computed) < rows_held + block         # equal up to block padding
    assert rows_held == {"all_to_the_held": 2 * n, "none_to_the_held": 0}.get(routing_kind, rows_held)
    jaxpr = str(jax.make_jaxpr(lambda x: grouped_ffn(x, routing, wg, wu, wd))(x))
    assert f"f32[{block},{h}]" in jaxpr and f"f32[{n * k},{h}]" not in jaxpr   # no N x k rows anywhere


def test_the_model_reports_rows_held_and_rows_computed(model, weights):
    eng = serve(model, weights, {u: ids_of(u, 20) for u in range(2)}, new=10)
    m = eng.metrics
    assert m["moe_ticks"] == 7 * m["decode_steps"]                # seven sparse layers a decode tick
    assert 0 < m["moe_rows_held"] <= m["moe_rows_computed"]
    assert m["moe_rows_computed"] <= m["moe_rows_held"] + m["moe_ticks"] * 8     # a block of 2 slots x 4 choices
    assert len(m["expert_tokens"]) == 8 and m["expert_tokens"].sum() >= m["moe_rows_held"]
    assert m["window_visible_sum"] < m["global_visible_sum"]      # 6 layers x <= 8 keys against 2 x context


def test_a_decode_step_reads_each_live_querys_own_whole_pages(model, weights):
    """``global_walked_sum``: rows of the pages the ``paged_walk_decode``
    kernel read, beside ``global_visible_sum``, the keys its live queries may
    see: never fewer, and less than one page of rows more for each live query
    of each of the two full-attention layers (contexts of 7 .. 52 keys over
    pages of 8: the walk that gathers every slot to the longest live context
    in whole blocks would read several pages more for the short ones)."""
    eng = serve(model, weights, {0: ids_of(0, 40), 1: ids_of(1, 6), 2: ids_of(2, 23)}, new=12)
    m = eng.metrics
    queries = 2 * m["decode_lane_passes"]           # a live slot of a decode step, in two layers
    assert m["decode_steps"] > 0 and queries >= 2 * 3 * 11
    assert m["global_visible_sum"] <= m["global_walked_sum"] < m["global_visible_sum"] + 8 * queries
    assert m["global_walked_sum"] % 8 == 0 and m["global_walked_sum"] > m["global_visible_sum"]


# -- 6. the published checkpoint's names -----------------------------------------------------


def test_hf_names_load_into_the_tree_the_benchmark_builds():
    """``load_hf_k_exaone``: torch ``[out, in]`` tensors under ``model.``,
    one tensor per expert, the selection bias under the router, the shared
    expert and the dense layer's MLP by their own names."""
    from accelerate_tpu.models import hf_k_exaone_key_map, load_hf_k_exaone

    layers = 2
    whole = f32(make_weights(family.weight_shapes(BASE, layers), seed=5))
    block = {"q": "self_attn.q_proj", "k": "self_attn.k_proj", "v": "self_attn.v_proj",
             "o": "self_attn.o_proj", "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
             "attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm",
             "router": "mlp.gate", "mlp_gate": "mlp.gate_proj", "mlp_up": "mlp.up_proj",
             "mlp_down": "mlp.down_proj", "shared_gate": "mlp.shared_experts.gate_proj",
             "shared_up": "mlp.shared_experts.up_proj", "shared_down": "mlp.shared_experts.down_proj"}
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
    params, _ = load_hf_k_exaone(model, pairs, dtype=jnp.float32)
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(params), flat(family.to_program(whole))
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert hf_k_exaone_key_map("model.mtp.block.mlp.experts_stacked.up_proj") == \
        "params.mtp.block.mlp.experts_up_proj"
    assert hf_k_exaone_key_map("model.mtp.eh_proj.weight") == "params.mtp.eh_proj.kernel"
