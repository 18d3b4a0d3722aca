"""Qwen3-Next's language model (``models/qwen3_next.py``: Gated DeltaNet layers
whose recurrent state lives per slot beside the full-attention layers' pages,
gated attention, softmax-routed experts beside a gated shared expert, the
chip's share of heads, experts and vocabulary) against its plain reference
(``perfbench/reference/qwen3_next.py``: float32 ``jax.numpy``, the token
recurrence, no kernel, no cache), at tiny sizes on seeded weights.  LOGITS are
compared, never tokens alone: with random weights the largest logit changes
on rounding."""

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
from accelerate_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM  # noqa: E402
from accelerate_tpu.models.qwen3_next import (Qwen3NextAttention, Qwen3NextGatedDeltaNet,  # noqa: E402
                                              Qwen3NextSparseMoE)
from accelerate_tpu.ops import gated_delta as gd  # noqa: E402
from accelerate_tpu.serving import (Request, ServingEngine, cache_accounting,  # noqa: E402
                                    verify_serving_invariants)
from accelerate_tpu.utils.dataclasses import ServingPlugin  # noqa: E402
from perfbench.families import qwen3_next as family  # noqa: E402
from perfbench.reference import qwen3_next as reference  # noqa: E402
from perfbench.weights import make_weights  # noqa: E402

BASE = {   # the published config's keys at test scale, held whole
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "partial_rotary_factor": 0.25, "full_attention_interval": 4,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_num_key_heads": 4, "linear_num_value_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "rope_scaling": None, "tie_word_embeddings": False,
    "assumed": {"weight_scales": {"embed": 2.0, "router": 2.0, "norm": 0.1, "q_norm": 3.0,
                                  "conv": 1.0, "A_log": 1.0, "A_log_mean": -4.0, "dt_bias": 0.5},
                "prefill_chunk": 16},
}
# rank 0 of two chips that share each layer: half the heads, experts and vocabulary
CFG = {**BASE, "vocab_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
       "linear_num_key_heads": 2, "linear_num_value_heads": 4, "num_experts": 8,
       "published": {"num_hidden_layers": 8, "num_experts": 16, "num_attention_heads": 4,
                     "num_key_value_heads": 2, "linear_num_key_heads": 4,
                     "linear_num_value_heads": 8, "vocab_size": 256},
       "share": {"chips_per_layer": 2, "rank": 0, "experts_held": list(range(8))}}
LAYERS = 8                           # linear, linear, linear, full, twice
TOL = dict(rtol=2e-4, atol=2e-4)     # float32 both sides; the orders of summation differ


def f32(made):
    """Seeded bf16 values held in float32, so program and reference read the same numbers."""
    return {k: jnp.asarray(v, jnp.float32) for k, v in made.items()}


@pytest.fixture(scope="module")
def weights():
    return f32(make_weights(family.weight_shapes(CFG, LAYERS), seed=7))


@pytest.fixture(scope="module")
def model():
    return family.build_model(CFG, LAYERS, dtype=jnp.float32)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(1, CFG["vocab_size"], n).astype(np.int32)


# -- 1. the forward with no cache ------------------------------------------------------


def test_the_model_builds_exactly_the_weights_it_holds(model, weights):
    shapes = jax.tree.map(lambda x: x.shape, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"])
    mixer = shapes["layers_0"]["linear_attn"]
    assert mixer["in_proj_qkvz"]["kernel"] == (64, 2 * (2 * 16 + 2 * 2 * 16))       # 2 of 4 key heads' groups
    assert mixer["in_proj_ba"]["kernel"] == (64, 2 * 4) and mixer["conv1d"] == (4, 2 * 32 + 64)
    assert mixer["A_log"] == mixer["dt_bias"] == (4,) and mixer["norm"] == (16,)    # 4 of 8 value heads
    assert mixer["out_proj"]["kernel"] == (64, 64)
    assert shapes["layers_3"]["self_attn"]["q_proj"]["kernel"] == (64, 2 * 2 * 32)  # 2 of 4 heads: query and gate
    assert shapes["layers_3"]["self_attn"]["k_proj"]["kernel"] == (64, 1 * 32)      # 1 of 2 KV heads
    assert shapes["layers_1"]["mlp"]["experts_gate_proj"] == (8, 64, 32)            # 8 of 16 experts
    assert shapes["layers_1"]["mlp"]["gate"]["kernel"] == (64, 16)                  # the router: all 16
    assert shapes["layers_1"]["mlp"]["shared_expert_gate"]["kernel"] == (64, 1)
    assert "self_attn" not in shapes["layers_0"] and "linear_attn" not in shapes["layers_7"]
    assert shapes["lm_head"]["kernel"] == (64, 128) and shapes["embed_tokens"]["embedding"] == (128, 64)
    given = jax.tree.map(lambda x: x.shape, family.to_program(weights, CFG)["params"])
    assert given == shapes                       # the benchmark makes exactly these


@pytest.mark.parametrize("dtype,length,tol", [
    (jnp.float32, 6, TOL), (jnp.float32, 150, TOL),
    (jnp.bfloat16, 150, dict(rtol=0.0, atol=0.3)),       # bf16 operands: rounding, not a missing term
], ids=["float32_short", "float32_three_blocks", "bfloat16"])
def test_forward_matches_the_reference(weights, dtype, length, tol):
    """Every layer kind (linear x 3, full, twice over), the share, the gated
    shared expert: the program's chunked form (blocks of 64) against the
    reference's token recurrence."""
    ids = ids_of(length, length)
    logits = family.build_model(CFG, LAYERS, dtype=dtype).apply(
        family.to_program(weights, CFG), jnp.asarray(ids[None]))
    want = reference.row_logits(weights, CFG, LAYERS, ids)
    np.testing.assert_allclose(np.asarray(logits[0], np.float32), want, **tol)
    assert float(jnp.std(want)) > 0.5            # the tolerance is small beside the logits


def test_the_layer_kinds_are_what_the_config_says(model, weights):
    """A Gated DeltaNet layer's output at ``t`` depends on the order of the
    tokens before it only through the conv and the state (no positions); a
    full-attention layer rotates the first quarter of a head's dims only:
    shifting every position leaves its output unchanged (relative rotary),
    and its output gate is read from ``q_proj``."""
    cfg = model.config
    assert cfg.kinds == ("linear_attention",) * 3 + ("full_attention",) + ("linear_attention",) * 3 \
        + ("full_attention",) and cfg.rotary_dim == 8
    x = jax.random.normal(jax.random.key(1), (1, 24, 64))
    pos = jnp.arange(24)[None]
    tree = family.to_program(weights, CFG)["params"]
    linear = lambda p: Qwen3NextGatedDeltaNet(cfg).apply({"params": tree["layers_0"]["linear_attn"]}, x, p)[0]
    np.testing.assert_array_equal(linear(pos), linear(pos + 100))          # no position enters
    attn = tree["layers_3"]["self_attn"]
    full = lambda p, params=attn: Qwen3NextAttention(cfg).apply({"params": params}, x, p)[0]
    np.testing.assert_allclose(full(pos), full(pos + 100), **TOL)
    closed = jax.tree.map(lambda a: a, attn)
    kernel = attn["q_proj"]["kernel"].reshape(64, 2, 2, 32).at[:, :, 1].set(0.0)    # gate logits 0
    closed["q_proj"] = {"kernel": kernel.reshape(64, -1)}
    assert float(jnp.max(jnp.abs(full(pos, closed)))) > 0                   # sigmoid(0) = 1/2, not 0
    assert float(jnp.max(jnp.abs(full(pos, closed) - full(pos)))) > 1e-3


# -- 2. the two forms of the recurrence ----------------------------------------------------


def _rule_inputs(seed, t, hv=3, d=16, decay=(0.9, 0.999)):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = gd.l2norm(normal(t, hv, d)) * d ** -0.5, gd.l2norm(normal(t, hv, d))
    g = jnp.log(jnp.asarray(rng.uniform(*decay, size=(t, hv)), jnp.float32))
    beta = jnp.asarray(rng.uniform(0.0, 1.0, size=(t, hv)), jnp.float32)
    return q, k, normal(t, hv, d), g, beta, normal(hv, d, d)


def _recurrence(s0, q, k, v, g, beta):
    return reference._delta_scan(s0, q, k, v, g, beta, reference.NO_FAULT,
                                 key=reference.cfg_key(CFG))


@pytest.mark.parametrize("t,decay", [
    (5, (0.9, 0.999)), (63, (0.9, 0.999)), (64, (0.9, 0.999)), (65, (0.9, 0.999)),
    (200, (0.9, 0.999)), (130, (1e-6, 1e-3)), (130, (0.99999, 1.0)), (130, (1e-6, 1.0)),
], ids=["shorter_than_a_block", "one_short_of_a_block", "a_block", "one_past_a_block",
        "three_blocks_and_a_bit", "decay_near_0", "decay_near_1", "decay_of_every_size"])
def test_the_chunked_form_is_the_token_recurrence(t, decay):
    q, k, v, g, beta, s0 = _rule_inputs(t, t, decay=decay)
    o, s = gd.gated_delta_chunk(q, k, v, g, beta, s0)
    want_o, want_s = _recurrence(s0, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)
    # the state is handed on: two chunks give what one gives
    cut = t // 2
    first = gd.gated_delta_chunk(q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], s0)
    second = gd.gated_delta_chunk(q[cut:], k[cut:], v[cut:], g[cut:], beta[cut:], first[1])
    np.testing.assert_allclose(jnp.concatenate([first[0], second[0]]), want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(second[1], want_s, rtol=1e-4, atol=1e-5)


def test_positions_that_are_not_live_change_nothing_in_the_chunked_form():
    q, k, v, g, beta, s0 = _rule_inputs(3, 40)
    live = jnp.arange(40) < 23
    o, s = gd.gated_delta_chunk(q, k, v, jnp.where(live[:, None], g, 0.0),
                                jnp.where(live[:, None], beta, 0.0), s0)
    want_o, want_s = _recurrence(s0, q[:23], k[:23], v[:23], g[:23], beta[:23])
    np.testing.assert_allclose(o[:23], want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)


def test_the_step_kernel_updates_live_lanes_in_place_and_no_other():
    """``gated_delta_step``: a live lane's state is the recurrence's next
    one, a fresh lane starts from zero whatever its slot holds (NaN here), a
    lane that is not live and a slot no lane names keep their bits."""
    q, k, v, g, beta, _ = _rule_inputs(5, 4)
    state = jnp.asarray(np.random.default_rng(0).normal(size=(6, 3, 16, 16)), jnp.float32)
    state = state.at[2].set(jnp.nan)
    slots = jnp.asarray([4, 0, 2, 5])
    live = jnp.asarray([True, False, True, True])
    fresh = jnp.asarray([False, False, True, False])
    o, new = gd.gated_delta_step(q, k, v, g, beta, state, slots, live, fresh)
    for lane, slot in enumerate(np.asarray(slots)):
        start = jnp.zeros_like(state[0]) if fresh[lane] else state[slot]
        want_o, want_s = _recurrence(start, *(a[lane:lane + 1] for a in (q, k, v, g, beta)))
        if live[lane]:
            np.testing.assert_allclose(o[lane], want_o[0], rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(new[slot], want_s, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(new[slot], state[slot])
            assert not np.asarray(o[lane]).any()
    np.testing.assert_array_equal(new[np.asarray([1, 3])], state[np.asarray([1, 3])])
    with pytest.raises(ValueError, match="float32"):
        gd.gated_delta_step(q, k, v, g, beta, state.astype(jnp.bfloat16), slots, live, fresh)


def test_the_conv_hands_its_window_from_chunk_to_chunk_and_to_the_steps():
    rng = np.random.default_rng(2)
    x, w = jnp.asarray(rng.normal(size=(21, 6)), jnp.float32), jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    padded = jnp.pad(x, ((3, 0), (0, 0)))
    want = sum(padded[j:j + 21] * w[j] for j in range(4))
    zeros = jnp.zeros((3, 6), jnp.float32)
    y1, win = gd.causal_conv_chunk(jnp.pad(x[:10], ((0, 6), (0, 0))), zeros, w, 10)     # 6 padded rows
    np.testing.assert_array_equal(win, x[7:10])
    y2, win = gd.causal_conv_chunk(x[10:12], win, w, 2)                                  # shorter than the window
    np.testing.assert_array_equal(win, x[9:12])
    steps = []
    for t in range(12, 21):
        y, win = gd.causal_conv_step(x[t][None], win[None], w)
        steps.append(y[0])
        win = win[0]
    np.testing.assert_allclose(jnp.concatenate([y1[:10], y2, jnp.stack(steps)]), want, rtol=1e-5, atol=1e-6)


# -- 3. through the engine: pages for the full-attention layers, a state a slot for the others --

PLUGIN = dict(num_slots=2, page_size=8, pages_per_slot=12, num_pages=24, prefill_chunk=32,
              prefill_buckets=(16, 32), decode_kernel="native")
GEN = GenerationConfig(max_new_tokens=24, do_sample=False, eos_token_id=None)


def serve(model, weights, prompts, new=12, before=None, **over):
    eng = ServingEngine(model, family.to_program(weights, CFG), ServingPlugin(**{**PLUGIN, **over}),
                        dataclasses.replace(GEN, max_new_tokens=new))
    eng.warmup()
    if before is not None:
        before(eng)
    compiles = eng.compile_events
    for uid, prompt in prompts.items():
        eng.add_request(Request(uid=uid, prompt=tuple(int(t) for t in prompt), max_new_tokens=new))
    while not eng.idle():
        eng.step()
    assert eng.compile_events == compiles
    assert verify_serving_invariants(eng) == []
    return eng


class Probe(Qwen3NextForCausalLM):
    """The model with its paged calls' LOGITS copied out to the host as the
    engine's compiled programs run (the engine itself hands back tokens)."""

    seen = []

    def apply(self, *args, **kwargs):
        out = super().apply(*args, **kwargs)
        if kwargs.get("cache") is not None:
            jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), out[0], ordered=True)
        return out


def poison(eng):
    """Every slot's recurrent state and conv window NaN: what an engine that
    clears nothing may hand a request."""
    for layer in eng.cache["layers"]:
        for name in ("state", "conv"):
            if name in layer:
                layer[name] = jnp.full_like(layer[name], jnp.nan)


@pytest.fixture(scope="module")
def probed(weights):
    """One engine with one slot (so a call's logits line up with the row),
    warmed once, its state and conv NaN before the first request: its
    requests reuse the slot back to back, each finding what the last left."""
    return serve(Probe(family.build_model(CFG, LAYERS, dtype=jnp.float32).config), weights, {},
                 new=20, num_slots=1, before=poison)


def run_probed(probed, uid, prompt, new=20):
    jax.effects_barrier()
    Probe.seen.clear()
    probed.add_request(Request(uid=uid, prompt=tuple(int(t) for t in prompt), max_new_tokens=new))
    while not probed.idle():
        probed.step()
    jax.effects_barrier()
    assert verify_serving_invariants(probed) == []
    return list(Probe.seen), np.asarray(probed.results[uid])


@pytest.mark.parametrize("prompt_len", [1, 5, 16, 17, 32, 37, 64, 75],
                         ids=lambda n: f"prompt_{n}")
def test_paged_programs_give_the_references_logits(probed, weights, prompt_len):
    """Prefill in chunks, then decode, through ``ServingEngine`` against the
    reference's ONE full forward, logits compared: prompts of one token, of
    less than a bucket, of whole buckets, one past, and several chunks long
    (the state and the window handed from chunk to chunk and on to the decode
    steps); every request takes the slot its predecessor left uncleared, the
    first one a slot full of NaN."""
    prompt = ids_of(100 + prompt_len, prompt_len)
    seen, tokens = run_probed(probed, prompt_len, prompt)
    row = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    want = reference.row_logits(weights, CFG, LAYERS, row)
    decode = np.concatenate([x[:, 0] for x in seen if x.shape[1] == 1])      # [19, V]
    np.testing.assert_allclose(decode, want[prompt_len:], **TOL)
    chunks = [x[0] for x in seen if x.shape[1] > 1]
    at = 0
    for chunk in chunks:            # every REAL position of every prefill chunk
        real = min(chunk.shape[0], prompt_len - at)
        np.testing.assert_allclose(chunk[:real], want[at:at + real], **TOL)
        at += real
    assert at == prompt_len and len(chunks) == -(-prompt_len // 32)


def test_a_slot_handed_on_without_clearing_serves_the_fresh_logits(probed, weights):
    """The same request after two different tenants of the one slot: its
    logits are bit for bit the same, because the state it starts from is
    zero by the model's own rule, not by anything the engine cleared."""
    prompt = ids_of(900, 21)
    run_probed(probed, 9001, ids_of(901, 50))
    first, tokens = run_probed(probed, 9002, prompt)
    run_probed(probed, 9003, ids_of(902, 7), new=3)
    again, tokens_again = run_probed(probed, 9004, prompt)
    np.testing.assert_array_equal(tokens, tokens_again)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    left = probed.cache["layers"][0]
    assert bool(jnp.all(jnp.isfinite(left["state"]))) and float(jnp.max(jnp.abs(left["state"]))) > 0


def served_gap(weights, prompt, tokens):
    row = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    logits = reference.row_logits(weights, CFG, LAYERS, row)[len(prompt) - 1:]
    at = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(logits, axis=-1) - at))


def test_slots_go_round_and_the_counters_count_resets_and_steps(model, weights):
    """Six requests through two slots, NaN in every state before the first:
    what is served is what the reference puts first; ``linear_resets`` is the
    admissions times the six Gated DeltaNet layers and ``linear_steps`` the
    decode slot-steps times six."""
    prompts = {u: ids_of(40 + u, n) for u, n in enumerate([50, 3, 24, 9, 41, 8])}
    eng = serve(model, weights, prompts, new=18, before=poison)
    m = eng.metrics
    assert m["evictions"] == 0 and int(eng.cache["free_top"]) == PLUGIN["num_pages"]
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(eng.results[uid])) < 1e-3
    assert m["linear_resets"] == 6 * len(prompts)
    assert m["linear_steps"] == 6 * m["decode_lane_passes"] and m["decode_lane_passes"] == 6 * 17
    assert m["moe_ticks"] == 8 * m["decode_steps"]                 # every layer is sparse
    assert 0 < m["moe_rows_held"] <= m["moe_rows_computed"]
    assert m["global_visible_sum"] <= m["global_walked_sum"] < m["global_visible_sum"] \
        + 8 * 2 * m["decode_lane_passes"]                          # two full-attention layers
    # the short tenant after the long one in the same slot saw none of its state
    alone = serve(model, weights, {1: prompts[1]}, new=18)
    assert alone.results[1] == eng.results[1]


def test_eviction_and_readmission_rebuild_the_state(model, weights):
    """A pool too small for the requests at once: a sequence is evicted and
    readmitted from position 0 (its prompt and tokens prefilled again, so its
    recurrent state is rebuilt by the chunks — no snapshot), and it is served
    the tokens it is served with room to spare."""
    prompts = {u: ids_of(60 + u, 50 + 7 * u) for u in range(3)}
    tight = serve(model, weights, prompts, new=20, num_pages=13)
    roomy = serve(model, weights, prompts, new=20, num_slots=3, num_pages=36)
    assert tight.metrics["evictions"] > 0 and tight.free_page_mirror_in_sync()
    assert roomy.metrics["evictions"] == 0
    assert tight.metrics["linear_resets"] == 6 * (3 + tight.metrics["evictions"])
    for uid, prompt in prompts.items():
        assert tight.results[uid] == roomy.results[uid]
        assert served_gap(weights, prompt, np.asarray(tight.results[uid])) < 1e-3


def _paged_call(model, weights, cache, ids, positions, slots, mask):
    tables = cache["block_tables"][slots]
    views = [{**layer, "block_tables": tables, "slots": slots} for layer in cache["layers"]]
    return model.apply(family.to_program(weights, CFG), ids, positions=positions, cache=views,
                       cache_write_mask=mask)


def test_masked_lanes_and_padded_positions_leave_state_and_conv_as_they_were(weights):
    """A decode step whose mask is off for a lane, and a prefill chunk's
    padded positions: the lane's (and every other slot's) state and conv keep
    their bits; the chunk stores what the same tokens store in a bucket they
    fill.  One period deep (three Gated DeltaNet layers)."""
    model = family.build_model(CFG, 4, dtype=jnp.float32)
    weights = {k: v for k, v in weights.items() if not k.startswith("layers.") or int(k.split(".")[1]) < 4}
    cache = model.init_paged_cache(num_pages=16, page_size=8, num_slots=3, pages_per_slot=4)
    rng = np.random.default_rng(5)
    for layer in cache["layers"]:
        for name in ("state", "conv"):
            if name in layer:
                layer[name] = jnp.asarray(rng.normal(size=layer[name].shape), layer[name].dtype)
    cache["block_tables"] = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    slots = jnp.arange(3, dtype=jnp.int32)
    _, after, counters = _paged_call(model, weights, cache, jnp.asarray([[5], [6], [7]]),
                                     jnp.asarray([[9], [3], [12]]), slots,
                                     jnp.asarray([[True], [False], [True]]))
    for old, new in zip(cache["layers"], after):
        for name in ("state", "conv"):
            if name in old:
                np.testing.assert_array_equal(new[name][1], old[name][1])
                assert bool(jnp.any(new[name][0] != old[name][0]))
    names = [n for n, _ in model.tick_counters]
    assert int(counters[-2]) == 3 * 2 and names[-2:] == ["linear_steps", "linear_resets"]
    # a chunk of 5 live tokens in a bucket of 16 and in a bucket of 8, into slot 2 from position 8
    ids = jnp.asarray(ids_of(3, 16))[None]
    at = jnp.asarray([2], jnp.int32)
    wide = _paged_call(model, weights, cache, ids, 8 + jnp.arange(16)[None], at, jnp.arange(16)[None] < 5)
    tight = _paged_call(model, weights, cache, ids[:, :8], 8 + jnp.arange(8)[None], at, jnp.arange(8)[None] < 5)
    np.testing.assert_allclose(wide[0][0, :5], tight[0][0, :5], **TOL)
    for old, a, b in zip(cache["layers"], wide[1], tight[1]):
        for name in ("state", "conv"):
            if name in old:
                np.testing.assert_array_equal(a[name][:2], old[name][:2])       # the other slots
                np.testing.assert_allclose(a[name][2], b[name][2], rtol=1e-5, atol=1e-6)
                assert bool(jnp.any(a[name][2] != old[name][2]))
    assert int(wide[2][-1]) == 0                                     # position 8: no reset
    zero = _paged_call(model, weights, cache, ids, jnp.arange(16)[None], at, jnp.arange(16)[None] < 5)
    assert int(zero[2][-1]) == 3 and int(zero[2][-2]) == 0           # position 0: three states from zero


def test_the_cache_holds_two_kinds_of_state_and_the_accounting_counts_both(model, weights):
    eng = serve(model, weights, {0: ids_of(1, 20)}, new=4)
    kinds = [{k: (v.shape, v.dtype) for k, v in layer.items()} for layer in eng.cache["layers"]]
    slot = {"state": ((2, 4, 16, 16), jnp.float32), "conv": ((2, 3, 128), jnp.float32)}
    pages = {"k_pages": ((24, 8, 32), jnp.float32), "v_pages": ((24, 8, 32), jnp.float32)}
    assert kinds == [slot, slot, slot, pages] * 2
    acct = cache_accounting(model, num_pages=24, page_size=8, num_slots=2, pages_per_slot=12)
    assert acct["paged_layers"] == 2
    assert acct["bytes_per_page"] == 2 * 2 * 8 * 32 * 4          # K + V, the 2 paged layers only (float32 here)
    assert acct["slot_state_bytes"] == 6 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    held = sum(int(a.nbytes) for layer in eng.cache["layers"] for a in layer.values())
    assert acct["pool_bytes"] + acct["slot_state_bytes"] == held          # what the engine really holds
    # at the cell's engine, the published widths and the share: as the configuration file reckons
    cell = Qwen3NextForCausalLM(Qwen3NextConfig(
        num_hidden_layers=8, experts_held=tuple(range(128)), attention_heads_held=4,
        key_value_heads_held=1, linear_key_heads_held=4, linear_value_heads_held=8, vocab_held=37984))
    acct = cache_accounting(cell, num_pages=36864, page_size=64, num_slots=128, pages_per_slot=288)
    assert acct["bytes_per_page"] == 64 * 2048 and acct["pool_bytes"] == 4_831_838_208     # 2,048 B a token
    assert acct["slot_state_bytes"] == 6 * 128 * (8 * 128 * 128 * 4 + 3 * 2048 * 2) == 412_090_368
    assert acct["tokens_capacity"] == 128 * 18432 and acct["paged_layers"] == 2


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


def test_a_prefill_bucket_is_whole_pages(model):
    with pytest.raises(ValueError, match="multiples of page_size"):
        ServingEngine(model, None, ServingPlugin(**{**PLUGIN, "prefill_buckets": (12, 32)}), GEN)


# -- 4. the share: all four shares' parts add up to the uncut layer -----------------------------


def linear_attn_share(params, config, rank, chips):
    """What rank ``rank`` of ``chips`` holds of a WHOLE Gated DeltaNet layer's
    leaves (``linear_attn``'s param dict): a run of whole key-head groups of
    the fused in-projections, those heads' channels of the conv (whose
    channels are ``[q of all heads; k of all heads; v of all heads]``), their
    ``A_log`` / ``dt_bias`` and rows of ``out_proj``; the gated norm's weight
    is whole on every chip."""
    cfg = config
    kh, vh = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    part = lambda n: slice(rank * n // chips, (rank + 1) * n // chips)
    keys, vals = jnp.arange(kh * dk)[part(kh * dk)], jnp.arange(vh * dv)[part(vh * dv)]
    channels = jnp.concatenate([keys, kh * dk + keys, 2 * kh * dk + vals])
    fused = lambda kernel: kernel[:, part(kernel.shape[1])]
    return {"in_proj_qkvz": {"kernel": fused(params["in_proj_qkvz"]["kernel"])},
            "in_proj_ba": {"kernel": fused(params["in_proj_ba"]["kernel"])},
            "conv1d": params["conv1d"][:, channels], "A_log": params["A_log"][part(vh)],
            "dt_bias": params["dt_bias"][part(vh)], "norm": params["norm"],
            "out_proj": {"kernel": params["out_proj"]["kernel"][part(vh * dv)]}}


def test_the_four_shares_add_up_to_the_uncut_reference():
    """Four chips share each layer: the Gated DeltaNet heads' and the
    attention heads' output-projection partials and the experts' partials of
    ALL FOUR shares, with the router's choice, the shared expert and its
    gate, the residual and the norms counted once, add up to what the uncut
    reference gives for a layer of each kind; the four vocabulary slices side
    by side are the uncut logits."""
    whole = f32(make_weights(family.weight_shapes(BASE, 4), seed=11))
    full_cfg = family.build_model(BASE, 4, dtype=jnp.float32).config
    tree = family.to_program(whole, BASE)["params"]
    key = reference.cfg_key(BASE)
    x = jax.random.normal(jax.random.key(3), (1, 24, 64)) * 2.0
    pos = jnp.arange(24)[None]
    centred = lambda v, w: reference.rms_norm(v, 1.0 + w, 1e-6)
    for at, full in ((1, False), (3, True)):
        layer = tree[f"layers_{at}"]
        want, _ = reference.layer(whole, f"layers.{at}", x[0], 24, key, None, reference.NO_FAULT, full=full)
        n = centred(x, layer["input_layernorm"]["weight"])
        parts = []
        for rank in range(4):
            if full:
                attn = layer["self_attn"]
                cfg = dataclasses.replace(full_cfg, attention_heads_held=1, key_value_heads_held=1)
                q_cols, kv_cols = slice(rank * 64, rank * 64 + 64), slice(rank // 2 * 32, rank // 2 * 32 + 32)
                params = {"q_proj": {"kernel": attn["q_proj"]["kernel"][:, q_cols]},
                          "k_proj": {"kernel": attn["k_proj"]["kernel"][:, kv_cols]},
                          "v_proj": {"kernel": attn["v_proj"]["kernel"][:, kv_cols]},
                          "o_proj": {"kernel": attn["o_proj"]["kernel"][rank * 32:rank * 32 + 32]},
                          "q_norm": attn["q_norm"], "k_norm": attn["k_norm"]}
                parts.append(Qwen3NextAttention(cfg).apply({"params": params}, n, pos)[0])
            else:
                cfg = dataclasses.replace(full_cfg, linear_key_heads_held=1, linear_value_heads_held=2)
                share = linear_attn_share(layer["linear_attn"], full_cfg, rank, 4)
                assert share["in_proj_qkvz"]["kernel"].shape == (64, 2 * 16 + 2 * 2 * 16)   # one group
                assert share["conv1d"].shape == (4, 2 * 16 + 2 * 16)
                parts.append(Qwen3NextGatedDeltaNet(cfg).apply({"params": share}, n, pos)[0])
        h = x + sum(parts)                                    # the residual once, the partials joined
        n = centred(h, layer["post_attention_layernorm"]["weight"])
        mlp, moe_parts = layer["mlp"], []
        for rank in range(4):
            held = tuple(range(4 * rank, 4 * rank + 4))
            cfg = dataclasses.replace(full_cfg, experts_held=held)
            params = {**mlp, **{f"experts_{k}_proj": mlp[f"experts_{k}_proj"][jnp.asarray(held)]
                                for k in ("gate", "up", "down")}}
            moe_parts.append(Qwen3NextSparseMoE(cfg).apply({"params": params}, n)[0])
        lw = lambda name: whole[f"layers.{at}.{name}"]
        shared = jax.nn.sigmoid(n[0] @ lw("shared_sigmoid")) * reference.kx.swiglu(
            n[0], lw("shared_gate"), lw("shared_up"), lw("shared_down"), key, None)
        y = (h + sum(moe_parts))[0] - 3 * shared             # every share added the shared expert: once
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
        assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts + moe_parts)
    normed = centred(y, whole["final_norm"])
    slices = [normed @ whole["head"][:, r * 64:(r + 1) * 64] for r in range(4)]
    np.testing.assert_allclose(jnp.concatenate(slices, axis=-1), reference._logits(whole, BASE, y, None),
                               rtol=1e-4, atol=1e-4)


# -- 5. the published checkpoint's names -----------------------------------------------------


def test_hf_names_load_into_the_tree_the_benchmark_builds():
    """``load_hf_qwen3_next``: torch ``[out, in]`` tensors under ``model.``,
    one tensor per expert, the fused in-projections as they are (a key head's
    group after another, which ``linear_attn_share`` cuts), the depthwise
    conv's ``[C, 1, 4]``, the bare ``A_log`` / ``dt_bias``, the gated shared
    expert by its own names; ``mtp.*`` is skipped."""
    from accelerate_tpu.models import hf_qwen3_next_key_map, load_hf_qwen3_next

    layers = 4
    whole = f32(make_weights(family.weight_shapes(BASE, layers), seed=5))
    want_tree = family.to_program(whole, BASE)
    block = {"qkvz": "linear_attn.in_proj_qkvz.weight", "ba": "linear_attn.in_proj_ba.weight",
             "conv": "linear_attn.conv1d.weight", "A_log": "linear_attn.A_log",
             "dt_bias": "linear_attn.dt_bias", "gdn_norm": "linear_attn.norm.weight",
             "gdn_out": "linear_attn.out_proj.weight",
             "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
             "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
             "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
             "attn_norm": "input_layernorm.weight", "mlp_norm": "post_attention_layernorm.weight",
             "router": "mlp.gate.weight", "shared_gate": "mlp.shared_expert.gate_proj.weight",
             "shared_up": "mlp.shared_expert.up_proj.weight",
             "shared_down": "mlp.shared_expert.down_proj.weight",
             "shared_sigmoid": "mlp.shared_expert_gate.weight"}
    top = {"embed": "model.embed_tokens.weight", "final_norm": "model.norm.weight"}
    pairs = [("lm_head.weight", np.asarray(whole["head"]).T),
             ("mtp.fc.weight", np.zeros((4, 4), np.float32)),
             ("model.layers.3.self_attn.rotary_emb.inv_freq", np.zeros((4,), np.float32))]
    for name, arr in whole.items():
        arr = np.asarray(arr)
        if name in top:
            pairs.append((top[name], arr))
        elif name.startswith("layers."):
            _, i, leaf = name.split(".")
            at = f"model.layers.{i}"
            if leaf == "conv":
                pairs.append((f"{at}.{block[leaf]}", arr.T[:, None, :]))
            elif leaf == "A_log":
                pairs.append((f"{at}.{block[leaf]}", arr + BASE["assumed"]["weight_scales"]["A_log_mean"]))
            elif leaf in block:
                pairs.append((f"{at}.{block[leaf]}", arr.T if arr.ndim == 2 else arr))
            else:
                pairs += [(f"{at}.mlp.experts.{e}.{leaf}_proj.weight", arr[e].T)
                          for e in range(arr.shape[0])]
    model = family.build_model(BASE, layers, dtype=jnp.float32)
    params, _ = load_hf_qwen3_next(model, pairs, dtype=jnp.float32)
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(params), flat(want_tree)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert hf_qwen3_next_key_map("model.layers.2.mlp.experts_stacked.up_proj") == \
        "params.layers_2.mlp.experts_up_proj"
    assert hf_qwen3_next_key_map("mtp.layers.0.self_attn.q_proj.weight") is None
    # the fused rows, cut per key-head group: the four shares' columns side by side are the whole
    mixer = params["params"]["layers_0"]["linear_attn"]
    shares = [linear_attn_share(mixer, model.config, r, 4) for r in range(4)]
    for leaf in ("in_proj_qkvz", "in_proj_ba"):
        np.testing.assert_array_equal(
            jnp.concatenate([s[leaf]["kernel"] for s in shares], axis=1), mixer[leaf]["kernel"])
    q_of = lambda s: s["conv1d"][:, :16]                      # a share's q channels: its one key head's
    np.testing.assert_array_equal(jnp.concatenate([q_of(s) for s in shares], axis=1),
                                  mixer["conv1d"][:, :64])
