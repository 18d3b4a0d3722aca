"""Keye-VL-2.0's language model (``models/keye_vl2.py``: learned sparse
attention + dropless experts) against its plain reference
(``perfbench/reference/keye_vl2.py``: float32 ``jax.numpy``, no kernel, no
cache), at tiny sizes on seeded weights.  LOGITS are compared, never tokens
alone: with random weights the largest logit changes on rounding."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from accelerate_tpu.generation import GenerationConfig  # noqa: E402
from accelerate_tpu.models import KeyeVL2Config, KeyeVL2ForCausalLM, LlamaConfig, LlamaForCausalLM  # noqa: E402
from accelerate_tpu.models.keye_vl2 import KeyeVL2SparseMoE  # noqa: E402
from accelerate_tpu.parallel.expert_parallel import (expert_capacity, grouped_ffn,  # noqa: E402
                                                     route_dropless, top_k_routing)
from accelerate_tpu.serving import Request, ServingEngine, verify_serving_invariants  # noqa: E402
from accelerate_tpu.utils.dataclasses import ServingPlugin  # noqa: E402
from perfbench.families import keye_vl2 as family  # noqa: E402
from perfbench.reference import keye_vl2 as reference  # noqa: E402
from perfbench.weights import make_weights  # noqa: E402

TOPK = 16
CFG = {   # the published config's keys at test scale
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [4, 6, 6], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 32, "q_chunk_size": 32, "topk": TOPK},
    "tie_word_embeddings": False,
}
LAYERS = 2
TOL = dict(rtol=2e-4, atol=2e-4)     # float32 both sides; the orders of summation differ


@pytest.fixture(scope="module")
def weights():
    """Seeded bf16 values held in float32, so program and reference read the same numbers."""
    made = make_weights(family.weight_shapes(CFG, LAYERS), seed=7)
    return {k: jnp.asarray(v, jnp.float32) for k, v in made.items()}


@pytest.fixture(scope="module")
def model():
    return family.build_model(CFG, LAYERS, dtype=jnp.float32)


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(1, CFG["vocab_size"], n).astype(np.int32)


# -- 1. the forward with no cache ------------------------------------------------------


@pytest.mark.parametrize("length", [12, 96], ids=["below_topk", "above_topk"])
def test_forward_matches_the_reference(model, weights, length):
    ids = ids_of(length, length)
    got = model.apply(family.to_program(weights), jnp.asarray(ids)[None])[0]
    want = reference.row_logits(weights, CFG, LAYERS, ids=jnp.asarray(ids))
    np.testing.assert_allclose(got, want, **TOL)


def test_three_axis_positions_and_embeddings_handed_in(model, weights):
    """What a vision tower asks of the language model: positions [3, B, T]
    with unequal axes, and embeddings in place of token ids."""
    ids = ids_of(3, 96)
    rng = np.random.default_rng(5)
    text = np.arange(96)
    grid = np.stack([text, text + rng.integers(0, 7, 96), text + rng.integers(0, 5, 96)])
    params = family.to_program(weights)
    got = model.apply(params, jnp.asarray(ids)[None], positions=jnp.asarray(grid)[:, None])[0]
    want = reference.row_logits(weights, CFG, LAYERS, ids=jnp.asarray(ids), positions=grid)
    np.testing.assert_allclose(got, want, **TOL)
    plain = model.apply(params, jnp.asarray(ids)[None])[0]
    assert float(jnp.max(jnp.abs(got - plain))) > 1e-2           # the axes are read
    embeds = weights["embed"][jnp.asarray(ids)][None]
    np.testing.assert_array_equal(model.apply(params, inputs_embeds=embeds)[0], plain)
    equal = np.stack([text, text, text])                           # a text token: plain RoPE
    np.testing.assert_allclose(
        model.apply(params, jnp.asarray(ids)[None], positions=jnp.asarray(equal)[:, None])[0],
        plain, rtol=1e-6, atol=1e-6)


# -- 2. serving: chunked prefill, then decode through the pages -------------------------

PLUGIN = dict(num_slots=2, page_size=8, pages_per_slot=10, num_pages=20, prefill_chunk=16,
              prefill_buckets=(8, 16), decode_kernel="native")
GEN = GenerationConfig(max_new_tokens=12, do_sample=False, eos_token_id=None)


def serve(model, weights, prompts, new=12, **over):
    eng = ServingEngine(model, family.to_program(weights), ServingPlugin(**{**PLUGIN, **over}), GEN)
    eng.warmup()
    before = eng.compile_events
    for uid, prompt in prompts.items():
        eng.add_request(Request(uid=uid, prompt=tuple(int(t) for t in prompt), max_new_tokens=new))
    while not eng.idle():
        eng.step()
    assert eng.compile_events == before
    assert verify_serving_invariants(eng) == []
    return eng


def served_gap(weights, prompt, tokens):
    """How far below the reference's best logit the served tokens lie."""
    row = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    logits = reference.row_logits(weights, CFG, LAYERS, ids=jnp.asarray(row))[len(prompt) - 1:]
    at = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(logits, axis=-1) - at))


class Probe(KeyeVL2ForCausalLM):
    """The model with its paged calls' LOGITS copied out to the host as the
    engine's compiled programs run (the engine itself hands back tokens)."""

    seen = []

    def apply(self, *args, **kwargs):
        out = super().apply(*args, **kwargs)
        if kwargs.get("cache") is not None:
            jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), out[0], ordered=True)
        return out


def test_paged_programs_give_the_references_logits(model, weights):
    """Through ``ServingEngine`` — allocator, block table, page writes, indexer
    pages, both prefill buckets, decode across ``topk`` — one request at a
    time, so that the logits of the programs' calls line up with the row."""
    eng = serve(Probe(model.config), weights, {})
    for uid, (n_prompt, chunks) in enumerate([(21, [16, 5]), (9, [9])]):    # 9 < topk < 9 + 12
        prompt = ids_of(40 + uid, n_prompt)
        Probe.seen.clear()
        eng.add_request(Request(uid=uid, prompt=tuple(int(t) for t in prompt), max_new_tokens=12))
        while not eng.idle():
            eng.step()
        jax.effects_barrier()
        slot = 0          # the one live request takes the first slot
        rows = [Probe.seen[i][0, :c] for i, c in enumerate(chunks)] + \
            [x[slot] for x in Probe.seen[len(chunks):]]
        got = np.concatenate(rows)
        row = np.concatenate([prompt, eng.results[uid][:-1]]).astype(np.int32)
        want = reference.row_logits(weights, CFG, LAYERS, ids=jnp.asarray(row))
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, **TOL)


def test_the_engine_serves_what_the_reference_puts_first(model, weights):
    prompts = {0: ids_of(1, 37), 1: ids_of(2, 9), 2: ids_of(3, 50)}   # 9 < topk < 9 + 12
    eng = serve(model, weights, prompts)
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(eng.results[uid])) < 1e-3
    m = eng.metrics
    assert m["expert_tokens"].sum() == (m["prompt_tokens"] + m["decode_emitted_tokens"]) * 4 * LAYERS
    assert m["moe_ticks"] == m["decode_steps"] * LAYERS
    assert 0 < m["moe_experts_hit_sum"] <= m["moe_ticks"] * 2 * 4
    assert 0 < m["sparse_selected_sum"] < m["sparse_visible_sum"]     # contexts pass topk
    assert m["sparse_selected_sum"] <= m["decode_emitted_tokens"] * TOPK * LAYERS


# -- 3. the selected sets ----------------------------------------------------------------


def test_program_and_reference_select_the_same_keys(model, weights):
    """``S_t`` agrees except for keys whose reference score lies within
    ``eps`` of the row's threshold (the smallest selected score)."""
    eps, length = 1e-4, 96
    ids = ids_of(9, length)
    _, state = model.apply(family.to_program(weights), jnp.asarray(ids)[None],
                           mutable=["intermediates"])
    keep = {}
    reference.row_hidden(weights, CFG, LAYERS, ids=jnp.asarray(ids), keep=keep)
    near = 0
    for i in range(LAYERS):
        got = np.asarray(state["intermediates"][f"layers_{i}"]["self_attn"]["selected"][0][0])
        want = np.concatenate([np.asarray(b[0]) for b in keep[i]])
        scores = np.concatenate([np.asarray(b[1]) for b in keep[i]])
        least = np.concatenate([np.asarray(b[2]) for b in keep[i]])
        assert want.sum(axis=1).tolist() == [min(t + 1, TOPK) for t in range(length)]
        differ = got != want
        band = np.abs(np.where(np.isfinite(scores), scores, np.inf) - least[:, None]) <= eps
        assert not (differ & ~band).any()
        near += int(differ.sum())
    assert near <= 4          # of 2 x 96 x 16 selections


# -- 3b. the planted faults the benchmark's controls use ------------------------------------


@pytest.mark.parametrize("fault", sorted(reference.FAULTS))
def test_a_planted_fault_is_the_fault_it_names(weights, fault):
    """``quant=<fault>`` (``prove.py --control``) plants one fault in a float32
    forward: where the same forward can be written another way (a larger or
    smaller ``topk``, fewer experts held) the two agree; every fault moves
    the logits, and no fault leaks into the sound forward."""
    length = 96
    ids = jnp.asarray(ids_of(13, length))
    sound = reference.row_logits(weights, CFG, LAYERS, ids=ids)
    keep = {}
    hidden = reference.row_hidden(weights, CFG, LAYERS, ids=ids, quant=fault, keep=keep)
    faulty = reference.row_logits(weights, CFG, LAYERS, ids=ids, quant=fault)
    assert float(jnp.max(jnp.abs(faulty - sound))) > 1e-2
    assert hidden.shape[0] == length

    def held_only(held):     # a share's weights are its own experts' arrays, in the order held
        part = {k: (v[jnp.asarray(held)] if k.split(".")[-1] in reference.EXPERT_KEYS else v)
                for k, v in weights.items()}
        return reference.row_logits(part, CFG, LAYERS, ids=ids, experts_held=held)

    with_topk = lambda k: {**CFG, "sa_config": {**CFG["sa_config"], "topk": k}}
    same = {
        "dense": lambda: reference.row_logits(weights, with_topk(length), LAYERS, ids=ids),
        "halfkeys": lambda: reference.row_logits(weights, with_topk(TOPK // 2), LAYERS, ids=ids),
        "expert": lambda: held_only([e for e in range(16) if e != 7]),
        "experts8": lambda: held_only(list(range(8, 16))),
    }
    if fault in same:
        np.testing.assert_allclose(faulty, same[fault](), **TOL)
    chosen = np.concatenate([np.asarray(b[0]) for b in keep[0]])
    if fault == "approx":        # one key in eight is never looked at
        assert not chosen[:, 7::8].any()
        assert chosen.sum(axis=1).tolist() == [min(t + 1 - (t + 1) // 8, TOPK) for t in range(length)]
    if fault in ("lastrank", "halfranks", "expert", "experts8"):    # the selection is untouched
        assert chosen.sum(axis=1).tolist() == [min(t + 1, TOPK) for t in range(length)]


# -- 4. dropless -------------------------------------------------------------------------


def expert_loop(x, logits, k, wg, wu, wd):
    p = jax.nn.softmax(logits, axis=-1)
    gate, experts = jax.lax.top_k(p, k)
    gate = np.asarray(gate / gate.sum(-1, keepdims=True))
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for c in range(k):
            e = int(experts[t, c])
            out[t] += gate[t, c] * np.asarray((jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("routing", ["uniform", "forced"])
def test_no_token_is_dropped_at_any_routing(routing):
    n, h, f, e, k = 24, 16, 8, 8, 2
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(keys[0], (n, h))
    wg, wu = jax.random.normal(keys[1], (e, h, f)), jax.random.normal(keys[2], (e, h, f))
    wd = jax.random.normal(keys[3], (e, f, h))
    logits = jax.random.normal(keys[4], (n, e))
    if routing == "forced":                       # every token onto experts 5 and 2
        logits = logits * 0.01 + jnp.zeros((e,)).at[5].set(9.0).at[2].set(6.0)
    route = route_dropless(logits, k)
    np.testing.assert_allclose(grouped_ffn(x, route, wg, wu, wd),
                               expert_loop(x, logits, k, wg, wu, wd), rtol=1e-4, atol=1e-4)
    assert int(route.group_sizes.sum()) == n * k
    kept = int(top_k_routing(logits, k, expert_capacity(n, e, k, 1.25)).dispatch.sum())
    if routing == "forced":
        assert route.group_sizes.tolist() == [0, 0, n, 0, 0, n, 0, 0]
        assert kept < n * k                       # the capacity path drops what this one computes


# -- 5. the share: a chip that holds some of the experts ----------------------------------


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer(weights):
    cfg = family.build_model(CFG, LAYERS, dtype=jnp.float32).config
    x = jax.random.normal(jax.random.key(2), (1, 40, CFG["hidden_size"]))
    lw = {k: weights[f"layers.0.{k}"] for k in ("router", "gate", "up", "down")}
    shares = []
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        layer = KeyeVL2SparseMoE(cfg.__class__(**{**cfg.__dict__, "experts_held": held}))
        params = {"params": {"gate": {"kernel": lw["router"]},
                             **{f"experts_{k}_proj": lw[k][jnp.asarray(held)]
                                for k in ("gate", "up", "down")}}}
        y, counts = layer.apply(params, x)
        assert int(counts.sum()) == 40 * 4        # every share routes over all 16 experts
        shares.append(y[0])
    n = x[0]
    p = jax.nn.softmax(n @ lw["router"], axis=-1)
    gate, experts = jax.lax.top_k(p, 4)
    gate = gate / gate.sum(-1, keepdims=True)
    live = jnp.ones((40,), bool)
    uncut = reference.moe(weights, 0, n, experts, gate, live, 40, tuple(range(16)), None)
    np.testing.assert_allclose(sum(shares), uncut, rtol=1e-4, atol=1e-4)
    one = reference.moe({f"layers.0.{k}": lw[k][4:8] for k in ("gate", "up", "down")}, 0, n,
                        experts, gate, live, 40, (4, 5, 6, 7), None)
    np.testing.assert_allclose(shares[1], one, rtol=1e-4, atol=1e-4)     # the reference's share too


# -- 6. the cache --------------------------------------------------------------------------


def test_eviction_readmission_and_page_recycling_leave_the_tokens_equal(model, weights):
    """A pool too small for three long requests at once: sequences are
    evicted and readmitted, and pages (K, V and indexer rows alike) go round.
    What is served stays what the reference puts first."""
    prompts = {u: ids_of(20 + u, 30 + 5 * u) for u in range(4)}
    tight = serve(model, weights, prompts, num_pages=11)
    assert tight.metrics["evictions"] > 0
    assert tight.free_page_mirror_in_sync()
    assert int(tight.cache["free_top"]) == 11                      # every page came back
    for uid, prompt in prompts.items():
        assert served_gap(weights, prompt, np.asarray(tight.results[uid])) < 1e-3
    kinds = {k: v.shape for k, v in tight.cache["layers"][0].items()}
    assert kinds == {"k_pages": (11, 8, 64), "v_pages": (11, 8, 64), "index_pages": (11, 8, 128)}


@pytest.mark.parametrize("feature,kwargs", [
    ("LoRA adapters", dict(adapters=object())),
    ("int8/fp8 KV pages", dict(plugin=dict(kv_dtype="int8"))),
    ("speculative verify", dict(plugin=dict(speculate="ngram"))),
    ("prefix-cache hashing", dict(plugin=dict(prefix_cache="on"))),
    ("page transfer", dict(hold_finished=True)),
])
def test_the_engine_refuses_what_it_cannot_do_for_this_family(model, weights, feature, kwargs):
    plugin = ServingPlugin(**{**PLUGIN, **kwargs.pop("plugin", {})})
    with pytest.raises(NotImplementedError, match=feature):
        ServingEngine(model, family.to_program(weights), plugin, GEN, **kwargs)


def test_a_chunk_that_is_not_whole_pages_is_refused(model, weights):
    with pytest.raises(ValueError, match="multiples of page_size"):
        ServingEngine(model, family.to_program(weights),
                      ServingPlugin(**{**PLUGIN, "prefill_chunk": 12, "prefill_buckets": (12,)}), GEN)


def test_the_llama_cache_is_what_it_was():
    """The shared builder gives the Llama family the pools it always had."""
    cfg = LlamaConfig.tiny()
    eng = ServingEngine(LlamaForCausalLM(cfg), None, ServingPlugin(**PLUGIN), GEN)
    assert sorted(eng.cache) == ["block_tables", "free_stack", "free_top", "layers", "seq_lens"]
    assert {k: v.shape for k, v in eng.cache["layers"][0].items()} == \
        {"k_pages": (2, 20, 8, 16), "v_pages": (2, 20, 8, 16)}
    assert eng._tick_counters == ()


# -- 8. the published checkpoint's names -----------------------------------------------------


def test_hf_names_of_the_language_model_load_and_the_towers_are_skipped(model, weights):
    """``load_hf_keye_vl2``: torch ``[out, in]`` tensors under the
    ``model.language_model.`` prefix, one tensor per expert, and a vision
    tower's tensor beside them (skipped) give the tree the benchmark builds."""
    from accelerate_tpu.models import load_hf_keye_vl2

    hf = {"embed": "embed_tokens.weight", "final_norm": "norm.weight"}
    attn = {"q": "q_proj", "k": "k_proj", "v": "v_proj", "o": "o_proj", "q_norm": "q_norm",
            "k_norm": "k_norm", "idx_q": "indexer.q_proj", "idx_k": "indexer.k_proj",
            "idx_w": "indexer.weights_proj"}
    pairs = [("visual.blocks.0.attn.qkv.weight", np.zeros((3, 3), np.float32)),
             ("lm_head.weight", np.asarray(weights["head"]).T)]
    for name, arr in weights.items():
        arr = np.asarray(arr)
        if name in hf:
            pairs.append((f"model.language_model.{hf[name]}", arr))
            continue
        if not name.startswith("layers."):
            continue
        _, i, leaf = name.split(".")
        at = f"model.language_model.layers.{i}"
        if leaf in attn:
            pairs.append((f"{at}.self_attn.{attn[leaf]}.weight", arr.T if arr.ndim == 2 else arr))
        elif leaf in ("attn_norm", "mlp_norm"):
            which = "input" if leaf == "attn_norm" else "post_attention"
            pairs.append((f"{at}.{which}_layernorm.weight", arr))
        elif leaf == "router":
            pairs.append((f"{at}.mlp.gate.weight", arr.T))
        else:
            pairs += [(f"{at}.mlp.experts.{e}.{leaf}_proj.weight", arr[e].T)
                      for e in range(arr.shape[0])]
    params, _ = load_hf_keye_vl2(model, pairs, dtype=jnp.float32)
    want = family.to_program(weights)
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(params), flat(want)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


INT_MIN = np.int32(-2 ** 31)


def _threshold_oracle(keys, k):
    """``numpy`` alone: sort each row by (value down, position up), keep the first
    ``k`` that are visible.  Returns ``(threshold, ties_taken, selected)``."""
    keys = np.asarray(keys)
    rows = keys.reshape(-1, keys.shape[-1])
    threshold, ties, chosen = [], [], np.zeros(rows.shape, bool)
    for i, row in enumerate(rows):
        order = np.argsort(-row.astype(np.int64), kind="stable")[:k]
        order = order[row[order] > INT_MIN]
        chosen[i, order] = True
        threshold.append(row[order[-1]] if len(order) == k else INT_MIN + 1)
        ties.append(k - int(np.sum(row > threshold[-1])))
    lead = keys.shape[:-1]
    return (np.asarray(threshold, np.int32).reshape(lead), np.asarray(ties, np.int32).reshape(lead),
            chosen.reshape(keys.shape))


# case -> (shape of the scores, k, share visible, kv_len or None)
THRESHOLD_CASES = {
    "plain": ((3, 5, 700), 64, 0.7, None),
    "ties": ((3, 5, 700), 64, 0.7, None),
    "few_visible": ((3, 5, 700), 64, 0.05, None),
    "decode_rows": ((8, 1, 5000), 64, 0.7, None),          # one tile of 8, three counting steps
    "chunk_rows": ((1, 96, 1300), 64, 0.7, None),          # tiles of 32 rows, columns padded
    "rows_off_the_tile": ((13, 300), 64, 0.7, None),       # padded to 16 rows
    "width_of_whole_lanes": ((2, 8, 1024), 64, 0.7, None),
    "k_over_the_width": ((2, 4, 40), 64, 0.9, None),
    "a_row_with_nothing_visible": ((2, 8, 300), 64, 0.7, None),
    "exactly_k_visible": ((2, 8, 300), 64, 0.7, None),
    "all_scores_equal": ((2, 8, 300), 64, 0.7, None),
    "signed_zeros": ((2, 8, 300), 64, 0.7, None),
    "kv_len_cuts_the_walk": ((8, 1, 8192), 64, 0.7, 500),
}


@pytest.mark.parametrize("case", list(THRESHOLD_CASES))
def test_the_threshold_selects_what_top_k_selects(case):
    """``ops/sparse_attention``: the kernel's threshold (interpret mode here),
    with ties taken in order of position, is ``jax.lax.top_k``'s set and a
    ``numpy`` sort's — for a decode step's rows and a chunk's, widths on and
    off the lanes, many equal scores, rows with fewer than ``k`` visible keys,
    with none and with exactly ``k``, ``k`` over the width, and a ``kv_len``
    past which the kernel does not look."""
    from accelerate_tpu.ops import sparse_attention as sa

    shape, k, share, kv_len = THRESHOLD_CASES[case]
    rng = np.random.default_rng(4)
    x = rng.normal(size=shape).astype(np.float32)
    visible = rng.random(x.shape) < share
    if case == "ties":
        x = np.round(x * 3) / 3
    elif case == "a_row_with_nothing_visible":
        visible[0, 3] = False
    elif case == "exactly_k_visible":
        visible[1, 2] = np.arange(shape[-1]) % 4 == 1
        visible[1, 2, 4 * k:] = False
        assert visible[1, 2].sum() == k
    elif case == "all_scores_equal":
        x[:] = 0.25
    elif case == "signed_zeros":
        x = np.where(rng.random(x.shape) < 0.5, np.float32(-0.0), np.float32(0.0))
        x[..., ::7] = -1.0
    keys = np.array(jnp.where(jnp.asarray(visible), sa.order_key(jnp.asarray(x)), INT_MIN))
    handed = keys
    if kv_len is not None:
        # by contract nothing is visible at or past kv_len; the keys planted in the last
        # lanes would change every threshold if the kernel's counts walked that far
        keys[..., kv_len:] = INT_MIN
        handed = keys.copy()
        handed[..., -128:] = np.int32(2 ** 31 - 1)
    want = _threshold_oracle(keys, k)
    threshold, ties_taken = sa.kth_largest_key(jnp.asarray(handed), k, kv_len)
    np.testing.assert_array_equal(threshold, want[0])
    np.testing.assert_array_equal(ties_taken, want[1])
    got, _ = sa.selected(jnp.asarray(keys), threshold, ties_taken)
    np.testing.assert_array_equal(got, want[2])
    if k <= shape[-1] and case != "signed_zeros":     # and jax.lax.top_k's own set (it tells -0.0 from +0.0)
        seen = jnp.asarray(keys > INT_MIN)
        top, where = jax.lax.top_k(jnp.where(seen, jnp.asarray(x), -jnp.inf), k)
        theirs = np.zeros(x.shape, bool)
        np.put_along_axis(theirs, np.asarray(where), np.asarray(top > -jnp.inf), -1)
        np.testing.assert_array_equal(got, theirs)


def _equations(jaxpr, outer=""):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a kernel's
    own body left out: (primitive, scope from the program's root, params)."""
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, scope, eqn.params
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, scope)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_threshold_is_one_kernel_in_both_programs(model, weights, program):
    """The engine's decode and prefill programs find each layer's threshold
    with ONE ``sparse_threshold`` kernel inside ``sparse_index``: no sort or
    ``top_k`` in the attention's scopes, and no loop of a fixed trip count
    (the 16-step counting loops of an XLA bisection) beside it — the scoring
    loop's trip count follows ``kv_len``."""
    from accelerate_tpu.serving.engine import fresh_engine_jits

    params = family.to_program(weights)
    cache = model.init_paged_cache(20, 8, 2, 10)
    decode, prefill, *_ = fresh_engine_jits(model, GEN, 8)
    if program == "decode":
        traced = decode.trace(params, cache, jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                              jnp.zeros((2,), jnp.uint32))
    else:
        traced = prefill.trace(params, cache, jnp.int32(0), jnp.zeros((16,), jnp.int32),
                               jnp.int32(0), jnp.int32(16))
    eqns = list(_equations(traced.jaxpr.jaxpr))
    assert [name for name, scope, _ in eqns if name in ("sort", "top_k", "approx_top_k")
            and "sparse_" in scope] == []               # the router's own top-8 is elsewhere
    index = [(name, params) for name, scope, params in eqns if "sparse_index" in scope]
    kernels = [params for name, params in index if name == "pallas_call"]
    assert len(kernels) == LAYERS
    assert all("sparse_threshold" in str(params["name"]) for params in kernels)
    assert [name for name, _ in index if name == "scan"] == []
    assert [name for name, _ in index].count("while") == LAYERS          # the scoring loop alone
