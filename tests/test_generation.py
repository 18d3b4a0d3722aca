"""Generation tests: KV-cache decode parity with the full forward, sampling
filters, variable-length prompts, EOS handling, MoE decode (reference
capability role: big-model inference / generate — the reference's
big_modeling.py:513 + benchmarks/big_model_inference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, generate, sample_logits
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, init_cache
from accelerate_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def test_cached_forward_matches_full(tiny_model):
    """Prefill + per-token decode logits == one uncached forward (the
    fundamental KV-cache invariant)."""
    model, params = tiny_model
    ids = jnp.asarray([[3, 17, 99, 4, 250, 7, 12, 63]], jnp.int32)
    full_logits = model.apply(params, ids)

    cache = init_cache(model.config, 1, ids.shape[1])
    # prefill the first 5 tokens, then decode tokens 5..7 one at a time
    pre_logits, cache = model.apply(params, ids[:, :5], cache=cache)
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(full_logits[:, :5]), atol=2e-2
    )
    for t in range(5, 8):
        step_logits, cache = model.apply(
            params, ids[:, t : t + 1], positions=jnp.asarray([[t]]), cache=cache
        )
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, t]), atol=2e-2,
            err_msg=f"step {t}",
        )


@pytest.mark.slow
def test_greedy_generate_matches_manual_argmax(tiny_model):
    """generate() greedy tokens == manually re-running the full model and
    taking argmax each step (no cache)."""
    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7]], jnp.int32)
    out = generate(model, params, prompt, GenerationConfig(max_new_tokens=4))
    seq = prompt
    expect = []
    for _ in range(4):
        logits = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        expect.append(int(nxt[0]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    assert out.shape == (1, 4)
    assert [int(x) for x in out[0]] == expect


def test_variable_length_prompts_batch(tiny_model):
    """Right-padded prompts of different lengths decode as if each ran alone
    (padding slots positionally dead in the cache)."""
    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=3)
    p1 = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    p2 = jnp.asarray([[11, 3]], jnp.int32)
    solo1 = generate(model, params, p1, cfg)
    solo2 = generate(model, params, p2, cfg)
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 0, 0]], jnp.int32)
    out = generate(model, params, batch, cfg, prompt_lengths=jnp.asarray([4, 2]))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(solo1[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(solo2[0]))


def test_eos_pads_tail(tiny_model):
    """Tokens after EOS come back as pad_token_id."""
    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7]], jnp.int32)
    free = generate(model, params, prompt, GenerationConfig(max_new_tokens=5))
    eos = int(free[0, 1])  # force EOS at the second emitted token
    out = generate(
        model, params, prompt,
        GenerationConfig(max_new_tokens=5, eos_token_id=eos, pad_token_id=123),
    )
    toks = [int(x) for x in out[0]]
    assert toks[1] == eos
    assert all(t == 123 for t in toks[2:])


def test_sampling_respects_top_k():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0, 5.0]])
    cfg = GenerationConfig(do_sample=True, top_k=2)
    picks = {
        int(sample_logits(logits, jax.random.PRNGKey(i), cfg)[0]) for i in range(50)
    }
    assert picks <= {4, 5}
    assert len(picks) == 2  # both survivors actually reachable


def test_sampling_top_k_larger_than_vocab_clamps():
    """transformers silently clamps top_k > V; lax.top_k would raise."""
    logits = jnp.asarray([[0.0, 1.0, 2.0]])
    cfg = GenerationConfig(do_sample=True, top_k=50)
    picks = {
        int(sample_logits(logits, jax.random.PRNGKey(i), cfg)[0]) for i in range(60)
    }
    assert picks == {0, 1, 2}


def test_sampling_respects_top_p():
    # softmax of [0,0,0,10] puts ~1.0 mass on index 3 -> top_p=0.5 keeps only it
    logits = jnp.asarray([[0.0, 0.0, 0.0, 10.0]])
    cfg = GenerationConfig(do_sample=True, top_p=0.5)
    for i in range(20):
        assert int(sample_logits(logits, jax.random.PRNGKey(i), cfg)[0]) == 3


def test_sampling_top_p_zero_is_greedy():
    """top_p=0.0 keeps the single best token (never uniform-over-masked)."""
    logits = jnp.asarray([[0.5, 3.0, 1.0, 2.0]])
    cfg = GenerationConfig(do_sample=True, top_p=0.0)
    for i in range(10):
        assert int(sample_logits(logits, jax.random.PRNGKey(i), cfg)[0]) == 1


def test_sampling_greedy_ignores_rng():
    logits = jnp.asarray([[0.3, 0.1, 2.0]])
    cfg = GenerationConfig(do_sample=False)
    assert int(sample_logits(logits, jax.random.PRNGKey(0), cfg)[0]) == 2


@pytest.mark.slow
def test_mixtral_generates():
    """MoE decode path: cache threads through the Mixtral block."""
    cfg = MixtralConfig.tiny()
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    out = generate(model, params, jnp.asarray([[1, 2, 3]], jnp.int32),
                   GenerationConfig(max_new_tokens=3))
    assert out.shape == (1, 3)
    assert np.asarray(out).dtype == np.int32


def test_generate_do_sample_runs(tiny_model):
    model, params = tiny_model
    out = generate(
        model, params, jnp.asarray([[5, 42, 7]], jnp.int32),
        GenerationConfig(max_new_tokens=4, do_sample=True, temperature=0.8, top_k=20),
        rng=jax.random.PRNGKey(7),
    )
    assert out.shape == (1, 4)


@pytest.mark.slow
def test_t5_generate_seq2seq_greedy_matches_manual():
    """Encoder-decoder decode: scan over the fixed decoder buffer equals a
    manual grow-the-sequence greedy loop."""
    from accelerate_tpu.generation import generate_seq2seq
    from accelerate_tpu.models.t5 import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    src = jnp.asarray([[9, 4, 17, 2, 0, 0]], jnp.int32)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0]], bool)
    params = model.init(jax.random.PRNGKey(0), src, src[:, :3])

    out = generate_seq2seq(model, params, src, GenerationConfig(max_new_tokens=4),
                           attention_mask=mask)

    dec = jnp.zeros((1, 1), jnp.int32)  # decoder_start_token_id = 0
    expect = []
    for _ in range(4):
        logits = model.apply(params, src, dec, mask)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        expect.append(int(nxt[0]))
        dec = jnp.concatenate([dec, nxt[:, None]], axis=1)
    assert [int(x) for x in out[0]] == expect


@pytest.mark.slow
def test_t5_encode_only_and_cached_decode():
    """encoder_output round-trip: decode with cached states == joint call."""
    from accelerate_tpu.models.t5 import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    src = jnp.asarray([[9, 4, 17, 2]], jnp.int32)
    dec = jnp.asarray([[0, 7, 3]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), src, dec)
    joint = model.apply(params, src, dec)
    enc = model.apply(params, src, None)
    split = model.apply(params, None, dec, encoder_output=enc)
    np.testing.assert_allclose(np.asarray(split), np.asarray(joint), atol=1e-5)


def test_beam_search_k1_equals_greedy(tiny_model):
    from accelerate_tpu.generation import beam_search

    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7]], jnp.int32)
    cfg = GenerationConfig(max_new_tokens=4)
    greedy = generate(model, params, prompt, cfg)
    beam1 = beam_search(model, params, prompt, cfg, num_beams=1)
    np.testing.assert_array_equal(np.asarray(beam1), np.asarray(greedy))


@pytest.mark.slow
def test_beam_search_score_at_least_greedy(tiny_model):
    """The best of K beams scores >= the greedy hypothesis (sum of token
    log-probs under the model)."""
    from accelerate_tpu.generation import beam_search

    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    cfg = GenerationConfig(max_new_tokens=5)

    def seq_logprob(new_tokens):
        seq = jnp.concatenate([prompt, new_tokens[None]], axis=1)
        logits = model.apply(params, seq)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        total = 0.0
        for i, tok in enumerate(np.asarray(new_tokens)):
            total += float(logp[0, prompt.shape[1] - 1 + i, int(tok)])
        return total

    greedy = generate(model, params, prompt, cfg)[0]
    beam = beam_search(model, params, prompt, cfg, num_beams=4)[0]
    assert seq_logprob(beam) >= seq_logprob(greedy) - 1e-4


def test_beam_search_length_penalty_counts_eos_step(tiny_model):
    """GNMT normalization parity (ADVICE r1): a hypothesis ending in EOS at
    step 2 has gen_len 2 (the EOS step counts), not 1.  The stub transition
    is built so the correct normalization picks the EOS beam and the
    off-by-one normalization flips to the other beam."""
    from accelerate_tpu.generation import beam_search

    model, params = tiny_model

    # vocab 4, pad=0, eos=3.  Prompt step: p = [.25, .30, .28, .17] so the
    # two live beams after step 1 hold tokens 1 (score log .30) and 2
    # (log .28).  Decode: token 1 -> EOS almost surely; token 2 -> token 2.
    # Final raw scores: A ~= log .30, B ~= log .28, both over 2 generated
    # tokens.  Correct: A/2 > B/2 -> A wins.  If the EOS step were dropped
    # from gen_len, A/1 < B/2 -> B would win.
    prefill_row = jnp.log(jnp.asarray([0.25, 0.30, 0.28, 0.17]))
    row_eos = jnp.log(jnp.asarray([0.001, 0.001, 0.001, 0.997]))
    row_tok2 = jnp.log(jnp.asarray([0.001, 0.001, 0.997, 0.001]))

    def stub_apply(params, ids, positions=None, cache=None, cache_write_mask=None):
        b, t = ids.shape
        if t > 1:  # prefill
            logits = jnp.broadcast_to(prefill_row, (b, t, 4))
        else:
            logits = jnp.where((ids == 1)[..., None], row_eos, row_tok2)
        return logits, cache

    cfg = GenerationConfig(max_new_tokens=2, eos_token_id=3, pad_token_id=0)
    out = beam_search(model, params, jnp.asarray([[5, 5]], jnp.int32), cfg,
                      num_beams=2, length_penalty=1.0, apply_fn=stub_apply)
    np.testing.assert_array_equal(np.asarray(out), [[1, 3]])


@pytest.mark.slow
def test_beam_search_batch_and_lengths(tiny_model):
    """Beam search handles right-padded variable-length prompts per row."""
    from accelerate_tpu.generation import beam_search

    model, params = tiny_model
    cfg = GenerationConfig(max_new_tokens=3)
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 0, 0]], jnp.int32)
    out = beam_search(model, params, batch, cfg, num_beams=3,
                      prompt_lengths=jnp.asarray([4, 2]))
    solo = beam_search(model, params, jnp.asarray([[11, 3]], jnp.int32), cfg, num_beams=3)
    assert out.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(solo[0]))


@pytest.mark.slow
def test_generate_with_sharded_params():
    """Generation over TP+FSDP-sharded params produces identical tokens to
    the unsharded run (GSPMD propagates shardings through prefill + the
    decode scan — the sharded big-model inference path)."""
    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.generation import beam_search
    from accelerate_tpu.parallel.sharding import make_sharding_plan
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    prompt = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    ref = generate(model, params, prompt, GenerationConfig(max_new_tokens=5))

    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=2, tp_size=4))
    plan = make_sharding_plan(params, acc.mesh, parallelism_config=acc.parallelism_config)
    sharded = jax.device_put(params, plan)
    out = generate(model, sharded, prompt, GenerationConfig(max_new_tokens=5))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    beam = beam_search(model, sharded, prompt, GenerationConfig(max_new_tokens=5), num_beams=3)
    assert beam.shape == (1, 5)
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()


def test_generate_from_quantized_params(tiny_model):
    """int8-quantized params decode natively: QuantizedTensor kernel leaves
    route through QuantizableDense -> the Pallas in-tile-dequant matmul (the
    bnb-analog inference path, reference utils/bnb.py:469), with no apply
    wrapper."""
    from accelerate_tpu.generation import beam_search
    from accelerate_tpu.utils.quantization import QuantizationConfig, quantize_params

    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    qparams = quantize_params(
        params, QuantizationConfig(load_in_8bit=True, min_size=1, skip_patterns=(
            "embed", "norm", "bias", "scale", "lm_head"))
    )
    from accelerate_tpu.utils.quantization import is_quantized

    assert any(is_quantized(x) for x in jax.tree_util.tree_leaves(
        qparams, is_leaf=is_quantized))
    out = generate(model, qparams, prompt, GenerationConfig(max_new_tokens=6))
    ref = generate(model, params, prompt, GenerationConfig(max_new_tokens=6))
    # int8 blockwise-absmax is tight enough that the tiny model's greedy
    # path is unchanged — a strong end-to-end dequant-correctness signal
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    beam = beam_search(model, qparams, prompt, GenerationConfig(max_new_tokens=4),
                       num_beams=3)
    assert beam.shape == (1, 4)


def test_generate_quantized_via_apply_wrapper(tiny_model):
    """The generic quantized_apply wrapper (for model families without
    QuantizableDense) still decodes correctly."""
    from accelerate_tpu.utils.quantization import (
        QuantizationConfig,
        quantize_params,
        quantized_apply,
    )

    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    qparams = quantize_params(params, QuantizationConfig(load_in_8bit=True, min_size=1))
    out = generate(model, qparams, prompt, GenerationConfig(max_new_tokens=6),
                   apply_fn=quantized_apply(model.apply))
    ref = generate(model, params, prompt, GenerationConfig(max_new_tokens=6))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.slow
def test_generate_streamed_matches_regular(tiny_model):
    """Layer-streamed decode (the over-HBM inference mode) matches the
    one-jit generate.  Token streams are compared where logits are
    decisive; near-ties (the per-layer jits fuse differently, so float
    noise can flip an argmax between two ~equal logits) are tolerated by
    also accepting positions where the manual no-cache forward agrees with
    the streamed choice."""
    from accelerate_tpu.generation import generate_streamed
    from accelerate_tpu.utils.quantization import QuantizationConfig, quantize_params

    model, params = tiny_model
    prompt = jnp.asarray([[5, 42, 7]], jnp.int32)
    cfg = GenerationConfig(max_new_tokens=4)
    ref = generate(model, params, prompt, cfg)
    st = generate_streamed(model, params, prompt, cfg)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(st))

    # variable-length rows + EOS padding + int8 leaves: compare step tokens,
    # accepting a divergence only if the two candidates' full-forward logits
    # are within float noise of each other at that step (a genuine tie)
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 0, 0]], jnp.int32)
    lens = jnp.asarray([4, 2])
    cfg = GenerationConfig(max_new_tokens=5, eos_token_id=2)
    qparams = quantize_params(params, QuantizationConfig(load_in_8bit=True, min_size=1))
    for p in (params, qparams):
        ref = np.asarray(generate(model, p, batch, cfg, prompt_lengths=lens))
        st = np.asarray(generate_streamed(model, p, batch, cfg, prompt_lengths=lens))
        if np.array_equal(ref, st):
            continue
        # divergences must start at a near-tie, and the streams must agree
        # up to the first divergent step per row
        for r in range(ref.shape[0]):
            row_ref, row_st = ref[r], st[r]
            if np.array_equal(row_ref, row_st):
                continue
            first = int(np.argmax(row_ref != row_st))
            seq = np.concatenate([np.asarray(batch[r][: int(lens[r])]), row_st[:first]])
            logits = np.asarray(
                model.apply(p, jnp.asarray(seq[None], jnp.int32))
            )[0, -1].astype(np.float32)
            a, b = int(row_ref[first]), int(row_st[first])
            assert abs(logits[a] - logits[b]) < 2e-2, (
                f"row {r} step {first}: {a} vs {b} not a near-tie "
                f"({logits[a]:.4f} vs {logits[b]:.4f})"
            )


def test_generate_streamed_prefetch_logits_equal(tiny_model):
    """The layer double buffer (ops/streaming.LayerPrefetcher) only moves
    WHERE the H2D copy is dispatched — prefetch-on and prefetch-off must
    produce bit-identical logits at every forward, and identical tokens.
    The prefetcher's accounting must show the lookahead actually engaged."""
    from accelerate_tpu.generation import generate_streamed
    from accelerate_tpu.ops.streaming import StreamStats

    model, params = tiny_model
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 2, 0]], jnp.int32)
    lens = jnp.asarray([4, 3])
    cfg = GenerationConfig(max_new_tokens=5, eos_token_id=2)

    logits_off: list = []
    off = generate_streamed(model, params, batch, cfg, prompt_lengths=lens,
                            prefetch=False, capture_logits=logits_off)
    stats = StreamStats()
    logits_on: list = []
    on = generate_streamed(model, params, batch, cfg, prompt_lengths=lens,
                           prefetch=True, stream_stats=stats,
                           capture_logits=logits_on)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    assert len(logits_on) == len(logits_off)
    for a, b in zip(logits_on, logits_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # accounting: every layer of every forward fetched exactly once, all but
    # the cold first already in flight when requested (wrap prefetch)
    n_layers = model.config.num_hidden_layers
    assert stats.fetches >= len(logits_on) * n_layers
    assert stats.prefetch_hits >= len(logits_on) * n_layers - 1
    assert stats.h2d_bytes > 0 and stats.wall_s > 0


def test_generate_streamed_from_offload_store(tmp_path, tiny_model):
    """generate_streamed decodes straight from an OffloadStore's memmap
    leaves (the disk tier): the prefetcher uploads each layer from its .dat
    files, and tokens match the in-memory params."""
    from accelerate_tpu.big_modeling import offload_state_dict, offload_store_params
    from accelerate_tpu.generation import generate_streamed

    model, params = tiny_model
    flat, _ = jax.tree_util.tree_flatten_with_path(params)

    def key_of(path):
        parts = []
        for k in path:
            for attr in ("key", "idx", "name"):
                if hasattr(k, attr):
                    parts.append(str(getattr(k, attr)))
                    break
        return "/".join(parts)

    store = offload_state_dict(
        str(tmp_path), {key_of(path): np.asarray(leaf) for path, leaf in flat}
    )
    disk_params = offload_store_params(store)
    assert isinstance(
        jax.tree_util.tree_leaves(disk_params["params"]["layers_0"])[0], np.memmap
    )
    prompt = jnp.asarray([[5, 42, 7]], jnp.int32)
    cfg = GenerationConfig(max_new_tokens=4)
    ref = generate_streamed(model, params, prompt, cfg)
    disk = generate_streamed(model, disk_params, prompt, cfg)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(disk))


def test_generate_from_scan_layout_params():
    """A scan_layers-trained state generates directly: generate() converts
    to the unrolled layout transparently (unstack + config replace)."""
    import dataclasses

    from accelerate_tpu.models.llama import stack_layer_params

    cfg = LlamaConfig.tiny(scan_layers=True)
    model = LlamaForCausalLM(cfg)
    un_model = LlamaForCausalLM(dataclasses.replace(cfg, scan_layers=False))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 255, (1, 8)), jnp.int32)
    un_params = un_model.init(jax.random.PRNGKey(0), ids)
    out_scan = generate(model, stack_layer_params(un_params), ids,
                        GenerationConfig(max_new_tokens=4))
    out_ref = generate(un_model, un_params, ids, GenerationConfig(max_new_tokens=4))
    np.testing.assert_array_equal(np.asarray(out_scan), np.asarray(out_ref))
