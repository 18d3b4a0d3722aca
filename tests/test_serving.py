"""Serving-core tests: paged KV cache, block allocator, continuous-batching
scheduler, and the acceptance pin — the paged serving path emits tokens
IDENTICAL to ``generate()`` for the same requests (ISSUE 6 / ROADMAP item 1;
reference capability role: production-scale big-model inference,
big_modeling.py:513)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, generate, generate_paged
from accelerate_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    init_paged_cache,
    cached_attention,
)
from accelerate_tpu.ops.paged_cache import paged_gather_kv
from accelerate_tpu.serving import (
    Request,
    ServingEngine,
    allocate,
    kv_pool_accounting,
    pages_for,
    release,
    replay,
    static_batching_report,
    synthesize_trace,
)
from accelerate_tpu.utils.dataclasses import ServingPlugin


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _plugin(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("pages_per_slot", 8)
    kw.setdefault("num_pages", 24)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_kernel", "native")
    return ServingPlugin(**kw)


def _ref_tokens(model, params, prompt, n, **cfg_kw):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   GenerationConfig(max_new_tokens=n, **cfg_kw))
    return [int(x) for x in out[0]]


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_release_roundtrip():
    """Pages popped for a batch of slots are unique; releasing the slots
    pushes exactly those pages back and restores the free count."""
    num_pages, n_slots, n_cols, page = 16, 4, 4, 4
    bt = jnp.zeros((n_slots, n_cols), jnp.int32)
    stack = jnp.arange(num_pages, dtype=jnp.int32)
    top = jnp.asarray(num_pages, jnp.int32)

    # slot i allocates its page 0 (4 pops at once)
    need = jnp.ones((n_slots,), bool)
    bt, top = allocate(bt, stack, top, jnp.arange(n_slots), jnp.zeros((n_slots,), jnp.int32), need)
    assert int(top) == num_pages - n_slots
    got = np.asarray(bt[:, 0])
    assert len(set(got.tolist())) == n_slots  # all distinct physical pages

    # write 3 tokens into each slot, then release slots 1 and 3
    seq_lens = jnp.full((n_slots,), 3, jnp.int32)
    mask = jnp.asarray([False, True, False, True])
    seq_lens, stack, top2 = release(bt, seq_lens, stack, top, mask, page)
    assert int(top2) == int(top) + 2
    assert np.asarray(seq_lens).tolist() == [3, 0, 3, 0]
    # the returned pages are the released slots' page-0 entries
    returned = set(np.asarray(stack)[int(top): int(top2)].tolist())
    assert returned == {int(got[1]), int(got[3])}

    # masked-out lanes never allocate: need=False drops the scatter
    bt2, top3 = allocate(bt, stack, top2, jnp.arange(n_slots),
                         jnp.ones((n_slots,), jnp.int32), jnp.zeros((n_slots,), bool))
    assert int(top3) == int(top2)
    np.testing.assert_array_equal(np.asarray(bt2), np.asarray(bt))


def test_pages_for_and_pool_accounting():
    assert [int(pages_for(t, 4)) for t in (0, 1, 4, 5, 8)] == [0, 1, 1, 2, 2]
    cfg = LlamaConfig.tiny()
    acct = kv_pool_accounting(cfg, num_pages=64, page_size=16, dtype_bytes=2)
    # 2 (K+V) * L * page * Hkv * D * bytes
    assert acct["bytes_per_page"] == 2 * cfg.num_hidden_layers * 16 * \
        cfg.num_key_value_heads * cfg.head_dim * 2
    assert acct["pool_bytes"] == acct["bytes_per_page"] * 64
    assert acct["tokens_capacity"] == 64 * 16
    assert 0 < acct["hbm_frac"]["v5e_16GiB"] < 1


# ---------------------------------------------------------------------------
# paged attention parity (model level + kernel level)
# ---------------------------------------------------------------------------


# The paged path and the uncached forward are two programs for one function:
# attention over keys gathered through the block table (sums over the padded
# page extent) against attention over the row itself.  Float32 logits of
# magnitude ~1 then differ in their last bits (4.8e-7 read on jax 0.9);
# 1e-5 is 20x that, and a key read from the wrong page or at the wrong
# position moves a logit by order one (planted on this model: two pages
# swapped in the block table 2.1-3.4, a decode position off by one 1.0-1.4).
_PAGED_VS_FULL_ATOL = 1e-5


def test_paged_prefill_decode_matches_full_forward(tiny_model):
    """Prefill + per-token decode through the paged cache reproduce the
    uncached forward's logits to a written float32 tolerance (the paged
    analog of the dense-cache invariant)."""
    model, params = tiny_model
    ids = jnp.asarray([[3, 17, 99, 4, 250, 7, 12, 63]], jnp.int32)
    full = np.asarray(model.apply(params, ids))

    page_size, slots, pps = 4, 1, 4
    pc = init_paged_cache(model.config, 8, page_size, slots, pps)
    bt = jnp.arange(slots * pps, dtype=jnp.int32).reshape(slots, pps)
    layers = [{"k_pages": l["k_pages"], "v_pages": l["v_pages"], "block_tables": bt}
              for l in pc["layers"]]
    lg, layers = model.apply(
        params, ids[:, :5], positions=jnp.arange(5)[None],
        cache=layers, cache_write_mask=jnp.ones((1, 5), bool),
    )
    np.testing.assert_allclose(np.asarray(lg), full[:, :5], rtol=0, atol=_PAGED_VS_FULL_ATOL)
    for t in range(5, 8):
        layers = [{**l, "block_tables": bt} for l in layers]
        lg, layers = model.apply(
            params, ids[:, t:t + 1], positions=jnp.asarray([[t]]),
            cache=layers, cache_write_mask=jnp.ones((1, 1), bool),
        )
        np.testing.assert_allclose(np.asarray(lg[:, 0]), full[:, t], rtol=0,
                                   atol=_PAGED_VS_FULL_ATOL, err_msg=f"step {t}")


def test_paged_flash_decode_matches_gather_reference():
    """The Pallas paged-decode kernel == gather-through-the-block-table +
    dense cached attention, on ragged positions incl. a dead slot."""
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    hkv, num_pages, page, d, slots, n, h = 2, 16, 8, 32, 4, 4, 4
    kp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(num_pages)[: slots * n].reshape(slots, n), jnp.int32)
    pos = jnp.asarray([0, 5, 17, 31], jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    k_lin, v_lin, kvpos = paged_gather_kv(kp, vp, bt)
    ref = cached_attention(q[:, None], k_lin, v_lin, kvpos, pos[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _quantized_pool(rng, hkv, num_pages, page, d):
    """Emulate the write path's per-(head, page) int8 quantization."""
    f = rng.normal(size=(hkv, num_pages, page, d)).astype(np.float32)
    amax = np.abs(f).max(axis=(2, 3))                        # [Hkv, P]
    codes = np.rint(f * (127.0 / amax[:, :, None, None]))
    return (jnp.asarray(np.clip(codes, -127, 127), jnp.int8),
            jnp.asarray(amax, jnp.float32))


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_multitoken_matches_cached_reference(kv_dtype):
    """Interpret-mode parity for the multi-token Pallas kernel at the
    speculative-verify width T = k+1, ragged positions, dense AND
    quantized pools: kernel == gather(+dequant) + dense cached attention
    with per-row causal masking."""
    from accelerate_tpu.ops.flash_attention import paged_multitoken_attention

    rng = np.random.default_rng(0)
    hkv, num_pages, page, d, slots, n, h, width = 2, 16, 8, 32, 4, 4, 4, 4
    if kv_dtype:
        kp, ks = _quantized_pool(rng, hkv, num_pages, page, d)
        vp, vs = _quantized_pool(rng, hkv, num_pages, page, d)
    else:
        kp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
        ks = vs = None
    bt = jnp.asarray(rng.permutation(num_pages)[: slots * n].reshape(slots, n), jnp.int32)
    # per-slot verify windows starting at ragged depths (last one ends at
    # the pool's final token, exercising the page-skip predicate edge)
    pos = jnp.asarray([0, 5, 17, 28], jnp.int32)[:, None] + jnp.arange(width)[None]
    q = jnp.asarray(rng.normal(size=(slots, width, h, d)), jnp.float32)
    out = paged_multitoken_attention(q, kp, vp, bt, pos, k_scales=ks, v_scales=vs)
    k_lin, v_lin, kvpos = paged_gather_kv(kp, vp, bt, ks, vs, kv_dtype, jnp.float32)
    ref = cached_attention(q, k_lin, v_lin, kvpos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_fused_bgmv_paged_decode_matches_composed_reference(kv_dtype):
    """The consolidated LoRA-query + paged-decode kernel == the two-trip
    composition it replaces: bgmv adapter delta, roped at the slot's
    position, added to the pre-roped base query, then paged decode."""
    from accelerate_tpu.models.llama import apply_rope, rope_frequencies
    from accelerate_tpu.ops.flash_attention import (
        fused_bgmv_paged_decode,
        paged_decode_attention,
    )

    rng = np.random.default_rng(1)
    hkv, num_pages, page, d, slots, n, h = 2, 16, 8, 32, 4, 4, 4
    d_in, rank, n_adapters = 48, 4, 3
    if kv_dtype:
        kp, ks = _quantized_pool(rng, hkv, num_pages, page, d)
        vp, vs = _quantized_pool(rng, hkv, num_pages, page, d)
    else:
        kp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hkv, num_pages, page, d)), jnp.float32)
        ks = vs = None
    bt = jnp.asarray(rng.permutation(num_pages)[: slots * n].reshape(slots, n), jnp.int32)
    pos = jnp.asarray([0, 5, 17, 31], jnp.int32)
    x = jnp.asarray(rng.normal(size=(slots, d_in)), jnp.float32)
    q_base = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.float32)
    # AdapterStore pool layout: row 0 is the zero base slot
    a_np = rng.normal(size=(n_adapters, d_in, rank)).astype(np.float32) * 0.1
    b_np = rng.normal(size=(n_adapters, rank, h * d)).astype(np.float32) * 0.1
    a_np[0] = 0.0
    b_np[0] = 0.0
    a_stack, b_stack = jnp.asarray(a_np), jnp.asarray(b_np)
    ids = jnp.asarray([0, 1, 2, 1], jnp.int32)
    cos, sin = map(jnp.asarray, rope_frequencies(d, 64, 10000.0))

    out = fused_bgmv_paged_decode(x, q_base, a_stack, b_stack, ids, cos, sin,
                                  kp, vp, bt, pos, k_scales=ks, v_scales=vs)
    # composed reference: per-slot bgmv, rope the delta, add, paged decode
    delta = jnp.einsum("sr,srm->sm", jnp.einsum("si,sir->sr", x, a_stack[ids]),
                       b_stack[ids]).reshape(slots, h, d)
    delta = apply_rope(delta[:, None], cos, sin, pos[:, None])[:, 0]
    ref = paged_decode_attention(q_base + delta, kp, vp, bt, pos,
                                 k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# the acceptance pin: serving tokens == generate() tokens
# ---------------------------------------------------------------------------


def test_generate_paged_matches_generate(tiny_model):
    """Same requests through generate() and the paged serving path produce
    IDENTICAL tokens (variable-length rows + EOS padding included)."""
    model, params = tiny_model
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 0, 0]], jnp.int32)
    lens = jnp.asarray([4, 2])
    cfg = GenerationConfig(max_new_tokens=5, eos_token_id=2, pad_token_id=0)
    ref = generate(model, params, batch, cfg, prompt_lengths=lens)
    got = generate_paged(model, params, batch, cfg, prompt_lengths=lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_generate_paged_chunked_prefill_matches(tiny_model):
    """Chunked prefill (prompt split across engine ticks, bucket-padded)
    changes nothing about the emitted tokens."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = tuple(int(x) for x in rng.integers(1, 255, 11))
    plugin = _plugin(num_slots=2, num_pages=16, prefill_chunk=4, prefill_buckets=(4,))
    gcfg = GenerationConfig(max_new_tokens=5)
    eng = ServingEngine(model, params, plugin, gcfg)
    eng.add_request(Request(uid=0, prompt=prompt, max_new_tokens=5))
    while not eng.idle():
        eng.step()
    assert eng.results[0] == _ref_tokens(model, params, prompt, 5)
    assert eng.metrics["prefill_steps"] == 3  # 11 tokens / chunk 4
    assert eng.free_page_mirror_in_sync()


def test_paged_flash_decode_kernel_end_to_end(tiny_model):
    """decode_kernel='flash' routes decode through the Pallas paged kernel
    (interpret mode off-TPU) — tokens still match generate()."""
    model, params = tiny_model
    plugin = _plugin(num_slots=2, num_pages=16, decode_kernel="flash")
    eng = ServingEngine(model, params, plugin, GenerationConfig(max_new_tokens=4))
    eng.add_request(Request(uid=0, prompt=(5, 42, 7), max_new_tokens=4))
    while not eng.idle():
        eng.step()
    assert eng.results[0] == _ref_tokens(model, params, (5, 42, 7), 4)


# ---------------------------------------------------------------------------
# continuous batching: eviction, determinism, preemption, the static twin
# ---------------------------------------------------------------------------


def test_eviction_recompute_preserves_tokens(tiny_model):
    """A pool too small for the offered load forces preempt-and-recompute
    evictions; every request still emits exactly its solo-run tokens, and
    the host page mirror stays in sync with the device allocator."""
    model, params = tiny_model
    rng = np.random.default_rng(1)
    prompts = [tuple(int(x) for x in rng.integers(1, 255, n)) for n in (9, 7, 8)]
    plugin = ServingPlugin(num_slots=3, page_size=2, pages_per_slot=10,
                           num_pages=12, prefill_chunk=8, decode_kernel="native")
    eng = ServingEngine(model, params, plugin, GenerationConfig(max_new_tokens=8))
    for i, p in enumerate(prompts):
        eng.add_request(Request(uid=i, prompt=p, max_new_tokens=8))
    while not eng.idle():
        eng.step()
    assert eng.metrics["evictions"] > 0
    assert eng.free_page_mirror_in_sync()
    for i, p in enumerate(prompts):
        assert eng.results[i] == _ref_tokens(model, params, p, 8), f"request {i}"


def test_scheduler_determinism_under_seeded_trace(tiny_model):
    """Same seed -> same trace -> identical schedule (event-for-event) and
    identical tokens; a different seed schedules differently."""
    model, params = tiny_model
    gcfg = GenerationConfig(max_new_tokens=32)

    def run(seed):
        trace = synthesize_trace(seed, 8, vocab_size=255,
                                 prompt_len_range=(3, 10), new_tokens_range=(2, 6))
        eng = ServingEngine(model, params, _plugin(), gcfg)
        results = eng.run(trace)
        return eng.sched.events, results

    ev_a, res_a = run(7)
    ev_b, res_b = run(7)
    assert ev_a == ev_b
    assert res_a == res_b
    ev_c, _ = run(8)
    assert ev_c != ev_a


def test_preemption_mid_serve_drains_and_resumes(tiny_model):
    """A 'preempt' fault at the serve_step site (resilience/faults.py) drains
    the engine: finished results survive, every other request comes back
    intact, and a fresh engine finishing the remainder reproduces the
    uninterrupted run token-for-token."""
    from accelerate_tpu.resilience.faults import FaultEvent, FaultPlan, fault_plan

    model, params = tiny_model
    gcfg = GenerationConfig(max_new_tokens=32)
    trace = synthesize_trace(7, 8, vocab_size=255,
                             prompt_len_range=(3, 10), new_tokens_range=(2, 6))
    full = ServingEngine(model, params, _plugin(), gcfg).run(trace)

    eng = ServingEngine(model, params, _plugin(), gcfg)
    plan = FaultPlan([FaultEvent("preempt", at=9, site="serve_step")])
    with fault_plan(plan):
        partial = eng.run(trace)
    assert eng.interrupted
    assert plan.fired  # the injection actually happened
    remaining = eng.remaining_requests()
    assert set(partial) | {r.uid for r in remaining} == {r.uid for r in trace}

    resumed = ServingEngine(model, params, _plugin(), gcfg).run([
        Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        for r in remaining
    ])
    assert {**partial, **resumed} == full


def test_continuous_beats_static_batching(tiny_model):
    """The CPU-measurable acceptance proxy: on the bench's seeded dense
    trace, continuous batching beats fixed-batch scheduling on BOTH
    padding-waste fraction and scheduled-token efficiency."""
    model, params = tiny_model
    plugin = ServingPlugin(num_slots=4, page_size=4, pages_per_slot=16,
                           num_pages=40, prefill_chunk=16, decode_kernel="native")
    trace = synthesize_trace(0, 16, vocab_size=255, mean_interarrival_steps=0.5,
                             prompt_len_range=(4, 24), new_tokens_range=(4, 24))
    eng = ServingEngine(model, params, plugin, GenerationConfig(max_new_tokens=64))
    rep = replay(eng, trace)
    per_req = [(len(r.prompt), len(rep["results"][r.uid])) for r in trace]
    static = static_batching_report(per_req, plugin.num_slots)
    assert rep["padding_waste_frac"] < static["padding_waste_frac"]
    assert rep["scheduled_token_efficiency"] > static["scheduled_token_efficiency"]
    # the measured/predicted utilization twins agree to the EOS-exit error
    assert rep["kv_pool_utilization"] > 0
    assert abs(rep["kv_pool_utilization"] - rep["kv_pool_utilization_predicted"]) < 0.2
    # every report field the bench contract promises is present
    for field in ("tokens_per_sec_per_chip", "p50_token_latency_ms",
                  "p99_token_latency_ms", "kv_pool_utilization",
                  "padding_waste_frac", "scheduled_token_efficiency",
                  "scheduler_occupancy", "evictions"):
        assert field in rep, field


# ---------------------------------------------------------------------------
# plugin knobs + guards + lint
# ---------------------------------------------------------------------------


def test_serving_plugin_env_defaults(monkeypatch):
    monkeypatch.setenv("ACCELERATE_SERVE_SLOTS", "3")
    monkeypatch.setenv("ACCELERATE_SERVE_PAGE_SIZE", "8")
    monkeypatch.setenv("ACCELERATE_SERVE_PAGES", "21")
    monkeypatch.setenv("ACCELERATE_SERVE_KERNEL", "native")
    p = ServingPlugin()
    assert (p.num_slots, p.page_size, p.num_pages, p.decode_kernel) == (3, 8, 21, "native")
    # explicit arguments always win over env
    p2 = ServingPlugin(num_slots=5)
    assert p2.num_slots == 5
    # derived defaults: bucket ladder ends at prefill_chunk
    p3 = ServingPlugin(prefill_chunk=48)
    assert p3.prefill_buckets[-1] == 48 and p3.prefill_buckets[0] == 16
    with pytest.raises(ValueError):
        ServingPlugin(decode_kernel="mystery")
    with pytest.raises(ValueError):
        ServingPlugin(num_pages=2, pages_per_slot=8)
    with pytest.raises(ValueError):
        ServingPlugin(prefill_chunk=64, prefill_buckets=(16, 32))


def test_request_capacity_guard(tiny_model):
    model, params = tiny_model
    eng = ServingEngine(model, params, _plugin(), GenerationConfig(max_new_tokens=8))
    cap = min(eng.plugin.pages_per_slot, eng.plugin.num_pages) * eng.plugin.page_size
    with pytest.raises(ValueError):
        eng.add_request(Request(uid=0, prompt=tuple(range(1, cap + 1)), max_new_tokens=8))
    with pytest.raises(ValueError):
        eng.add_request(Request(uid=1, prompt=(), max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.add_request(Request(uid=2, prompt=(5, 6), max_new_tokens=0))


def test_admission_matches_submit_capacity(tiny_model):
    """A submit-accepted request is always eventually admittable: a prompt
    that exactly fills the pool's last page (pages_for(prompt) == num_pages)
    must serve, not idle-spin forever (the admit-vs-submit consistency
    regression — admission may not demand pages the pool can never have)."""
    model, params = tiny_model
    rng = np.random.default_rng(5)
    prompt = tuple(int(x) for x in rng.integers(1, 255, 17))  # 2 pages of 16, minus 15
    plugin = ServingPlugin(num_slots=1, page_size=16, pages_per_slot=2,
                           num_pages=2, prefill_chunk=32, decode_kernel="native")
    gcfg = GenerationConfig(max_new_tokens=1)
    eng = ServingEngine(model, params, plugin, gcfg)
    eng.add_request(Request(uid=0, prompt=prompt, max_new_tokens=1))
    eng.run([], max_steps=200)
    assert eng.results[0] == _ref_tokens(model, params, prompt, 1)
    # and through the offline wrapper that hit the livelock originally
    out = generate_paged(model, params, jnp.asarray([prompt], jnp.int32),
                         GenerationConfig(max_new_tokens=1))
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(generate(model, params, jnp.asarray([prompt], jnp.int32),
                            GenerationConfig(max_new_tokens=1))),
    )


def test_serving_decode_step_audits_donation_clean(tiny_model):
    """The satellite contract: the pool update is donation-clean — the
    graft-lint jaxpr audit of the real decode step reports no unsuppressed
    GL101/GL103/GL105 (and the AST sweep holds GL201 repo-wide)."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _plugin(num_slots=2, num_pages=16),
                        GenerationConfig(max_new_tokens=4))
    rep = eng.audit_decode_step(default_memory_kind="device")
    assert not rep.unsuppressed(), rep.render()
