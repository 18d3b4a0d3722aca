"""Speculative multi-token decode tests (ISSUE 13 / ROADMAP item 1): the
draft providers, the batched verify program's acceptance + page-rollback
arithmetic, and THE parity pin — greedy tokens through
``generate_paged(speculate=...)`` are BITWISE identical to ``generate()``,
including under eviction/recompute pressure and mixed LoRA tenant traffic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, generate, generate_paged
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from accelerate_tpu.serving import (
    NgramDraft,
    Request,
    ServingEngine,
    Speculator,
    predicted_acceptance,
    replay,
    synthesize_trace,
)
from accelerate_tpu.serving.scheduler import ContinuousBatchingScheduler
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
    kw.setdefault("pages_per_slot", 16)
    kw.setdefault("num_pages", 40)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("decode_kernel", "native")
    kw.setdefault("speculate", "ngram")
    kw.setdefault("speculate_k", 4)
    return ServingPlugin(**kw)


def _ref_tokens(model, params, prompt, n, **cfg_kw):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   GenerationConfig(max_new_tokens=n, **cfg_kw))
    return [int(x) for x in out[0]]


# ---------------------------------------------------------------------------
# draft providers (host-side, deterministic)
# ---------------------------------------------------------------------------


def test_ngram_draft_prompt_lookup():
    d = NgramDraft(max_ngram=3)
    # the trailing bigram (7, 8) occurred earlier, followed by 9, 10
    assert d.propose_one([1, 7, 8, 9, 10, 2, 7, 8], 3) == [9, 10, 2]
    # longest n-gram wins: trailing (5, 6) matches at two sites, the
    # 3-gram (4, 5, 6) disambiguates to the continuation after IT
    ctx = [4, 5, 6, 11, 9, 5, 6, 12, 4, 5, 6]
    assert d.propose_one(ctx, 2) == [11, 9]
    # no earlier occurrence of anything -> no drafts
    assert d.propose_one([1, 2, 3, 4], 3) == []
    # k clamps the continuation
    assert d.propose_one([7, 8, 9, 7, 8], 1) == [9]


def test_ngram_draft_batched_shapes_and_determinism():
    d = NgramDraft()
    ctxs = [[1, 2, 1, 2], [3, 4, 5], [9, 9, 9, 9, 9, 9, 9, 9]]
    drafts, lens = d.propose(ctxs, 4)
    assert drafts.shape == (3, 4) and lens.shape == (3,)
    assert lens[1] == 0                   # no repeat -> nothing proposed
    assert lens[2] == 4                   # unigram cycle fills the window
    assert list(drafts[2, :4]) == [9, 9, 9, 9]
    drafts2, lens2 = d.propose(ctxs, 4)
    np.testing.assert_array_equal(drafts, drafts2)
    np.testing.assert_array_equal(lens, lens2)


def test_speculator_clamps_depth_to_token_budget():
    sp = Speculator(NgramDraft(), 4, (4,))
    # a cycling context drafts the full k, but remaining-1 caps the depth:
    # a slot one token from max_new verifies at depth 0 (plain decode)
    drafts, spec = sp.draft([[5, 6, 5, 6, 5, 6, 5, 6]] * 2, [8, 1])
    assert spec[0] == 4 and spec[1] == 0  # min(draft_len, k=4, remaining-1)
    assert sp.bucket_for(0) == 4 and sp.bucket_for(4) == 4
    with pytest.raises(ValueError):
        Speculator(NgramDraft(), 4, (2,))  # ladder must cover k


def test_predicted_acceptance_arithmetic():
    """Hand-checkable replay: stream [9, 5, 6, 5] from prompt (5, 6, 5, 6).
    Pass 1 (e=1): context (5,6,5,6,9) has no 9-continuation beyond the
    unigram match at... -> drafts follow the last earlier occurrence; the
    acceptance count must equal the hand count."""
    d = NgramDraft()
    trace = [Request(uid=0, prompt=(5, 6, 5, 6), max_new_tokens=4)]
    results = {0: [9, 5, 6, 5]}
    pred = predicted_acceptance(trace, results, d, k=4)
    # walk: e=1 ctx=(5,6,5,6,9): no earlier 9 -> no drafts -> emit 1 (pass 1)
    # e=2 ctx=(..9,5): depth=min(4, 4-2-1)=1, trailing (6,5)? max bigram
    # (9,5) unseen; unigram 5 -> last earlier 5 at idx 2 -> cont (6,) ->
    # draft [6] matches stream[2]=6 -> m=1, emit 2 (pass 2)
    # e=4 = len(stream): done.  2 passes, 1 drafted, 1 accepted, 3 emitted.
    assert pred["verify_passes"] == 2
    assert pred["drafted"] == 1 and pred["accepted"] == 1
    assert pred["accept_rate"] == 1.0
    assert pred["tokens_per_step"] == 1.5


# ---------------------------------------------------------------------------
# THE parity pin: speculative greedy tokens == generate() tokens
# ---------------------------------------------------------------------------


def test_generate_paged_speculate_matches_generate(tiny_model):
    """Variable-length rows + EOS padding: speculation changes nothing
    about the emitted tokens (the acceptance contract extends)."""
    model, params = tiny_model
    batch = jnp.asarray([[5, 42, 7, 9], [11, 3, 0, 0]], jnp.int32)
    lens = jnp.asarray([4, 2])
    cfg = GenerationConfig(max_new_tokens=5, eos_token_id=2, pad_token_id=0)
    ref = generate(model, params, batch, cfg, prompt_lengths=lens)
    got = generate_paged(model, params, batch, cfg, prompt_lengths=lens,
                         speculate="ngram")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def _prompts_the_drafter_can_follow(model, params, lengths, new, k):
    """Prompts that hold the n-grams the model goes on to emit.  Each is a
    random 3-token seed followed by the model's own greedy continuation of
    it, cut at the wanted length: a tiny random model soon repeats itself,
    so its next tokens continue n-grams the prompt already contains — which
    uniformly random prompts never do, and then the prompt-lookup drafter
    has nothing to accept.  Seeds are drawn until the draft-and-verify
    arithmetic replayed over the prompt's solo run (no engine) both accepts
    a draft and rejects one, so acceptance AND rollback are exercised."""
    rng = np.random.default_rng(1)
    prompts = []
    for n in lengths:
        for _ in range(64):
            seed = tuple(int(x) for x in rng.integers(1, 255, 3))
            prompt = (seed + tuple(_ref_tokens(model, params, seed, n - 3)))[:n]
            pred = predicted_acceptance(
                [Request(uid=0, prompt=prompt, max_new_tokens=new)],
                {0: _ref_tokens(model, params, prompt, new)}, NgramDraft(), k)
            if 0 < pred["accepted"] < pred["drafted"]:
                prompts.append(prompt)
                break
        else:
            raise AssertionError(f"no seed gave a followable prompt of length {n}")
    return prompts


def test_speculate_parity_under_eviction_pressure(tiny_model):
    """A pool too small for the offered load forces evictions mid-
    speculation: every request still emits exactly its solo-run tokens,
    rejected drafts rolled real pages back, and the host free-page mirror
    ends exactly in sync with the device allocator."""
    model, params = tiny_model
    prompts = _prompts_the_drafter_can_follow(model, params, (9, 7, 8), new=8, k=3)
    plugin = ServingPlugin(num_slots=3, page_size=2, pages_per_slot=10,
                           num_pages=12, prefill_chunk=8,
                           decode_kernel="native", speculate="ngram",
                           speculate_k=3)
    eng = ServingEngine(model, params, plugin,
                        GenerationConfig(max_new_tokens=8))
    for i, p in enumerate(prompts):
        eng.add_request(Request(uid=i, prompt=p, max_new_tokens=8))
    while not eng.idle():
        eng.step()
    assert eng.metrics["evictions"] > 0
    assert eng.metrics["speculative_rollbacks"] > 0
    assert eng.metrics["accepted_draft_tokens"] > 0
    assert eng.free_page_mirror_in_sync()
    for i, p in enumerate(prompts):
        assert eng.results[i] == _ref_tokens(model, params, p, 8), f"request {i}"


def test_draft_model_provider_proposes_fixed_shape(tiny_model):
    """The draft-model provider's windowed forward: one fixed-shape jitted
    program regardless of context length (shorter contexts right-pad,
    longer ones slide), proposals deterministic."""
    from accelerate_tpu.serving import DraftModelDraft

    model, params = tiny_model
    d = DraftModelDraft(model, params, window=8)
    ctxs = [[5, 42, 7], list(range(1, 20))]   # short + longer-than-window
    drafts, lens = d.propose(ctxs, 3)
    assert drafts.shape == (2, 3) and list(lens) == [3, 3]
    drafts2, _ = d.propose(ctxs, 3)
    np.testing.assert_array_equal(drafts, drafts2)


@pytest.mark.slow
def test_speculate_draft_model_parity_and_acceptance(tiny_model):
    """The draft-model e2e (slow tier per the test-budget note): tokens
    identical to generate(), and — since the draft IS the target — the
    drafts accept."""
    model, params = tiny_model
    rng = np.random.default_rng(5)
    batch = jnp.asarray(rng.integers(1, 255, (2, 5)), jnp.int32)
    g = GenerationConfig(max_new_tokens=8)
    ref = generate(model, params, batch, g)
    got = generate_paged(model, params, batch, g, speculate="draft",
                         draft_model=model, draft_params=params,
                         speculate_k=3)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.slow
def test_draft_model_strict_compiles_under_varying_occupancy(tiny_model):
    """Regression: the draft batch pads to the FULL slot width.  A shape
    tracking the live candidate count recompiled the draft forward the
    first time occupancy changed (staggered arrivals/retirements), tripping
    strict_compiles mid-traffic."""
    model, params = tiny_model
    plugin = ServingPlugin(num_slots=3, page_size=4, pages_per_slot=16,
                           num_pages=24, prefill_chunk=16,
                           decode_kernel="native", speculate="draft",
                           speculate_k=2)
    # staggered lengths + arrivals: occupancy sweeps 1 -> 2 -> 3 -> 2 -> 1
    trace = [
        Request(uid=0, prompt=(5, 42, 7), max_new_tokens=12, arrival_step=0),
        Request(uid=1, prompt=(9, 11), max_new_tokens=4, arrival_step=4),
        Request(uid=2, prompt=(3, 8, 2, 6), max_new_tokens=7, arrival_step=8),
    ]
    eng = ServingEngine(model, params, plugin,
                        GenerationConfig(max_new_tokens=12),
                        draft_model=model, draft_params=params)
    rep = replay(eng, trace)  # strict_compiles=True raises on a recompile
    assert rep["completed"] == 3 and rep["compiles_measured"] == 0
    for r in trace:
        assert rep["results"][r.uid] == _ref_tokens(
            model, params, r.prompt, r.max_new_tokens)
    # the draft-model predicted twin stays idle by design (no model-free
    # replay exists for a model's drafts) while the measured side records
    assert rep["accept_rate"] > 0 and rep["accept_rate_predicted"] == 0.0


def test_speculate_with_lora_tenant_mix(tiny_model, tmp_path):
    """Mixed-tenant traffic with hot-swap + page-pressure eviction, served
    speculatively: per-request tokens equal the dedicated single-request
    ``generate_paged`` pass with that adapter, zero post-warmup compiles
    (``strict_compiles`` raises otherwise), mirror in sync."""
    from accelerate_tpu.serving import AdapterStore
    from accelerate_tpu.utils.dataclasses import LoraPlugin

    model, params = tiny_model
    cfg = model.config
    lplug = LoraPlugin(rank=4, pool_slots=2, kernel="native")

    def store(d):
        s = AdapterStore(params, lplug, dtype=cfg.dtype, offload_dir=str(d))
        for t in (1, 2, 3):
            s.publish_random(t, jax.random.PRNGKey(1000 + t))
        return s

    splug = ServingPlugin(num_slots=4, page_size=2, pages_per_slot=10,
                          num_pages=14, prefill_chunk=8,
                          decode_kernel="native", speculate="ngram",
                          speculate_k=3)
    trace = synthesize_trace(3, 7, vocab_size=255, prompt_len_range=(3, 9),
                             new_tokens_range=(3, 6), adapters=3)
    eng = ServingEngine(model, params, splug,
                        GenerationConfig(max_new_tokens=32),
                        adapters=store(tmp_path / "a"))
    rep = replay(eng, trace)  # strict_compiles=True
    assert rep["completed"] == len(trace)
    assert rep["compiles_measured"] == 0
    assert eng.free_page_mirror_in_sync()
    ref_store = store(tmp_path / "b")
    for r in trace:
        out = generate_paged(model, params, jnp.asarray([r.prompt], jnp.int32),
                             GenerationConfig(max_new_tokens=r.max_new_tokens),
                             adapters=ref_store, adapter_ids=[r.adapter_id])
        ref = [int(x) for x in np.asarray(out[0])][: len(rep["results"][r.uid])]
        assert rep["results"][r.uid] == ref, f"request {r.uid} (tenant {r.adapter_id})"


# ---------------------------------------------------------------------------
# strict compiles, twins, metrics, determinism
# ---------------------------------------------------------------------------


def test_speculate_replay_strict_compiles_and_twins(tiny_model):
    """The seeded replay with speculation on: zero post-warmup compiles
    across the k-bucket ladder, tokens_per_step beats the plain-decode 1.0
    floor, and the accept-rate/tokens-per-step twins agree within their
    declared tolerance (registered in the central TwinRegistry)."""
    from accelerate_tpu.telemetry import twin_registry

    model, params = tiny_model
    trace = synthesize_trace(0, 16, vocab_size=255, mean_interarrival_steps=0.5,
                             prompt_len_range=(4, 24), new_tokens_range=(4, 24))
    eng = ServingEngine(model, params, _plugin(),
                        GenerationConfig(max_new_tokens=64))
    rep = replay(eng, trace)  # raises on any mid-traffic compile
    assert rep["compiles_measured"] == 0
    assert rep["speculate"] == "ngram" and rep["speculate_k"] == 4
    assert rep["tokens_per_step"] > 1.0
    assert rep["verify_steps"] > 0 and rep["accept_rate"] > 0
    # one verify program per bucket joins the predicted program set
    assert rep["programs_predicted"] == \
        len(eng.plugin.prefill_buckets) + 3 + len(eng.plugin.speculate_buckets)
    for name in ("speculate.accept_rate", "speculate.tokens_per_step"):
        twin = twin_registry().get(name)
        assert twin is not None and twin.status in ("ok", "warn"), \
            (name, twin and twin.row())
    assert eng.free_page_mirror_in_sync()


def test_speculate_scheduler_event_log_is_deterministic(tiny_model):
    """Same seed -> identical schedule including the per-pass accepted
    counts in the 'verify' events; a different seed schedules differently."""
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
    assert ev_a == ev_b and res_a == res_b
    assert any(ev[0] == "verify" for ev in ev_a)
    ev_c, _ = run(8)
    assert ev_c != ev_a


def test_speculate_verify_step_audits_donation_clean(tiny_model):
    """The verify program's allocate + multi-token append + page rollback
    pytree aliases the donated cache (no GL101/GL103/GL105)."""
    model, params = tiny_model
    eng = ServingEngine(model, params, _plugin(num_slots=2, num_pages=16),
                        GenerationConfig(max_new_tokens=4))
    rep = eng.audit_verify_step(default_memory_kind="device")
    assert not rep.unsuppressed(), rep.render()


# ---------------------------------------------------------------------------
# scheduler accounting (pure host arithmetic, no device programs)
# ---------------------------------------------------------------------------


def test_scheduler_speculative_page_accounting():
    sched = ContinuousBatchingScheduler(
        num_slots=2, num_pages=8, page_size=4, pages_per_slot=4,
        prefill_chunk=8, prefill_buckets=(8,), speculate_k=3,
    )
    # admission demands prompt + first-verify worst case, clamped by the
    # request's own budget — never more than submit guaranteed the pool has
    req = Request(uid=0, prompt=(1, 2, 3, 4, 5), max_new_tokens=8)
    # prompt: 2 pages; verify writes positions 5..8 -> page 2 -> 3 pages
    assert sched.admission_page_need(req) == 3
    short = Request(uid=1, prompt=(1, 2, 3), max_new_tokens=1)
    assert sched.admission_page_need(short) == 1  # depth 0: plain decode
    sched.submit(req)
    sched.admit()
    slot = next(iter(sched.slots))
    st = sched.slots[slot]
    st.prefilled = 5
    sched.free_pages = sched.num_pages - 2  # the 2 prompt pages
    st.tokens.append(42)  # first token sampled off the prefill logits
    # worst case for a depth-3 verify at kv=5: positions 5..8 cross into
    # page 2 -> exactly 1 fresh page
    assert sched.verify_page_need([slot], {slot: 3}) == {slot: 1}
    # device accepts m=2 -> kv 5 -> 8, pages for kv 8 = 2 (no new page...
    # positions 5,6,7 stay in page 1) -> consumed = pages_for(8)-pages_for(5) = 0
    sched.note_verify({slot: 2})
    assert st.kv_len == 8
    assert sched.free_pages == sched.num_pages - 2
    # next pass crosses the boundary: kv=8, depth 1 writes 8..9 -> 1 page
    assert sched.verify_page_need([slot], {slot: 1}) == {slot: 1}
    sched.note_verify({slot: 1})
    assert st.kv_len == 10 and sched.free_pages == sched.num_pages - 3
    # finish frees pages_for(kv_len)=3 — the kv_tokens discipline (NOT the
    # possibly-shorter host token list)
    sched.finish(slot)
    assert sched.free_pages == sched.num_pages


def test_scheduler_degrades_draft_depth_before_evicting():
    """Page pressure first COSTS DRAFT DEPTH, not live sequences: the
    worst-case speculative reservation is transient (rejected pages roll
    back), so the planner zeroes depths — youngest-admitted first — down
    to the plain-decode floor before the shared evict loop may run."""
    sched = ContinuousBatchingScheduler(
        num_slots=3, num_pages=6, page_size=2, pages_per_slot=4,
        prefill_chunk=4, prefill_buckets=(4,), speculate_k=2,
    )
    for uid in range(3):
        sched.submit(Request(uid=uid, prompt=(1, 2), max_new_tokens=6))
    admitted = sched.admit()
    assert len(admitted) == 3
    for s in admitted:
        st = sched.slots[s]
        st.prefilled = 2
        st.tokens.append(7)
    sched.free_pages = 2  # floor demand: 1 page/slot (kv=2 is a page start)
    spec = {s: 2 for s in admitted}
    survivors, evicted = sched.plan_speculative_evictions(list(admitted), spec)
    # worst case was 2 pages/slot = 6 > 2; floor is 3 > 2 -> depths zero
    # youngest-first, then ONE eviction covers the remaining floor deficit
    assert all(spec[s] == 0 for s in spec)
    assert any(ev[0] == "despeculate" for ev in sched.events)
    assert len(evicted) == 1 and len(survivors) == 2
    assert sum(sched.verify_page_need(survivors, spec).values()) <= sched.free_pages

    # with headroom for the floor but not the worst case: NO eviction at
    # all — depth degradation alone absorbs the pressure
    sched2 = ContinuousBatchingScheduler(
        num_slots=2, num_pages=8, page_size=2, pages_per_slot=4,
        prefill_chunk=4, prefill_buckets=(4,), speculate_k=2,
    )
    for uid in range(2):
        sched2.submit(Request(uid=uid, prompt=(1, 2), max_new_tokens=6))
    adm2 = sched2.admit()
    for s in adm2:
        sched2.slots[s].prefilled = 2
        sched2.slots[s].tokens.append(7)
    sched2.free_pages = 3  # fits one worst-case (2) + one floor (1)
    spec2 = {s: 2 for s in adm2}
    survivors2, evicted2 = sched2.plan_speculative_evictions(list(adm2), spec2)
    assert evicted2 == [] and set(survivors2) == set(adm2)
    assert sorted(spec2.values()) == [0, 2]  # only the youngest degraded


def test_generate_paged_speculate_false_overrides_armed_plugin(tiny_model):
    """speculate=False is an explicit opt-out: it must win over a plugin
    (or env) that armed speculation — the do_sample guard then never fires
    and sampling decodes through the plain path."""
    model, params = tiny_model
    batch = jnp.asarray([[5, 42, 7, 9]], jnp.int32)
    armed = ServingPlugin(num_slots=1, page_size=4, pages_per_slot=8,
                          num_pages=8, prefill_chunk=8, decode_kernel="native",
                          speculate="ngram", speculate_k=2)
    out = generate_paged(model, params, batch,
                         GenerationConfig(max_new_tokens=3, do_sample=True),
                         serving_plugin=armed, speculate=False)
    assert out.shape == (1, 3)


# ---------------------------------------------------------------------------
# plugin knobs + guards
# ---------------------------------------------------------------------------


def test_speculate_plugin_env_knobs(monkeypatch):
    monkeypatch.setenv("ACCELERATE_SERVE_SPECULATE", "on")
    monkeypatch.setenv("ACCELERATE_SERVE_SPECULATE_K", "6")
    monkeypatch.setenv("ACCELERATE_SERVE_SPECULATE_DRAFT", "48")
    p = ServingPlugin()
    assert (p.speculate, p.speculate_k, p.speculate_draft_window) == ("ngram", 6, 48)
    assert p.speculate_buckets == (6,)
    # explicit arguments always win over env
    p2 = ServingPlugin(speculate="draft", speculate_k=2,
                       speculate_buckets=(2, 4))
    assert p2.speculate == "draft" and p2.speculate_buckets == (2, 4)
    monkeypatch.delenv("ACCELERATE_SERVE_SPECULATE")
    assert ServingPlugin().speculate == "off"
    # the generate_paged(speculate=True) boolean convention works on the
    # plugin too
    assert ServingPlugin(speculate=True).speculate == "ngram"
    assert ServingPlugin(speculate=False).speculate == "off"
    with pytest.raises(ValueError):
        ServingPlugin(speculate="mystery")
    with pytest.raises(ValueError):
        ServingPlugin(speculate="ngram", speculate_k=4, speculate_buckets=(2,))
    with pytest.raises(ValueError):
        ServingPlugin(speculate="ngram", speculate_k=0)


def test_speculate_guards(tiny_model):
    model, params = tiny_model
    # greedy only: sampling breaks the greedy-prefix acceptance pin
    with pytest.raises(ValueError, match="greedy"):
        ServingEngine(model, params, _plugin(),
                      GenerationConfig(max_new_tokens=4, do_sample=True))
    # draft mode needs the draft model
    with pytest.raises(ValueError, match="draft_model"):
        ServingEngine(model, params, _plugin(speculate="draft"),
                      GenerationConfig(max_new_tokens=4))
