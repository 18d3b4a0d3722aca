"""The host ledger (accelerate_tpu/telemetry/host_ledger.py): the engine's own
account of the host's time, tracer on or off — the per-phase tick ledger, the
caller's time between ticks, the collector's pauses, the stall log, and
``warmup()`` by program and by part.  CPU, tiny model; a ``VirtualClock``
wherever a time is asserted."""

import gc
import logging
import time

import jax
import jax.numpy as jnp
import pytest

from accelerate_tpu.analysis.compiled_audit import PARTS, CompileCounter
from accelerate_tpu.telemetry import HostLedger, VirtualClock, install_global_gc_hook
from accelerate_tpu.telemetry import host_ledger as HL
from accelerate_tpu.utils.dataclasses import ServingPlugin

PHASES = ("control", "schedule", "plan", "stage", "dispatch", "host_sync", "commit")


def _setup(num_slots=4, **plugin):
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig.tiny())
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    plugin = ServingPlugin(**{**dict(num_slots=num_slots, page_size=4, pages_per_slot=16,
                                     num_pages=40, prefill_chunk=16, decode_kernel="native"),
                              **plugin})
    return model, params, plugin, GenerationConfig(max_new_tokens=64)


def _engine(**kw):
    from accelerate_tpu.serving import ServingEngine

    return ServingEngine(*_setup(**kw))


def _request(uid, prompt_len=8, new=12):
    from accelerate_tpu.serving import Request

    return Request(uid=uid, prompt=tuple(range(1, prompt_len + 1)), max_new_tokens=new)


def _virtual(eng, clk):
    """An injected clock with NO tracer armed (``enable_tracing(clock=)`` is
    the public way to inject one, and arms a tracer)."""
    eng._clock = eng.sched.clock = clk
    eng._ledger.set_clock(clk)


def _serve(eng, n=3):
    for uid in range(n):
        eng.add_request(_request(uid, prompt_len=8 + 12 * uid, new=6 + uid))
    while not eng.idle():
        eng.step()
    return dict(eng.results)


def _engine_spans(eng):
    return [e for e in eng.trace.recorder.events() if e[0] == "X" and e[3] == "engine"]


# -- the tick ledger ---------------------------------------------------------------

def test_a_fresh_engine_holds_the_ledgers_keys_at_zero():
    eng = _engine()
    m = eng.metrics
    for key in HL.COUNT_KEYS + HL.SECONDS_KEYS:
        assert m[key] == 0, key
    assert {"ticks.decode", "tick_wall_s.decode", "outside_s_sum", "add_request_s_sum",
            "gc_pause_s_sum", "gc_pause_n.gen2", "stall_n", "stall_excess_s_sum"} <= set(m)
    assert not eng.stalls and eng.stalls.maxlen == HL.STALL_LOG_LEN and eng.warmup_report == []
    assert not [k for k in m if k.startswith("host_s.")]         # phases appear with their first tick


def test_phases_and_outside_partition_the_engines_time_to_the_clocks_step():
    eng = _engine()
    eng.warmup()
    clk = VirtualClock(1.0)
    eng.enable_tracing(clock=clk, capacity=1 << 16)
    eng.add_request(_request(0, prompt_len=40, new=10))
    t_submit = clk.now - 1             # add_request reads twice: the request's stamp, then its own end
    while not eng.idle():
        eng.step()
        clk.now += 7.0                 # the caller's own time between ticks
    m, led = eng.metrics, eng._ledger
    ticks = sum(m[f"ticks.{k}"] for k in HL.TICK_KINDS)
    assert ticks == eng.steps and m["ticks.prefill"] == 3 and m["ticks.decode"] == 9
    wall = sum(m[f"tick_wall_s.{k}"] for k in HL.TICK_KINDS)
    # the engine had work from the request's stamp to the last tick's return:
    # every reading of the clock in between is inside a tick or in `outside`
    assert wall + m["outside_s_sum"] == led.t_end - t_submit
    # the stamp to tick 0's first reading, then the 7 added and one step to the next reading
    assert m["outside_n"] == ticks and m["outside_s_sum"] == 2 + (ticks - 1) * 8.0
    # a tick's wall is its phases plus one clock step between neighbouring brackets
    spans = [e for e in _engine_spans(eng) if e[1] != "gc"]
    phases = sum(v for k, v in m.items() if k.startswith("host_s."))
    assert wall == phases + (len(spans) - ticks) * clk.step
    for kind in ("decode", "prefill"):
        held = sum(v for k, v in m.items() if k.startswith(f"host_s.{kind}."))
        n = sum(1 for e in spans if (e[6] or {}).get("step") in _steps_of(eng, kind, spans))
        assert m[f"tick_wall_s.{kind}"] == held + (n - m[f"ticks.{kind}"]) * clk.step
        assert m[f"tick_wall_max_s.{kind}"] <= m[f"tick_wall_s.{kind}"]
    assert {k.rsplit(".", 1)[1] for k in m if k.startswith("host_s.decode.")} == set(PHASES) | {"trace"}
    assert m["host_n.decode.host_sync"] == m["ticks.decode"]
    assert m["host_n.prefill.host_sync"] == 1                  # the chunk that ends the prompt
    assert m["add_request_n"] == 1 and m["add_request_s_sum"] == 1.0 == m["add_request_s_max"]


def _steps_of(eng, kind, spans):
    """The ticks (``step`` numbers) of a kind, by their dispatch span."""
    return {(e[6] or {})["step"] for e in spans if e[1] == f"dispatch:{kind}"}


def test_tracer_on_and_off_read_the_same_ledger_and_the_same_tokens():
    eng_off, eng_on = _engine(), _engine()
    eng_off.warmup()
    _virtual(eng_off, VirtualClock(1.0))
    eng_on.enable_tracing(clock=VirtualClock(1.0), capacity=1 << 16)
    assert _serve(eng_off) == _serve(eng_on)                   # bitwise: telemetry sees, never steers
    off, on = eng_off.metrics, eng_on.metrics
    keep = lambda m: {k: v for k, v in m.items()               # noqa: E731
                      if k.split(".")[0] in ("host_s", "host_n", "host_max_s", "ticks")
                      and not k.endswith(".trace")}
    assert keep(off) == keep(on) and keep(off)
    assert not [k for k in off if k.endswith(".trace")]        # the tracer's own phase: only when armed
    assert on["host_n.decode.trace"] == on["ticks.decode"]
    # the one extra bracket a tick is all that separates the two walls
    ticks = sum(on[f"ticks.{k}"] for k in HL.TICK_KINDS)
    wall = lambda m: sum(m[f"tick_wall_s.{k}"] for k in HL.TICK_KINDS)    # noqa: E731
    trace_s = sum(v for k, v in on.items() if k.startswith("host_s.") and k.endswith(".trace"))
    assert wall(on) - wall(off) == trace_s + ticks


def test_no_span_is_added_to_the_engine_track_of_a_tick():
    """What ``trace_reduce`` walks: a decode tick is nine spans, as before the
    ledger (``gc`` apart, which is one a collector pause of a millisecond)."""
    eng = _engine()
    eng.warmup()
    eng.enable_tracing(clock=VirtualClock(1.0), capacity=1 << 16)
    eng.add_request(_request(0, prompt_len=8, new=12))
    while not eng.idle():
        eng.step()
    spans = [e for e in _engine_spans(eng) if e[1] != "gc"]
    decode_steps = sorted(_steps_of(eng, "decode", spans))[:10]
    assert len(decode_steps) == 10
    names = [e[1] for e in spans if (e[6] or {}).get("step") in decode_steps]
    assert len(names) == 90
    assert sorted(names[:9]) == sorted(["control", "schedule", "plan", "stage:decode",
                                        "dispatch:decode", "host_sync", "commit", "commit", "trace"])
    instants = {e[1] for e in eng.trace.recorder.events() if e[0] == "i" and e[3] == "engine"}
    assert "stall" not in instants                             # virtual time: every tick its class's median


def test_the_callers_time_is_outside_and_an_idle_engines_wait_is_not():
    eng = _engine()
    eng.warmup()
    clk = VirtualClock(1.0)
    _virtual(eng, clk)
    clk.now += 1000.0                                          # idle: nobody's time
    eng.add_request(_request(0, new=3))
    clk.now += 5.0                                             # the caller, with work queued
    eng.step()
    m = eng.metrics
    assert m["outside_n"] == 1 and m["outside_s_sum"] == 1 + 5 + 1     # stamp -> return, the 5, the next reading
    assert m["add_request_s_sum"] == 1.0
    while not eng.idle():
        eng.step()
    before = m["outside_s_sum"]
    clk.now += 1000.0                                          # drained: idle again
    eng.add_request(_request(1, new=2))
    eng.step()
    assert m["outside_s_sum"] == before + 2 and m["outside_s_max"] == 7.0


# -- stalls -----------------------------------------------------------------------------

def _plant(eng, monkeypatch, at_calls, what):
    """Run ``what()`` inside the ``dispatch`` phase of the given decode calls."""
    real, calls = eng._run_decode, []

    def slow(*args):
        calls.append(1)
        if len(calls) in at_calls:
            what()
        return real(*args)

    monkeypatch.setattr(eng, "_run_decode", slow)


def test_a_planted_sleep_is_one_logged_stall_that_names_its_phase_and_the_next_is_rate_limited(
        monkeypatch, caplog):
    eng = _engine()
    eng.warmup()
    eng.enable_tracing()                                       # the stall is an instant on the ring too
    _plant(eng, monkeypatch, (14, 16), lambda: time.sleep(0.2))
    eng.add_request(_request(0, new=30))
    with caplog.at_level(logging.WARNING, logger="accelerate_tpu.serving"):
        while not eng.idle():
            eng.step()
    slept = [r for r in eng.stalls if r["wall_s"] >= 0.2]
    assert len(slept) == 2 and eng.metrics["stall_n"] >= 2
    for row in slept:
        assert row["kind"] == "decode" and row["phase"] == "dispatch" and not row["carried"]
        assert row["phases"]["dispatch"] >= 0.2 > row["phases"]["host_sync"]
        assert row["median_s"] < 0.05 and row["live"] == 1 and row["waiting"] == 0
        assert set(row["phases"]) == set(PHASES) | {"trace"}
    assert slept[1]["step"] == slept[0]["step"] + 2
    m = eng.metrics
    assert m["stall_excess_s_sum"] >= 0.3 and m["stall_s_by_phase.dispatch"] >= 0.3
    lines = [r.getMessage() for r in caplog.records if r.getMessage().startswith("slow ")]
    assert len(lines) == m["stall_n"] + m["outside_stall_n"] - m["stall_log_suppressed"] >= 1
    assert m["stall_log_suppressed"] >= 1                      # the second, inside the same second
    first = next(line for line in lines if f"slow tick {slept[0]['step']} decode" in line)
    assert "(median " in first and ": dispatch 2" in first and " gc " in first and " outside " in first
    stalls = [e for e in eng.trace.recorder.events() if e[0] == "i" and e[1] == "stall"]
    assert len(stalls) == m["stall_n"] and stalls[0][6]["phase"] == "dispatch"


def test_a_collection_inside_a_tick_is_counted_and_stands_in_that_ticks_stall_row(monkeypatch):
    eng = _engine()
    eng.warmup()
    eng.enable_tracing()

    def collect():
        gc.collect()
        time.sleep(0.1)                                        # a stall whatever the heap's size

    _plant(eng, monkeypatch, (12,), collect)
    eng.add_request(_request(0, new=20))
    while not eng.idle():
        eng.step()
    m = eng.metrics
    assert m["gc_pause_n.gen2"] >= 1 and m["gc_pause_n"] >= m["gc_pause_n.gen2"]
    assert 0.0 < m["gc_pause_s_max"] <= m["gc_pause_s_sum"]
    (row,) = [r for r in eng.stalls if r["wall_s"] >= 0.1]
    assert 0.0 < row["gc_s"] <= row["phases"]["dispatch"] and row["phase"] == "dispatch"
    assert m["stall_gc_s_sum"] >= row["gc_s"]
    if row["gc_s"] >= HL.GC_SPAN_MIN_S:                         # a long pause is a span on the engine track
        spans = [e for e in _engine_spans(eng) if e[1] == "gc"]
        assert spans and spans[-1][6]["generation"] == 2


def test_two_engines_share_one_hook_and_read_their_own_deltas():
    hook = install_global_gc_hook()
    assert install_global_gc_hook() is hook and gc.callbacks.count(hook) == 1
    first = _engine()
    gc.collect()
    second = _engine()
    assert gc.callbacks.count(hook) == 1
    gc.collect()
    for eng in (first, second):
        eng.warmup()
        eng.add_request(_request(0, new=2))
        eng.step()
    a, b = first.metrics, second.metrics
    assert b["gc_pause_n.gen2"] >= 1 and a["gc_pause_n.gen2"] >= b["gc_pause_n.gen2"] + 1
    assert a["gc_pause_s_sum"] > b["gc_pause_s_sum"] > 0.0
    assert hook.n >= a["gc_pause_n"] and hook.by_gen[2] >= a["gc_pause_n.gen2"]


class _Clock:
    """A clock the test moves by hand."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _drive(led, clk, kind, phases, outside=0.001):
    """One synthetic tick: the phases' seconds, in order."""
    clk.now += outside
    led.begin_tick()
    for name, seconds in phases:
        with led.phase(name, step=0):
            clk.now += seconds
    return led.end_tick(kind, 0, 0)


def test_a_decode_tick_behind_an_unfetched_prefill_chunk_is_its_own_class():
    """JoyAI's traffic: a chunk that does not end its prompt is dispatched and
    not waited for, so the decode tick behind it waits for both programs —
    36.5 ms against 14.6.  Keyed by kind alone those ticks would all be
    stalls, or would hide every stall."""
    clk = _Clock()
    led = HostLedger({}, clk)
    chunk = (("stage:prefill", 0.001), ("dispatch:prefill", 0.001))
    decode = lambda sync: (("stage:decode", 0.001), ("dispatch:decode", 0.0005),    # noqa: E731
                           ("host_sync", sync))
    for _ in range(40):
        assert _drive(led, clk, "prefill", chunk) is None
        assert _drive(led, clk, "decode", decode(0.0345)) is None          # carries the chunk
        assert _drive(led, clk, "decode", decode(0.0126)) is None
    assert led.metrics["stall_n"] == 0
    alone = _drive(led, clk, "decode", decode(0.0585))                     # 60 ms with nothing carried
    assert alone["carried"] == 0 and alone["median_s"] == pytest.approx(0.0141, abs=1e-3)
    _drive(led, clk, "prefill", chunk)
    assert _drive(led, clk, "decode", decode(0.0585)) is None              # ... and behind a chunk: no stall
    _drive(led, clk, "prefill", chunk)
    row = _drive(led, clk, "decode", decode(0.1367))
    assert row["carried"] == 1 and row["phase"] == "host_sync"
    assert row["median_s"] == pytest.approx(0.036, abs=1e-3) and row["wall_s"] == pytest.approx(0.1382)
    assert led.metrics["stall_n"] == 2 and len(led.stalls) == 2
    # a chunk that ends its prompt waits for its program: not a stalled chunk
    ending = chunk + (("host_sync", 0.030),)
    for _ in range(12):
        assert _drive(led, clk, "prefill", ending) is None
    # the caller's pause is a stall of its own kind
    gap = _drive(led, clk, "decode", decode(0.0126), outside=0.5)
    assert gap is None and led.stalls[-1]["kind"] == "outside" and led.stalls[-1]["wall_s"] == pytest.approx(0.5)
    assert led.metrics["outside_stall_n"] == 1 and led.metrics["stall_n"] == 2
    assert led.metrics["outside_stall_excess_s_sum"] == pytest.approx(0.499)


def test_the_stall_line():
    row = {"step": 1843, "kind": "decode", "bucket": 0, "carried": 1, "wall_s": 0.1382,
           "median_s": 0.0365, "phase": "host_sync", "gc_s": 0.0, "outside_s": 0.00004,
           "phases": {"control": 0.00002, "stage": 0.0019, "host_sync": 0.1013, "dispatch": 0.0005}}
    assert HL.stall_line(row) == ("slow tick 1843 decode 138.2 ms (median 36.5): host_sync 101.3 gc 0.0 "
                                  "stage 1.9 dispatch 0.5 control 0.0 outside 0.0")
    gap = dict(row, kind="outside", phases={}, wall_s=0.25, gc_s=0.2, outside_s=0.25, median_s=0.00005)
    assert HL.stall_line(gap) == "slow gap before tick 1843 250.0 ms (median 0.1): gc 200.0 outside 250.0"
    assert HL.stall_line(dict(row, kind="prefill", bucket=2048)).startswith("slow tick 1843 prefill[2048] ")


def test_the_ledgers_own_time_a_tick_is_microseconds():
    """The budget is 10 us a tick (measured in PERF.md); held here with the
    room a shared test machine needs."""
    led = HostLedger({})
    names = ("control", "schedule", "plan", "stage:decode", "dispatch:decode", "host_sync",
             "commit", "commit")
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for step in range(2000):
            led.begin_tick()
            for name in names:
                with led.phase(name, step=step):
                    pass
            led.end_tick("decode", 0, step)
        best = min(best, (time.perf_counter() - t0) / 2000)
    assert best < 60e-6
    assert led.metrics["ticks.decode"] == 10_000 and led.metrics["host_n.decode.commit"] == 10_000


# -- warm-up by program and by part ------------------------------------------------------------

@pytest.fixture
def empty_compile_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_warmup_reports_each_program_cold_then_warm(empty_compile_cache):
    from accelerate_tpu.serving import engine as engine_mod

    reports = []
    for _ in range(2):
        jax.clear_caches()                       # nothing in memory: the cache on disk decides
        engine_mod._engine_fns.cache_clear()
        eng = _engine(num_slots=3, prefill_buckets=(8, 16))     # a geometry no other test compiles
        t0 = time.perf_counter()
        compiles = eng.warmup()
        reports.append((eng, compiles, time.perf_counter() - t0))
    (cold, n_cold, wall_cold), (warm, n_warm, wall_warm) = reports
    for eng, wall in ((cold, wall_cold), (warm, wall_warm)):
        rows = {r["label"]: r for r in eng.warmup_report}
        assert set(rows) == eng.warmup_programs() == {"decode", "prefill[8]", "prefill[16]",
                                                      "sample_first", "release"}
        m = eng.metrics
        for row in rows.values():
            parts = row["trace_s"] + row["lower_s"] + row["backend_s"] + row["cache_load_s"]
            assert parts + row["execute_s"] == pytest.approx(row["wall_s"])
            assert parts <= row["wall_s"] * 1.05 and row["execute_s"] >= -0.05 * row["wall_s"]
            assert min(row["trace_s"], row["lower_s"], row["cache_load_s"]) >= 0.0
        for key in PARTS:
            assert m[f"warmup_{key}"] >= sum(r[key] for r in rows.values()) - 1e-9
        assert sum(r["wall_s"] for r in rows.values()) <= m["warmup_wall_s"] <= wall
        assert eng.warmed_up
    assert n_cold >= 5 and cold.metrics["warmup_cache_misses"] >= 5 and cold.metrics["warmup_cache_hits"] == 0
    assert {r["cache"] for r in cold.warmup_report} == {"miss"}
    assert cold.metrics["warmup_backend_s"] > 0.0 == cold.metrics["warmup_cache_load_s"]
    assert warm.metrics["warmup_cache_hits"] >= 5 and warm.metrics["warmup_cache_misses"] == 0
    assert {r["cache"] for r in warm.warmup_report} == {"hit"}
    assert warm.metrics["warmup_cache_load_s"] > 0.0
    assert warm.metrics["warmup_backend_s"] < cold.metrics["warmup_backend_s"]
    assert warm.metrics["warmup_trace_s"] > 0.0 and warm.metrics["warmup_lower_s"] > 0.0
    assert n_warm == n_cold                      # on jax 0.9 a load from the cache is a backend event too


def test_warmup_spans_lie_on_a_track_of_their_own():
    eng = _engine()
    eng.enable_tracing(clock=VirtualClock(1.0))
    eng.warmup()
    events = [e for e in eng.trace.recorder.events() if e[0] == "X"]
    assert {e[3] for e in events} == {"warmup"}                # nothing on the engine track
    assert [e[1] for e in events] == ["warmup:decode", "warmup:prefill[16]", "warmup:sample_first",
                                      "warmup:release", "warmup:decode"]
    assert {(e[6] or {})["cache"] for e in events} <= {"hit", "miss", "none"}
    (decode,) = [r for r in eng.warmup_report if r["label"] == "decode"]
    assert decode["wall_s"] == sum(e[5] for e in events if e[1] == "warmup:decode")


def test_a_trace_inside_a_trace_is_booked_once():
    """jax times every ``jit`` it traces, the inner ones inside the outer's
    bracket: the counter books the outermost, so the parts fit the wall."""
    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 3.0

    def outer(x):
        for _ in range(20):
            x = inner(x) + 1.0
        return x

    with CompileCounter() as counter:
        t0 = time.perf_counter()
        jax.jit(outer)(jnp.ones((7, 13))).block_until_ready()
        wall = time.perf_counter() - t0
    trace_s, lower_s, backend_s, load_s, hits, misses = counter.parts()
    assert trace_s > 0.0 and lower_s > 0.0 and counter.count >= 1
    assert trace_s + lower_s + max(backend_s, 0.0) + load_s <= wall * 1.05


def test_a_collection_inside_the_ledgers_own_gc_loop_does_not_break_it():
    """With a tracer armed ``_read_gc`` records a span for each long pause, and
    recording allocates: a collection may start INSIDE the loop, and the
    process-wide listener then appends to the very deque the loop walks, on
    the same thread (``RuntimeError: deque mutated during iteration``: a traced
    run of ``qwen3-next.serve_assist``, ``ROADMAP.md`` C24).  Planted here by
    a recorder that fires the listener from ``complete``."""
    hook = install_global_gc_hook()
    led = HostLedger({})

    class CollectsWhenItRecords:
        spans = 0

        def complete(self, name, track, start, end, **args):
            self.spans += 1
            hook("start", {"generation": 0})
            hook("stop", {"generation": 0, "collected": 0})

    rec = CollectsWhenItRecords()
    led.set_clock(led.clock, rec)
    for _ in range(3):                       # three pauses the ledger has not read yet
        hook("start", {"generation": 2})
        hook._t0 -= 1.0                      # ... each a second long: worth a span
        hook("stop", {"generation": 2, "collected": 7})
    led._read_gc()
    assert rec.spans >= 3 and led.metrics["gc_pause_n.gen2"] >= 3     # a real collection may add its own
    assert led.metrics["gc_pause_s_max"] >= 1.0
