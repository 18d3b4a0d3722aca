"""The eight readers of the engine's host ledger (``perfbench/host_ledger.py`` and
``perfbench/metrics/{tick_host_ms,tick_stall_ms_per_s,gc_pause_ms_per_s}[.chat].py``,
``warmup_trace_lower_s.py``, ``warmup_cache_load_s.py``): each on a hand-made
``engine_metrics`` against the value worked out by hand, on a run that keeps no
ledger, and in the traced rehearsal of the chat cell and of an expert cell."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import harness, host_ledger  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
# (the issue lists `k-exaone.serve_reason` and `joyai-flash.serve_docs` too; their accepted tests pin the
# set of names a traced line of theirs may carry, and this PR may not edit them: a `benchmark` PR appends the two)
TOKENS_CELLS = ["mistral7b.serve_batch", "keye-vl2.serve_long"]
CHAT_CELLS = ["mistral7b.serve_chat"]
NOT_YET = ["k-exaone.serve_reason", "joyai-flash.serve_docs"]
NEW = {"tick_host_ms": 2.5, "tick_host_ms.chat": 2.5,
       "tick_stall_ms_per_s": 4.0, "tick_stall_ms_per_s.chat": 4.0,
       "gc_pause_ms_per_s": 1.5, "gc_pause_ms_per_s.chat": 1.5,
       "warmup_trace_lower_s": 3.25, "warmup_cache_load_s": 4.5}

# 100 decode ticks of 15 ms (12.5 ms of them the wait for the device), 20 prefill
# ticks of 30 ms, 0.9 s of the caller's time: 3.0 busy seconds
METRICS = {
    "ticks.decode": 100, "ticks.prefill": 20, "ticks.verify": 0, "ticks.idle": 0,
    "tick_wall_s.decode": 1.5, "tick_wall_s.prefill": 0.6, "tick_wall_s.verify": 0.0,
    "tick_wall_s.idle": 0.0, "outside_s_sum": 0.9,
    "host_s.decode.control": 0.002, "host_s.decode.schedule": 0.003, "host_s.decode.plan": 0.005,
    "host_s.decode.stage": 0.180, "host_s.decode.dispatch": 0.050, "host_s.decode.commit": 0.010,
    "host_s.decode.host_sync": 1.25, "host_n.decode.host_sync": 100, "host_max_s.decode.host_sync": 0.11,
    "host_s.prefill.stage": 0.04, "host_s.prefill.host_sync": 0.5,
    "stall_excess_s_sum": 0.012, "stall_n": 1, "outside_stall_excess_s_sum": 2.0,
    "gc_pause_s_sum": 0.0045, "gc_pause_n": 700,
    "warmup_trace_s": 2.75, "warmup_lower_s": 0.5, "warmup_backend_s": 0.25, "warmup_cache_load_s": 4.25,
    "warmup_cache_hits": 6, "warmup_cache_misses": 0, "warmup_wall_s": 8.5,
    "decode_steps": 100, "queue_wait_s_sum": 1.0,
}


def _entry(name):
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_gives_the_value_worked_out_by_hand(name):
    reader = harness.load_module("metrics", name)
    assert reader.read({"engine_metrics": dict(METRICS)}) == pytest.approx(NEW[name], rel=1e-12)
    assert reader.source == "program_counter"


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_run_that_keeps_no_ledger_reads_none_and_does_not_raise(name):
    """The parent's engine under these files: counters, but none of the ledger's."""
    reader = harness.load_module("metrics", name)
    parents = {"decode_steps": 100, "queue_wait_s_sum": 1.0, "queue_wait_n": 3, "evictions": 0}
    for run in ({}, {"engine_metrics": {}}, {"engine_metrics": None}, {"engine_metrics": parents},
                {"trace": {"x": 1}, "ticks": []}):
        assert reader.read(run) is None


def test_a_clean_run_reads_zero_not_nothing():
    clean = dict(METRICS, stall_excess_s_sum=0.0, gc_pause_s_sum=0.0)
    assert harness.load_module("metrics", "tick_stall_ms_per_s").read({"engine_metrics": clean}) == 0.0
    assert harness.load_module("metrics", "gc_pause_ms_per_s.chat").read({"engine_metrics": clean}) == 0.0


def test_the_helpers_arithmetic():
    run = {"engine_metrics": dict(METRICS)}
    assert host_ledger.busy_s(run) == pytest.approx(3.0)
    assert host_ledger.tick_host_ms(run, "prefill") == pytest.approx(2.0)      # 0.04 s over 20 ticks
    assert host_ledger.tick_host_ms(run, "verify") is None                     # no such tick
    assert host_ledger.seconds(run, "warmup_trace_s") == 2.75
    assert host_ledger.seconds(run, "warmup_trace_s", "no_such_key") is None
    assert host_ledger.ms_per_busy_s(run, "no_such_key") is None
    assert host_ledger.ms_per_busy_s({"engine_metrics": {"outside_s_sum": 0.0}}, "gc_pause_s_sum") is None
    # the caller's stalls (a profiler's start between two ticks) are not the ticks'
    assert host_ledger.ms_per_busy_s(run, "outside_stall_excess_s_sum") == pytest.approx(2000 / 3)


def test_the_benchmark_lists_the_eight_as_the_issue_gives_them():
    assert [m["name"] for m in BENCH["per_layer"][-8:]] == [
        "tick_host_ms", "tick_host_ms.chat", "tick_stall_ms_per_s", "tick_stall_ms_per_s.chat",
        "gc_pause_ms_per_s", "gc_pause_ms_per_s.chat", "warmup_trace_lower_s", "warmup_cache_load_s"]
    for name in NEW:
        entry = _entry(name)
        assert entry["source"] == "program_counter" and entry["better"] == "lower"
        assert not name.endswith((".docs", ".reason", ".long", ".batch"))
        if name.startswith("warmup_"):
            assert (entry["layer"], entry["moves"], entry["unit"]) == ("compile cache", "setup_s", "s")
            assert entry["workloads"] == CHAT_CELLS + TOKENS_CELLS
        else:
            chat = name.endswith(".chat")
            assert entry["layer"] == "serving engine"
            assert entry["moves"] == ("tpot_p90_ms" if chat else "serve_tokens_per_s")
            assert entry["workloads"] == (CHAT_CELLS if chat else TOKENS_CELLS)
            assert entry["unit"] == ("ms" if name.startswith("tick_host_ms") else "ms/s")
    for cell in CHAT_CELLS + TOKENS_CELLS:
        listed = {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(NEW)
        assert len(listed) == 5 and all(n.endswith(".chat") == (cell in CHAT_CELLS)
                                        for n in listed if not n.startswith("warmup_"))
    for cell in ["mistral7b.train_4k", "yi34b.train_fsdp2_tp2"] + NOT_YET:
        assert not {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(NEW)


def _rehearse(cell):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", str(2**31 + 38),
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


# (`trace` and `cell` are what conftest.py's lock reads: one traced rehearsal of a cell at a time)
@pytest.mark.parametrize("trace", [1])
@pytest.mark.parametrize("cell", ["mistral7b.serve_chat", "keye-vl2.serve_long"])
def test_the_traced_line_of_a_chat_and_of_an_expert_cell_carries_the_new_names(cell, trace):
    out = _rehearse(cell)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    chat = cell in CHAT_CELLS
    expected = {n for n in NEW if n.startswith("warmup_") or n.endswith(".chat") == chat}
    assert expected <= set(line["metrics"]) and line["correct"] is True
    values = {n: line["metrics"][n]["value"] for n in expected}
    host = values["tick_host_ms.chat" if chat else "tick_host_ms"]
    assert 0.0 < host < 1000.0                       # a count of the CPU's milliseconds: never a device number
    assert all(v >= 0.0 for v in values.values())
    assert values["warmup_trace_lower_s"] > 0.0 and values["warmup_cache_load_s"] > 0.0
    for n in expected:
        assert line["metrics"][n]["unit"] == _entry(n)["unit"]
