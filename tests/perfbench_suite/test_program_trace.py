"""``perfbench/program_trace.py`` and the readers on top of it, over two small
synthetic traces kept in ``perfbench/testdata/`` (built by the functions below,
so every expected number can be worked out by hand from the durations written
here; plain ``.json``, because ``test_perfbench.py`` takes the newest
``*.trace.json.gz`` under that directory for the recorded one).  No chip number
is asserted: the traces are made up."""

import gzip
import importlib.util
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import program_trace as PT  # noqa: E402

DATA = REPO / "perfbench" / "testdata"
DEV, HOST, HOST_TID = 3, 701, 1


def _meta(devices=(DEV,)):
    rows = [{"ph": "M", "pid": HOST, "name": "process_name", "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": HOST, "tid": HOST_TID, "name": "thread_name", "args": {"name": "python"}}]
    for n, pid in enumerate(devices):
        rows += [{"ph": "M", "pid": pid, "name": "process_name", "args": {"name": f"/device:TPU:{n}"}},
                 {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
                 {"ph": "M", "pid": pid, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}}]
    return rows


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur, "args": args}


def _span(name, start, end, step):
    """An engine phase as the trace viewer writes a TraceAnnotation: the part
    after ``:`` is the name, the whole name is ``long_name``."""
    extra = {"long_name": name} if ":" in name else {}
    return _x(HOST, HOST_TID, name.split(":")[-1], start, end - start, step=str(step), **extra)


def _module(name, start, end, pid=DEV):
    return _x(pid, 2, f"jit_{name}(123)", start, end - start)


def _op(hlo, start, dur, tf_op=None, pid=DEV):
    name = hlo.split(" = ")[0].lstrip("%")
    return _x(pid, 3, name, start, dur, long_name=hlo, **({"tf_op": tf_op} if tf_op else {}))


def _write_kv(at, scatter_us, program):
    """The relayout-in copy (the parameter's name), the scoped scatter, the
    relayout-out copy (no name) and the decode kernel, as the chip's trace has them."""
    pool = "bf16[8,1024,64,128]"
    scope = f"jit({program})/LlamaForCausalLM/layers_0/self_attn"
    return [
        _op(f"%copy.254 = {pool}{{3,0,2,1}} copy({pool}{{3,2,1,0}} %cache.1)", at, 80,
            "cache['layers'][0]['k_pages']:"),
        _op(f"%fusion.6 = {pool}{{3,0,2,1}} fusion({pool}{{3,0,2,1}} %copy.254, s32[32]{{0}} %fusion.330)",
            at + 90, scatter_us, f"{scope}/paged_write_kv/scatter:"),
        _op(f"%copy.341 = {pool}{{3,2,1,0}} copy({pool}{{3,0,2,1}} %fusion.6)", at + 100, 90),
        _op(f"%paged_decode.8 = bf16[32,8,4,128]{{3,2,1,0}} custom-call({pool}{{3,2,1,0}} %copy.341)",
            at + 300, 2000, f"{scope}/paged_decode/pallas_call:"),
    ]


def serve_events():
    """Four ticks inside a 200 ms window (times in microseconds):

    A  step 7  decode         stage 2300   program 3100-33100    host_sync ends 34000
    B  step 8  prefill+sample stage 35000  program 35900-63900, sampler 63905-63907, sync ends 64000
    C  step 9  prefill chunk  stage 64500  program 64900-92900   (no token: no host_sync)
    D  step 10 decode         stage 65000  program 92910-123000  host_sync ends 124000

    launch  A 3100-2300 less the 1 us convert program = 799; B 900; C 400; D: of 65000-92910 the prefill
            program still covers 64900-92900, 10 are idle                          -> median 599.5
    sync    A 900; B 64000-63907 = 93; D 1000                                        -> median 900
    between B 35000-34000 less the 12 us release program = 988; C 500; D 500 less 100 of C's program = 400
    gap     B 35900-33100 less 12 = 2788 = 900 + 988 + 900;  C 64900-63907 = 993 = 93 + 500 + 400;  D 10
    margins: smallest program start - stage start 400 (C), - dispatch start 200 (C); smallest host_sync
            end - program end 93 (B)
    """
    ev = _meta() + [_x(HOST, HOST_TID, "perfbench_window", 1000, 200000)]
    ev += [_span("control", 2000, 2010, 7), _span("schedule", 2010, 2100, 7),
           _span("plan", 2100, 2300, 7), _span("stage:decode", 2300, 2700, 7),
           _span("dispatch:decode", 2700, 3000, 7), _span("host_sync", 3000, 34000, 7),
           _span("commit", 34000, 34400, 7), _span("trace", 34400, 34500, 7),
           _module("convert_element_type", 2500, 2501), _module("decode_legacy", 3100, 33100),
           _module("release_step", 34100, 34112)]
    ev += _write_kv(3110, 4, "decode_legacy")
    ev += [_span("stage:prefill", 35000, 35300, 8), _span("dispatch:prefill", 35300, 35600, 8),
           _span("stage:sample", 35600, 35700, 8), _span("dispatch:sample", 35700, 35800, 8),
           _span("host_sync", 35800, 64000, 8),
           _module("prefill_legacy", 35900, 63900), _module("sample_first", 63905, 63907)]
    ev += _write_kv(36000, 6, "prefill_legacy")
    ev += [_span("stage:prefill", 64500, 64700, 9), _span("dispatch:prefill", 64700, 64800, 9),
           _module("prefill_legacy", 64900, 92900)]
    ev += _write_kv(65000, 6, "prefill_legacy")
    ev += [_span("stage:decode", 65000, 65200, 10), _span("dispatch:decode", 65200, 65500, 10),
           _span("host_sync", 65500, 124000, 10), _module("decode_legacy", 92910, 123000)]
    ev += _write_kv(93000, 4, "decode_legacy")
    return ev


def train_events():
    """Two whole runs of the step program on chip 0 inside a 3 s window, a third
    cut by the window's end, and a second chip with other numbers (not read).
    Per run, in microseconds: flash_fwd 40000 + flash_bwd_dq 50000 + flash_bwd_dkv
    60000 = 150000; under fused_xent a ``while`` of 100000 that encloses two
    matmuls of 30000 and an all-reduce of 20000 (self time 20000, so 100000 in
    all) and the backward matmul 70000 = 170000; optimizer_update 25000 + 5000 =
    30000; an MLP matmul 400000 that only its flax module names; a 9000 copy no
    name claims."""
    step, bwd = "jit(pinned_step_fn)", "jit(pinned_step_fn)/transpose(jvp(LlamaForCausalLM))"
    ev = _meta((DEV, 4)) + [_x(HOST, HOST_TID, "perfbench_window", 0, 3_000_000)]
    for k, start in enumerate((1000, 1_002_000, 2_500_000)):
        for pid, scale in ((DEV, 1), (4, 2)):
            ev.append(_module("pinned_step_fn", start, start + 1_000_000, pid))
            at = lambda off: start + off
            attn = "layers_0/self_attn/shard_map"
            ev += [
                _op("%flash_fwd.3 = bf16[2,32,4096,128]{3,2,1,0} custom-call(%q)", at(1000), 40000 * scale,
                    f"{step}/jvp(LlamaForCausalLM)/{attn}/flash_fwd/pallas_call:", pid),
                _op("%fusion.9 = bf16[2,4096,14336]{2,1,0} fusion(%x)", at(50000), 400000,
                    f"{step}/jvp(LlamaForCausalLM)/layers_0/mlp/up_proj/dot_general:", pid),
                _op("%while.1 = (f32[8190]{0}) while(%t)", at(500000), 100000,
                    f"{step}/jvp(fused_xent)/while:", pid),
                _op("%fusion.20 = f32[8190,16000]{1,0} fusion(%h)", at(500100), 30000,
                    f"{step}/jvp(fused_xent)/while/body/dot_general:", pid),
                _op("%fusion.21 = f32[8190,16000]{1,0} fusion(%h)", at(531000), 30000,
                    f"{step}/jvp(fused_xent)/while/body/dot_general:", pid),
                _op("%all-reduce.4 = f32[8190,16000]{1,0} all-reduce(%fusion.21)", at(562000), 20000,
                    f"{step}/jvp(fused_xent)/while/body/reduce_max:", pid),
                _op("%fusion.30 = f32[8190,3584]{1,0} fusion(%d)", at(610000), 70000,
                    f"{step}/transpose(jvp(fused_xent))/while/body/dot_general:", pid),
                _op("%flash_bwd_dq.5 = bf16[2,32,4096,128]{3,2,1,0} custom-call(%g)", at(700000), 50000,
                    f"{bwd}/{attn}/flash_bwd_dq/pallas_call:", pid),
                _op("%flash_bwd_dkv.6 = bf16[2,8,4096,128]{3,2,1,0} custom-call(%g)", at(760000), 60000,
                    f"{bwd}/{attn}/flash_bwd_dkv/pallas_call:", pid),
                _op("%copy.7 = bf16[4096,4096]{1,0} copy(%w)", at(830000), 9000, None, pid),
                _op("%fusion.40 = bf16[4096,14336]{1,0} fusion(%m)", at(900000), 25000,
                    f"{step}/optimizer_update/mul:", pid),
                _op("%fusion.41 = bf16[4096,14336]{1,0} fusion(%m)", at(930000), 5000,
                    f"{step}/optimizer_update/stochastic_round/convert_element_type:", pid),
            ]
    return ev


BUILDERS = {"program_trace_serve": serve_events, "program_trace_train": train_events}


def _file(name):
    return DATA / f"{name}.json"


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / "perfbench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _run(name, **extra):
    """A traced run's record, as far as the new readers look at it."""
    events = json.loads(_file(name).read_text())["traceEvents"]
    return {"trace": {"programs": {}}, "program_trace": PT.parse(events), **extra}


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_the_committed_synthetic_trace_is_what_its_builder_writes(name):
    assert json.loads(_file(name).read_text()) == {"traceEvents": BUILDERS[name]()}


def test_a_traced_run_reads_the_newest_trace_file_of_its_directory(tmp_path):
    for k, build in enumerate((train_events, serve_events)):
        with gzip.open(tmp_path / f"run{k}.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": build()}, f)
    run = {"trace": {"programs": {}}, "trace_dir": tmp_path}
    assert _reader("tick_sync_exposed_ms.chat")(run) == pytest.approx(0.9)
    assert "stage:decode" in run["program_trace"]["host"]       # parsed once, kept on the record


def test_a_tick_is_partitioned_and_the_parts_add_up_to_the_devices_gap():
    ticks = PT.ticks(PT.parse(serve_events()))
    assert [(t["step"], t["kind"]) for t in ticks] == [(7, "decode"), (8, "prefill"),
                                                       (9, "prefill"), (10, "decode")]
    assert [t["launch"] for t in ticks] == [799, 900, 400, 10]
    assert [t["sync"] for t in ticks] == [900, 93, None, 1000]
    assert [t["between"] for t in ticks] == [None, 988, 500, 400]
    assert [t["gap"] for t in ticks] == [None, 2788, 993, 10]
    for before, tick in zip(ticks, ticks[1:]):
        if before["sync"] is not None:
            assert before["sync"] + tick["between"] + tick["launch"] == tick["gap"]
    assert min(t["start_margin"] for t in ticks) == 400
    assert [t["dispatch_margin"] for t in ticks] == [400, 600, 200, 92910 - 65200]
    assert min(t["end_margin"] for t in ticks if t["end_margin"] is not None) == 93


def test_a_program_that_starts_before_its_stage_span_shows_as_a_negative_margin():
    """The causality check must be able to fail: shift the host's spans 2 ms late."""
    late = [dict(e, ts=e["ts"] + 2000) if e["ph"] == "X" and e["pid"] == HOST
            and e["name"] != "perfbench_window" else e for e in serve_events()]
    ticks = PT.ticks(PT.parse(late))
    assert min(t["dispatch_margin"] for t in ticks[1:]) < 0     # the first tick anchors the pairing
    assert PT.report(PT.parse(late))["ticks"]["min_dispatch_margin_us"] < 0


SERVE = {"tick_launch_exposed_ms": 0.5995, "tick_sync_exposed_ms": 0.9,
         "paged_write_kv_device_ms": (4 + 6 + 6 + 4) / 4 * 1e-3}
TRAIN = {"flash_kernels_device_ms": 150.0, "fused_xent_device_ms": 170.0, "optimizer_device_ms": 30.0}
COUNTERS = {"queue_wait_mean_ms.chat": 30.0, "engine_ttft_mean_ms.chat": 80.0}
ENGINE = {"queue_wait_s_sum": 0.9, "queue_wait_n": 30, "ttft_s_sum": 2.4, "ttft_n": 30}


@pytest.mark.parametrize("metric,expected",
                         [(f"{m}.{cell}", v) for m, v in SERVE.items() for cell in ("chat", "batch")]
                         + list(TRAIN.items()) + list(COUNTERS.items()))
def test_each_new_reader_returns_the_hand_computed_number(metric, expected):
    name = "program_trace_train" if metric in TRAIN else "program_trace_serve"
    assert _reader(metric)(_run(name, engine_metrics=ENGINE)) == pytest.approx(expected, rel=1e-12)


@pytest.mark.parametrize("metric", [f"{m}.{cell}" for m in SERVE for cell in ("chat", "batch")]
                         + sorted(TRAIN) + sorted(COUNTERS))
def test_each_new_reader_finds_nothing_in_a_run_that_holds_nothing(metric):
    """An untraced record, and the trace and record of a program from before the
    spans, scopes and counters (the recorded chip trace of PR 24's program)."""
    read = _reader(metric)
    assert read({"engine_metrics": {"decode_steps": 5}}) is None
    with gzip.open(DATA / "serve_chat_260ms.trace.json.gz") as f:
        old = PT.parse(json.load(f)["traceEvents"])
    assert read({"trace": {"programs": {}}, "program_trace": old,
                 "engine_metrics": {"decode_steps": 5}}) is None


def test_names_put_ops_to_layers_and_neighbours_claim_the_unnamed_copies():
    rep = PT.report(PT.parse(serve_events()))
    top = {k: names for k, _, names in rep["top_ops"]}
    assert top["attention_kernel:decode_legacy:bf16_32_8_4_128_"] == ["paged_decode"]
    # the copy into the scatter's layout, and the copy back into the kernel's
    assert top["copy:decode_legacy:bf16_8_1024_64_128_"] == [
        "beside paged_decode+paged_write_kv", "beside paged_write_kv"]
    assert rep["ticks"]["min_start_margin_us"] == 400 and rep["ticks"]["worst_identity_error_us"] == 0
    assert rep["ticks"]["annotations_per_tick"] == 18 / 4
    train = PT.report(PT.parse(train_events()))
    assert train["scope_us"] == {"fused_xent": 340000, "optimizer_update": 60000, "flash_fwd": 80000,
                                 "flash_bwd_dq": 100000, "flash_bwd_dkv": 120000}
    assert train["share_by_layer"]["none"] == pytest.approx(18000 / 1518000, abs=1e-4)


def test_every_new_metric_of_the_benchmark_lists_its_workloads_and_has_a_reader():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].rsplit(".", 1)[0] in {**SERVE, **TRAIN} or m["name"] in COUNTERS}
    assert len(new) == 11 and all(m["workloads"] for m in new.values())
    for name in new:
        assert callable(_reader(name))


if __name__ == "__main__":          # python tests/perfbench_suite/test_program_trace.py: rewrite the files
    for name, build in BUILDERS.items():
        _file(name).write_text(json.dumps({"traceEvents": build()}, indent=0) + "\n")
