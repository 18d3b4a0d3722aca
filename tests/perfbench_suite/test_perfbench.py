"""The benchmark's own tests (``perfbench/``): the contract of BENCHMARK.json,
the data-driven lookups, the replayed trace, the window arithmetic, the trace
reduction on a recorded TPU trace, the CPU rehearsal of every cell, and the
two proofs ``correct`` rests on — the lower-precision control comes out as not
correct, and so does a timed path broken underneath.  Chip numbers are never
asserted here: a rehearsal's numbers are CPU numbers."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import harness, trace_reduce  # noqa: E402
from perfbench import window as W  # noqa: E402
from perfbench.traffic import build_trace, prompt_tokens, train_batches  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SERVE_TRAFFIC = sorted(p.stem for p in (REPO / "perfbench" / "traffic").glob("serve_*.json"))


def _run(*args, code=None, timeout=600):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-c", code, *args] if code else \
        [sys.executable, "perfbench/run.py", *args]
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


# -- BENCHMARK.json ----------------------------------------------------------

def _named():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_named()),
                         ids=[f"{g}:{e['name']}" for g, e in _named()])
def test_every_name_and_unit_is_made_of_the_allowed_characters(group, entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    for text in (entry.get("why"), entry.get("layer"), entry.get("source")):
        assert text is None or (1 <= len(text) <= 200 and "\n" not in text and "\t" not in text)
    if group == "configs":
        assert entry["file"].startswith("perfbench/") and (REPO / entry["file"]).is_file()
        assert set(entry["reduced"]) == set(json.loads((REPO / entry["file"]).read_text())["reduced"])
    if group == "end_to_end":
        assert 0 < entry["bound"] <= 0.1


def test_the_file_keeps_to_the_contracts_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len({w["name"] for w in BENCH["workloads"]}) == len(BENCH["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in BENCH["end_to_end"])
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_finds_its_files_by_name_and_reports_what_its_metrics_move(cell):
    loaded = harness.load_cell(cell)
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell does not report")
    family = loaded["config"]["model_family"]
    for part in ("families", "reference"):
        assert (REPO / "perfbench" / part / f"{family}.py").is_file()
    kind = loaded["traffic"]["kind"].split("_")[0]
    assert (REPO / "perfbench" / "kinds" / f"{kind}.py").is_file()
    assert set(loaded["limits"]) >= {"readings"}


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_agrees_with_its_entry(entry):
    reader = harness.load_module("metrics", entry["name"])
    for key in ("layer", "unit", "moves", "source"):
        assert getattr(reader, key) == entry[key]
    assert reader.read({}) is None            # nothing to read: nothing returned, never a zero
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_a_metric_with_no_file_is_an_error_not_a_zero():
    with pytest.raises(SystemExit):
        harness.load_module("metrics", "no_such_metric")


def test_an_unknown_device_kind_has_no_peaks():
    assert harness.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.device_peaks("TPU v9 imaginary")


# -- the replayed trace --------------------------------------------------------

@pytest.mark.parametrize("traffic", SERVE_TRAFFIC)
def test_the_trace_is_the_same_for_every_seed_and_the_token_ids_are_not(traffic):
    spec = json.loads((REPO / "perfbench" / "traffic" / f"{traffic}.json").read_text())
    a, b = build_trace(spec), build_trace(spec)
    assert a == b and len(a) == spec["num_requests"]
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    assert all(lo <= r.prompt_len <= hi for r in a)
    cap = spec["engine"]["pages_per_slot"] * spec["engine"]["page_size"]
    assert max(r.prompt_len + r.output_len for r in a) <= cap
    assert max(r.output_len for r in a) <= spec["engine"]["max_new_tokens"]
    r = a[3]
    assert prompt_tokens(1, r.uid, r.prompt_len, 1000) == prompt_tokens(1, r.uid, r.prompt_len, 1000)
    assert prompt_tokens(1, r.uid, r.prompt_len, 1000) != prompt_tokens(2, r.uid, r.prompt_len, 1000)
    assert prompt_tokens(2**31 + 5, r.uid, 8, 1000) != prompt_tokens(5, r.uid, 8, 1000)
    if spec["kind"] == "serve_open":
        fast = build_trace(spec, rate_rps=2 * spec["rate_rps"])
        assert [(r.prompt_len, r.output_len) for r in fast] == [(r.prompt_len, r.output_len) for r in a]
        ramp = spec["ramp_s"]
        assert all(abs((f.due_s + ramp) * 2 - (s.due_s + ramp)) < 1e-9 for f, s in zip(fast, a))


def test_training_batches_come_from_the_seed_and_every_row_differs():
    a, b = train_batches(7, 4, 2, 16, 100), train_batches(7, 4, 2, 16, 100)
    assert (a == b).all() and (a != train_batches(8, 4, 2, 16, 100)).any()
    rows = {tuple(r) for batch in a for r in batch}
    assert len(rows) == 8


# -- window arithmetic on a synthetic tick log ----------------------------------

def _tick(end, kind, emitted=(), prompt=0, active=0):
    return {"start": end - 0.03, "end": end, "kind": kind, "prompt_tokens": prompt,
            "emitted": tuple(emitted), "active": active, "bucket": 0, "traced": False, "waiting": 0}


TICKS = [
    _tick(-0.5, "prefill", ["r0"], prompt=100),            # ramp: outside
    _tick(0.1, "decode", ["r0"], active=1),
    _tick(0.2, "prefill", prompt=512),                     # a chunk, no first token yet
    _tick(0.3, "prefill", ["a"], prompt=88),
    *[_tick(0.4 + 0.1 * i, "decode", ["r0", "a"], active=2) for i in range(8)],   # 0.4 .. 1.1
    _tick(1.2, "prefill", ["b"], prompt=40),
    _tick(2.05, "decode", ["a", "b"], active=2),           # ends after a 2 s window
]
DUE = {"r0": -0.6, "a": 0.05, "b": 1.0, "c": 1.9}


@pytest.mark.parametrize("what,expected", [
    ("tokens_per_s", (1 + 512 + 88 + 1 + 16 + 40 + 1) / 2.0),
    ("ttft", {"a": 250.0, "b": 200.0, "c": 600.0}),
    ("failed", ["c"]),
    ("tpot_a", (1.1 - 0.3) / 8 * 1e3),
    ("tpot_keys", {"r0", "a"}),
    ("backlog_half", 0),
    ("backlog_end", 1),
    ("train_rate", 4 * 8192 / 2.1),
    ("p90", 9.1),
])
def test_window_arithmetic(what, expected):
    ttft, failed = W.ttft_ms(TICKS, DUE, 2.0, 2.5)
    got = {
        "tokens_per_s": lambda: W.tokens_per_s(TICKS, 2.0),
        "ttft": lambda: {k: round(v, 6) for k, v in ttft.items()},
        "failed": lambda: failed,
        "tpot_a": lambda: W.tpot_ms(TICKS, 2.0)["a"],
        "tpot_keys": lambda: set(W.tpot_ms(TICKS, 2.0)),
        "backlog_half": lambda: W.backlog(TICKS, DUE, 0.5),
        "backlog_end": lambda: W.backlog(TICKS, DUE, 2.0),
        "train_rate": lambda: W.steps_tokens_per_s([0.5, 1.0, 1.5, 2.1], 8192),
        "p90": lambda: W.percentile(list(range(1, 11)), 90),
    }[what]()
    assert got == pytest.approx(expected) if not isinstance(expected, (set, list, dict)) else got == expected


# -- the trace reduction, on a recorded TPU trace -------------------------------

def test_trace_reduce_on_the_recorded_trace():
    spans = [("step", 0.0, 0.26), ("host_sync", 0.02, 0.26)]
    r = trace_reduce.reduce_dir(REPO / "perfbench" / "testdata", spans=spans, anchor=0.0)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.26)
    assert 0.0 < r["busy_s"] < r["window_s"]
    progs = r["programs"]
    assert progs["decode_legacy"]["runs"] == 6 and progs["prefill_legacy"]["runs"] == 1
    assert 0.025 < sorted(progs["decode_legacy"]["durations_s"])[3] < 0.035
    assert r["top_ops"][0][0].startswith("copy:decode_legacy:bf16_8_1024_64_128_")
    assert len(r["top_ops"]) <= 10
    calls = r["kernel_calls_s"]["decode_legacy:bf16_32_8_4_128_:fwd"]
    assert len(calls) == 6 * 8                                  # 8 layers a decode tick
    classes = r["class_s"]
    assert classes["copy"] > classes["attention_kernel"] > classes["matmul"]
    assert sum(classes.values()) <= r["busy_s"] * 1.001         # nothing booked twice
    idle = r["window_s"] - r["busy_s"]
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert r["idle_gaps"][0][0] == "host_sync"


@pytest.mark.parametrize("name,cls", [
    ("%copy.341 = bf16[8,1024,64,128]{3,2,1,0} copy(...)", "copy"),
    ("%self_attn.8 = bf16[32,8,4,128]{3,2,1,0} custom-call(s32[1280]{0} %r), custom_call_target=\"tpu_custom_call\"", "attention_kernel"),
    ("%fusion.12 = bf16[32,14336]{1,0} fusion(...)", "matmul"),
    ("%all-reduce.3 = bf16[4096]{0} all-reduce(...)", "collective"),
    ("%while.2 = (s32[]) while(...)", "while_loops"),
    ("%multiply_reduce_fusion.7 = f32[32]{0} fusion(...)", "elementwise_fusion"),
])
def test_classify_op(name, cls):
    assert trace_reduce.classify_op(name) == cls


def test_nested_ops_keep_only_their_own_time():
    ops = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (5.0, 4.0, "b"), (12.0, 1.0, "c")]
    assert [(p, d) for _, d, p in trace_reduce.self_times(ops)] == \
        [("while", 3.0), ("a", 3.0), ("b", 4.0), ("c", 1.0)]


# -- the rehearsal of every cell, end to end -------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_a_contract_shaped_line(cell, trace):
    loaded = harness.load_cell(cell)
    out = _run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "4",
               "--trace", str(trace), "--rehearse")
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    allowed = {"correct", "attempted", "failed", "metrics", "device"} | ({"breakdown"} if trace else set())
    assert set(line) == allowed
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == loaded["cell"]["chips"]
    names = {m["name"] for m in (loaded["per_layer"] if trace else loaded["end_to_end"])}
    assert set(line["metrics"]) <= names and line["metrics"]
    if not trace:
        assert set(line["metrics"]) == names
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert len(line["breakdown"]["device_ops"]) <= 10
    for name, m in line["metrics"].items():
        assert m["value"] == m["value"] and set(m) == {"value", "unit"}


def test_without_a_chip_and_without_the_option_it_refuses():
    out = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout == "" and "no accelerator" in out.stderr


# -- correct: the control fails, a broken timed path fails ------------------------

BROKEN = {
    "mistral7b.train_4k": (
        "from perfbench.families import llama as fam\n"
        "real = fam.build_trainer\n"
        "def build(*a, **k):\n"
        "    acc, step, new_state = real(*a, **k)\n"
        "    def frozen(state, batch):\n"
        "        import jax\n"
        "        _, metrics = step(jax.tree_util.tree_map(lambda x: x.copy(), state), batch)\n"
        "        return state, metrics          # the step returns its state unchanged\n"
        "    frozen._jitted = step._jitted\n"
        "    return acc, frozen, new_state\n"
        "fam.build_trainer = build\n"),
    "mistral7b.serve_chat": (
        "from perfbench.families import llama as fam\n"
        "real = fam.build_engine\n"
        "def build(*a, **k):\n"
        "    eng = real(*a, **k)\n"
        "    record = eng._record_token\n"
        "    def altered(slot, tok, release=True):   # a token altered where it is produced\n"
        "        return record(slot, (tok + 1) % 500 if eng.steps % 7 == 0 else tok, release)\n"
        "    eng._record_token = altered\n"
        "    return eng\n"
        "fam.build_engine = build\n"),
}


@pytest.mark.parametrize("cell", sorted(BROKEN))
def test_a_timed_path_broken_underneath_is_not_correct(cell):
    code = ("import sys; sys.path.insert(0, '.')\n" + BROKEN[cell] +
            "sys.path.insert(0, 'perfbench'); import run\n"
            f"run.main(['--workload', '{cell}', '--seed', '5', '--seconds', '3', '--trace', '0', '--rehearse'])\n")
    out = _run(code=code)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["mistral7b.train_4k", "mistral7b.serve_chat"])
def test_the_lower_precision_control_is_not_correct_and_the_program_is(cell):
    out = _run(code=("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import prove\n"
                     f"prove.main(['--workload', '{cell}', '--seeds', '1,2,3', '--control-seeds', '1,2,3',"
                     "  '--control', 'fp8', '--seconds', '5', '--rehearse'])\n"))
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith('{"seed"')]
    limits = harness.load_cell(cell)["limits"]["rehearse"]
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[harness.limit_key(n)] for n, v in row["program"].items()), row
        assert any(v > limits[harness.limit_key(n)] for n, v in row["control_fp8"].items()), row


# -- the bounds, against every reading on record (perfbench/bounds.py) -----------

def _bounds(*args):
    return subprocess.run([sys.executable, "perfbench/bounds.py", *args], cwd=REPO,
                          capture_output=True, text=True, timeout=60)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]])
def test_every_bound_lies_inside_its_window_on_the_recorded_spreads(metric):
    from perfbench import bounds

    checks = json.loads((REPO / "perfbench" / "spreads.json").read_text())["checks"]
    bound = next(m["bound"] for m in BENCH["end_to_end"] if m["name"] == metric)
    w = bounds.window(metric, bound, checks)
    assert w["low"] <= bound <= w["ceiling"], w
    if metric == "tpot_p90_ms":                # the driver's own ceiling (ledger, PR 23) and floor A
        assert bound <= 0.061 and bound >= w["floor_a"][0]
    origins = {c["origin"] for c in checks}
    assert {"builder, PR 23", "driver, ledger PR 23", "my chip runs, PR 24"} <= origins


@pytest.mark.parametrize("override,named", [
    (["--bound", "tpot_p90_ms=0.07"], "tpot_p90_ms"),              # PR 23: refused as too loose
    (["--bound", "serve_tokens_per_s=0.01"], "serve_tokens_per_s"),  # PR 23's first check: two sets 1.28% apart
    (["--bound", "ttft_mean_ms=0.07"], "ttft_mean_ms"),            # no longer judged: PR 24's sets spread 8% of the mean
    ([], None),
])
def test_bounds_tool_names_what_lies_outside_and_exits_non_zero(override, named):
    out = _bounds(*override)
    assert out.returncode == (1 if named else 0), out.stdout + out.stderr
    rows = {l.split("|")[1].strip(" `"): l for l in out.stdout.splitlines() if l.startswith("| `")}
    assert set(rows) == {m["name"] for m in BENCH["end_to_end"]} | ({named} if named else set())
    for name, row in rows.items():
        assert ("OUTSIDE" in row) == (name == named), row
    if named:
        assert named in out.stderr


def test_set_spread_is_the_contracts_and_sets_reduce_to_readings(tmp_path):
    from perfbench import bounds

    assert bounds.set_spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)
    assert bounds.trimmed([10.0, 10.1, 9.9, 14.0]) == [10.0, 10.1, 9.9]
    for s, values in (("A", [90.0, 30.0, 31.0, 32.0]), ("B", [30.5, 31.5, 33.0, 34.0])):
        for i, v in enumerate(values):
            path = tmp_path / f"{s}.{i}.out"
            path.write_text(json.dumps({"phase": "start", "workload": "x.cell", "trace": 0}) + "\n" +
                            json.dumps({"correct": True, "metrics": {
                                "setup_s": {"value": v, "unit": "s"},
                                "rate": {"value": 100.0 + i, "unit": "1/s"}}}) + "\n")
            os.utime(path, (1000 + i + 10 * (s == "B"),) * 2)
    by_metric = {r["metric"]: r for r in bounds.readings_of(bounds.read_sets(tmp_path))}
    assert [s["runs"] for s in by_metric["setup_s"]["sets"]] == [3, 3]      # each set's first run left out
    assert by_metric["setup_s"]["sets"][0]["median"] == 31.0
    assert by_metric["rate"]["sets"][1]["median"] == 101.5
    w = bounds.window("rate", 0.03, [{"origin": "t", "readings": list(by_metric.values())}])
    rate = [100.0, 101.0, 102.0, 103.0]                 # both sets; the mean of two equal spreads
    assert w["floor_a"][0] == pytest.approx(2 * bounds.set_spread(bounds.trimmed(rate)))
    assert w["ceiling"] == pytest.approx(0.1) and not w["inside"]     # 8 x 2.5% is over the cap; floor A is 3.9%
