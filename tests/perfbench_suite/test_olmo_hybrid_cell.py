"""The cell ``olmo-hybrid.train_8k`` and what PR 42 added beside it: the
benchmark lists the configuration, the traffic and the metrics as the issue
gives them (by membership, never by position); ``train_mfu`` is no longer
selected for the new cell and still is for the two accepted train cells; the
metrics before this cell's keep their entries; the rehearsal is ``correct``
and every new reader runs; the lower-precision control and each planted fault
that the limits file says is caught come out NOT correct through the
harness's own comparison; a checkout whose program lacks the family fails at
once; the two new rooflines by hand at the published sizes.  The rehearsal of
the cell itself, traced and untraced, is also ``test_perfbench.py``'s."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import harness  # noqa: E402
from perfbench.rooflines import linear_chunk_train, train_step_hybrid  # noqa: E402

CELL = "olmo-hybrid.train_8k"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = ["train_mfu.hybrid", "linear_chunk_device_ms.train", "linear_chunk_roofline.train",
       "linear_project_device_ms.train", "flash_fwd_roofline.hybrid", "flash_bwd_roofline.hybrid"]
SHARES = ("train_mfu.hybrid", "linear_chunk_roofline.train", "flash_fwd_roofline.hybrid", "flash_bwd_roofline.hybrid")
SHARED = ("flash_kernels_device_ms", "fused_xent_device_ms", "optimizer_device_ms")
UNLISTED = ("train_step_device_ms", "train_device_idle_share", "warmup_compile_s")
TRAIN_BEFORE = ["mistral7b.train_4k", "yi34b.train_fsdp2_tp2"]
FAULTS = ("beta_unit", "no_decay", "carry", "conv_off", "qk_norm_off", "rotary")


def _run(code, timeout=900):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


MAIN = ("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import run\n"
        f"run.main(['--workload', '{CELL}', '--seed', '2147483659', '--seconds', '3', '--trace', '1', "
        "'--rehearse'])\n")


def test_the_benchmark_lists_the_configuration_the_traffic_and_the_metrics_as_the_issue_gives_them():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert (m["unit"] == "%") == (name in SHARES)
    for name in SHARED:
        assert by_name[name]["workloads"] == TRAIN_BEFORE + [CELL]
    for name in ("flash_fwd_roofline", "flash_bwd_roofline"):      # their readers multiply by run["layers"]:
        assert by_name[name]["workloads"] == ["mistral7b.train_4k"]    # this cell reads the ``.hybrid`` pair
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert tokens["workloads"] == TRAIN_BEFORE + [CELL] and tokens["bound"] == 0.01
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("olmo-hybrid-7b", "train_8k", 1)
    listed = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    assert listed["reduced"] == ["num_hidden_layers"]
    assert listed["file"] == "perfbench/configs/olmo-hybrid-7b.json"
    assert listed["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    loaded = harness.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    published = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
                 "intermediate_size": 11008, "num_attention_heads": 30, "num_key_value_heads": 30,
                 "hidden_act": "silu", "max_position_embeddings": 65536, "attention_bias": False,
                 "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "linear_num_key_heads": 30,
                 "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
                 "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
                 "rope_parameters": {"rope_theta": None}}
    assert {k: cfg[k] for k in published} == published              # no width, head count or vocabulary row is cut
    assert cfg["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert set(cfg["reduced"]) == {"num_hidden_layers"} and cfg["model_family"] == "olmo_hybrid"
    assert cfg["num_hidden_layers"] == 4 and cfg["depth_by_kind"] == {"train": 4}
    assert {"block_order", "qk_norm", "no_rotary", "head_dim", "linear_attention_layer", "tensor_names",
            "weight_scales", "weights"} <= set(cfg["assumed"]) and len(cfg["departures"]) >= 4
    assert traffic["kind"] == "train" and (traffic["batch"], traffic["seq"]) == (1, 8192)
    assert traffic["ce_chunks"] == 8 and traffic["parallelism"] == {} and "model_dtype" not in traffic
    assert traffic["optimizer"] == "lion-sr" and traffic["optimizer_hyper"] == harness.load_json(
        REPO / "perfbench" / "traffic" / "train_4k.json")["optimizer_hyper"]       # Mistral's cell's recipe
    assert (traffic["distinct_batches"], traffic["reference_steps"], traffic["trace_seconds"]) == (8, 3, 5)
    small = traffic["rehearse"]
    assert small["layers"] == 4 and small["seq"] == 256
    assert small["config"]["linear_key_head_dim"] != small["config"]["linear_value_head_dim"]
    limits = loaded["limits"]
    assert {"loss_gap", "grad_norm_worst_leaf_gap", "param_change_worst_leaf_gap", "readings",
            "rehearse"} <= set(limits)


def test_train_mfu_is_read_on_the_two_accepted_train_cells_and_not_on_this_one():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "train_mfu")
    assert entry["workloads"] == TRAIN_BEFORE
    for cell in TRAIN_BEFORE:
        assert "train_mfu" in {m["name"] for m in harness.load_cell(cell)["per_layer"]}
        assert not {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(NEW)
    mine = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert "train_mfu" not in mine and mine == set(NEW) | set(SHARED) | set(UNLISTED)


def test_the_metrics_before_this_cells_keep_their_entries():
    """The accepted entries stand where they stood and this cell's stand behind
    them, by membership and RELATIVE order: nothing here asks that an entry be
    the last, so a later PR appends behind these without touching this test.
    All that ``test_qwen3_next_cell.py::test_the_host_ledgers_eight_metrics_keep_their_entries``
    asserts is asserted here, but for its one clause that nothing follow the
    ``.assist`` names (``tests/conftest.py`` says why that test is expected to fail)."""
    eight = ["tick_host_ms", "tick_host_ms.chat", "tick_stall_ms_per_s", "tick_stall_ms_per_s.chat",
             "gc_pause_ms_per_s", "gc_pause_ms_per_s.chat", "warmup_trace_lower_s", "warmup_cache_load_s"]
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    at = names.index(eight[0])
    assist = [n for n in names if n.endswith(".assist")]
    assert names[at:at + 8] == eight and names[at + 8:at + 8 + len(assist)] == assist and len(assist) == 20
    where = [names.index(n) for n in NEW]
    assert where == sorted(where) and where[0] >= at + 8 + len(assist)      # behind PR 40's, in the issue's order
    for cell in TRAIN_BEFORE + ["mistral7b.serve_chat", "mistral7b.serve_batch", "keye-vl2.serve_long",
                                "k-exaone.serve_reason", "joyai-flash.serve_docs", "qwen3-next.serve_assist"]:
        assert not {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(NEW)
    assert not {m["name"] for m in harness.load_cell(CELL)["per_layer"]} & set(eight + assist)
    chat_cells, tokens_cells = ["mistral7b.serve_chat"], ["mistral7b.serve_batch", "keye-vl2.serve_long"]
    for name in eight:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["source"] == "program_counter" and entry["better"] == "lower"
        if name.startswith("warmup_"):
            assert (entry["layer"], entry["moves"], entry["unit"]) == ("compile cache", "setup_s", "s")
            assert entry["workloads"] == chat_cells + tokens_cells
        else:
            chat = name.endswith(".chat")
            assert entry["layer"] == "serving engine"
            assert entry["moves"] == ("tpot_p90_ms" if chat else "serve_tokens_per_s")
            assert entry["workloads"] == (chat_cells if chat else tokens_cells)
            assert entry["unit"] == ("ms" if name.startswith("tick_host_ms") else "ms/s")
    for cell in chat_cells + tokens_cells:
        listed = {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(eight)
        assert len(listed) == 5 and all(n.endswith(".chat") == (cell in chat_cells)
                                        for n in listed if not n.startswith("warmup_"))


def test_the_traced_rehearsal_is_correct_and_every_new_reader_runs():
    """The cell's ``--rehearse --trace 1`` run ends in a ``correct`` line; on
    the CPU no op carries a device scope and there are no peaks, so the
    scoped readers and both shares return None (never raise, never a zero)
    and the line carries only names the cell lists."""
    out = _run(MAIN)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) <= set(NEW) | set(SHARED) | set(UNLISTED) and line["metrics"]
    assert "warmup_compile_s" in line["metrics"]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none(name):
    reader = harness.load_module("metrics", name)
    assert reader.read({"cfg": {}, "layers": 4}) is None
    llama_shaped = {"cfg": {"hidden_size": 4096, "head_dim": 128}, "layers": 6, "peaks": PEAKS, "seq": 4096,
                    "chips": 1, "tokens_per_step": 8192, "end_to_end": {"train_tokens_per_s": 15000.0}}
    assert reader.read(llama_shaped) is None                  # an untraced run of a cell without layer kinds


def test_the_controls_and_the_planted_faults_are_not_correct_in_rehearsal():
    """One process, the harness's own comparison at the rehearsal's sizes:
    the bf16 program reads under every limit of ``limits[rehearse]`` on each
    seed; the fp8 control and every planted fault of the family's own
    mechanisms read over at least one of them on each seed."""
    out = _run("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import prove\n"
               f"prove.main(['--workload', '{CELL}', '--seeds', '1,2,3', '--control-seeds', '1,2,3',"
               f"  '--control', 'fp8,{','.join(FAULTS)}', '--rehearse'])\n", timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith('{"seed"')]
    limits = harness.load_cell(CELL)["limits"]["rehearse"]
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[harness.limit_key(n)] for n, v in row["program"].items()), row
        for control in ("fp8",) + FAULTS:
            assert any(v > limits[harness.limit_key(n)] for n, v in row[f"control_{control}"].items()), \
                (control, row)


def test_a_program_without_the_family_fails_at_once_and_cleanly():
    """The parent of PR 42 has no ``models/olmo_hybrid.py``: given this
    benchmark, it exits non-zero on the family adapter's import, before jax
    is asked for a device."""
    t0 = time.perf_counter()
    out = _run("import sys; sys.path.insert(0, '.')\n"
               "sys.modules['accelerate_tpu.models.olmo_hybrid'] = None     # as if the file were absent\n"
               + MAIN.replace(", '--rehearse'", ""))
    assert out.returncode != 0 and out.stdout == ""
    assert "olmo_hybrid" in out.stderr and "no accelerator" not in out.stderr
    assert time.perf_counter() - t0 < 60


def test_roofline_arithmetic_at_the_published_sizes():
    cfg = harness.load_cell(CELL)["config"]
    # a Gated DeltaNet layer: q, k 2 x 3840 x 2880; v, the output gate, o 3 x 3840 x 5760; a, b 2 x 3840 x 30; the MLP
    linear = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 3 * 3840 * 11008
    full = 4 * 3840 * 3840 + 3 * 3840 * 11008
    assert train_step_hybrid.layer_matmul_params(cfg, "linear_attention") == linear == 215_516_160
    assert train_step_hybrid.layer_matmul_params(cfg, "full_attention") == full == 185_794_560
    head = 3840 * 100352
    assert train_step_hybrid.matmul_params(cfg, 4) == 3 * linear + full + head == 1_217_694_720
    assert train_step_hybrid.matmul_params(cfg, 8) == 6 * linear + 2 * full + head
    # causal attention in the ONE full layer: QK^T and PV over T/2 keys, x 3 for forward + backward
    attention = 3 * (2 * 2 * 30 * 128 * (8192 / 2))
    # the rule's own recurrence in the THREE linear layers: 7 Dk x Dv a token and head forward, twice that backward
    rule = 3 * (3 * 7 * 30 * 96 * 192)
    assert train_step_hybrid.flops_per_token(cfg, 4, 8192) == 6.0 * 1_217_694_720 + attention + rule
    assert train_step_hybrid.flops_per_token(cfg, 4, 8192) == pytest.approx(7.53e9, rel=0.001)
    # the rule over a step's tokens in three layers: q, k [96], v [192], g, beta read and o [192] written
    # forward (578 values); those and do read and the five gradients written backward (964); float32
    per_token_head = (2 * 96 + 192 + 2 + 192) + (2 * 96 + 192 + 2 + 192 + 2 * 96 + 192 + 2)
    assert per_token_head == 578 + 964
    assert linear_chunk_train.bytes_moved(3, 8192, 30, 96, 192) == 3 * 8192 * 30 * per_token_head * 4
    assert linear_chunk_train.bytes_moved(1, 8192, 30, 96, 192) == pytest.approx(1.516e9, rel=0.001)
    assert linear_chunk_train.operations(3, 8192, 30, 96, 192) == 3 * 8192 * 30 * 21 * 96 * 192
    # 63 FLOP a byte under the chip's 240: memory-bound, 1.85 ms a layer and step; no block size in the count
    assert linear_chunk_train.least_seconds(PEAKS, 1, 8192, 30, 96, 192) == \
        pytest.approx(linear_chunk_train.bytes_moved(1, 8192, 30, 96, 192) / 819e9)
    assert linear_chunk_train.least_seconds(PEAKS, 1, 8192, 30, 96, 192) == pytest.approx(1.851e-3, rel=0.001)
    assert linear_chunk_train.least_seconds(PEAKS, 3, 8192, 30, 96, 192) == \
        3 * linear_chunk_train.least_seconds(PEAKS, 1, 8192, 30, 96, 192)


def test_the_flash_shares_count_full_layers_by_kind_and_device_time_by_kernel_name():
    """A made-up trace of two steps at the published sizes: ``remat``'s second
    forward sits under ``transpose(`` as the backward kernel does, and belongs
    to ``flash_fwd`` all the same; one layer of four calls the kernels."""
    scope = "jit(pinned_step_fn)/{}(OlmoHybridForCausalLM)/layers_3/self_attn/{}/pallas_call"
    step = [(6000.0, scope.format("jvp", "flash_fwd")),
            (6000.0, scope.format("transpose(jvp", "rematted_computation/flash_fwd") + ")"),
            (8000.0, scope.format("transpose(jvp", "flash_bwd_dkv") + ")"),
            (500000.0, "jit(pinned_step_fn)/jvp(OlmoHybridForCausalLM)/layers_0/mlp/dot_general")]
    trace = {"modules": [(0.0, 0.0, "jit_pinned_step_fn(1)"), (0.0, 0.0, "jit_pinned_step_fn(2)")],
             "ops": [(0.0, d, tf_op, "%x = custom-call", run, 0) for run in (0, 1) for d, tf_op in step]}
    run = {"cfg": harness.load_cell(CELL)["config"], "layers": 4, "seq": 8192, "peaks": PEAKS, "trace": True,
           "program_trace": trace, "flash_shard": {"batch": 1, "heads": 30, "kv_heads": 30}}
    forward = 2 * 2 * 30 * 8192 * 8192 * 128 / 2 / 197e12             # QK^T and PV over half the square: 2.616 ms
    assert forward == pytest.approx(2.616e-3, rel=0.001)
    read = lambda name: harness.load_module("metrics", name).read(run)
    assert read("flash_fwd_roofline.hybrid") == pytest.approx(forward / 12e-3 * 100)       # ONE forward required
    assert read("flash_bwd_roofline.hybrid") == pytest.approx(2 * forward / 8e-3 * 100)    # four matmuls for two
