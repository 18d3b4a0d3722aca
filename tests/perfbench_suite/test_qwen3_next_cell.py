"""The cell ``qwen3-next.serve_assist`` and what PR 40 added beside it: the
benchmark lists the configuration, the traffic and the metrics as the issue
gives them (asserted by membership, never by position: a later cell is
appended behind this one); the cells before it keep their entries; the
rehearsal is ``correct`` and every new metric's reader runs; the
lower-precision control and each planted fault of the family's own mechanisms
(``reference/qwen3_next.FAULTS``) come out as NOT correct through the
harness's own comparison; a checkout whose program lacks the family fails at
once; the new roofline's counts by hand.  The rehearsal of the cell itself,
traced and untraced, is also ``test_perfbench.py``'s (every cell of
``BENCHMARK.json``)."""

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
from perfbench.rooflines import linear_attend  # noqa: E402

CELL = "qwen3-next.serve_assist"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = sorted(m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".assist"))
# the engine's host ledger (PR 38's counters) read on this cell: the review of PR 40 asked for them beside the issue's fifteen
LEDGER = ("tick_host_ms", "tick_stall_ms_per_s", "gc_pause_ms_per_s", "warmup_trace_lower_s", "warmup_cache_load_s")
SHARED = ("tick_launch_exposed_ms.batch", "tick_sync_exposed_ms.batch", "paged_write_kv_device_ms.batch")
REDUCED = {"num_hidden_layers", "num_experts", "num_attention_heads", "num_key_value_heads",
           "linear_num_key_heads", "linear_num_value_heads", "vocab_size"}
FAULTS = ("stale", "carry", "conv_carry", "decay", "beta", "l2", "out_gate", "attn_gate", "rope_all",
          "norm_plain", "shared_gate", "expert", "share", "softmax", "state_bf16")
# what moves every served token of the rehearsal's short requests (a few dozen positions, prompts
# of 8-48 tokens in chunks of 16, so every request crosses a chunk) by several limits; the others
# move the tokens of one expert, or by less, and read over the limit on SOME seed
EVERY_TOKEN = ("carry", "conv_carry", "beta", "l2", "out_gate", "norm_plain", "shared_gate", "share")


def _run(code, timeout=900):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


MAIN = ("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import run\n"
        f"run.main(['--workload', '{CELL}', '--seed', '2147483659', '--seconds', '3', '--trace', '1', "
        "'--rehearse'])\n")


def test_the_benchmark_lists_the_configuration_the_traffic_and_the_metrics_as_the_issue_gives_them():
    assert NEW == sorted(f"{name}.assist" for name in (
        "decode_tick_device_ms", "prefill_chunk_wall_ms", "serve_tick_wall_ms", "decode_batch_occupancy",
        "serve_device_idle_share", "linear_project_device_ms", "linear_attend_device_ms",
        "linear_attend_roofline", "global_attend_device_ms", "global_attend_roofline",
        "moe_route_device_ms", "moe_shared_device_ms", "moe_experts_device_ms", "moe_experts_roofline",
        "expert_load_max_over_mean") + LEDGER)
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == ("setup_s" if m["name"].startswith("warmup_") else "serve_tokens_per_s")
            assert m["unit"] == "%" if m["name"].endswith("_roofline.assist") else True
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"] and tokens["bound"] == 0.02
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("qwen3-next-80b-a3b", "serve_assist", 1)
    listed = next(c for c in BENCH["configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert set(listed["reduced"]) == REDUCED and listed["file"] == "perfbench/configs/qwen3-next-80b-a3b.json"
    assert listed["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    loaded = harness.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    published = {"hidden_size": 2048, "intermediate_size": 5120, "moe_intermediate_size": 512,
                 "shared_expert_intermediate_size": 512, "head_dim": 256, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "num_experts_per_tok": 10,
                 "partial_rotary_factor": 0.25, "full_attention_interval": 4, "rope_theta": 10000000,
                 "rms_norm_eps": 1e-6, "decoder_sparse_step": 1, "mlp_only_layers": []}
    assert {k: cfg[k] for k in published} == published            # no width is cut
    assert set(cfg["reduced"]) == REDUCED and cfg["model_family"] == "qwen3_next"
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512, "num_attention_heads": 16,
                                "num_key_value_heads": 2, "linear_num_key_heads": 16,
                                "linear_num_value_heads": 32, "vocab_size": 151936}
    held = ("num_hidden_layers", "num_experts", "num_attention_heads", "num_key_value_heads",
            "linear_num_key_heads", "linear_num_value_heads", "vocab_size")
    assert tuple(cfg[k] for k in held) == (8, 128, 4, 1, 4, 8, 37984) and cfg["vocab_size"] * 4 == 151936
    assert cfg["depth_by_kind"] == {"serve": 8}
    share = cfg["share"]
    assert share["experts_held"] == list(range(128)) and share["chips_per_layer"] == 4 and share["rank"] == 0
    assert share["linear_value_heads_held"] == list(range(8)) and share["vocab_rows_held"] == [0, 37984]
    assert traffic["kind"] == "serve_closed" and traffic["trace_seed"] == 20261040
    assert traffic["num_requests"] == 1024 and traffic["ramp_s"] == 20
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 4096, "sigma": 0.6,
                                     "min": 512, "max": 8192}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert traffic["engine"] == {"num_slots": 128, "page_size": 64, "num_pages": 128 * 288,
                                 "pages_per_slot": 288, "prefill_chunk": 2048,
                                 "prefill_buckets": [512, 2048], "max_new_tokens": 2048}
    assert 288 * 64 >= 16384 + 2048 and traffic["trace_seconds"] == 0.2    # the issue's one fallback: max 8192, nothing else moved


def test_the_cells_before_this_one_keep_their_entries():
    """What ``test_joyai_flash_cell.py::test_the_cell_before_this_one_keeps_its_entries``
    asserts, with membership and relative order where it asserts LAST TWO
    (``tests/conftest.py`` says why that one is expected to fail once a cell
    is appended behind PR 36's)."""
    before = ["k-exaone.serve_reason", "joyai-flash.serve_docs"]
    for suffix in (".reason", ".docs"):
        names = sorted(m["name"] for m in BENCH["per_layer"] if m["name"].endswith(suffix))
        assert len(names) == 13
        cell = before[suffix == ".docs"]
        for m in BENCH["per_layer"]:
            if m["name"] in names:
                assert m["workloads"] == [cell] and m["moves"] == "serve_tokens_per_s"
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    lists = [tokens["workloads"]] + [m["workloads"] for m in BENCH["per_layer"] if m["name"] in SHARED]
    assert len(lists) == 4 and tokens["bound"] == 0.02
    for cells in lists:           # appended behind them, nothing moved
        assert [c for c in cells if c in before + [CELL]] == before + [CELL]
        assert cells.index(before[1]) == cells.index(before[0]) + 1 == cells.index(CELL) - 1
    cfg = harness.load_cell(before[0])["config"]
    published = {"hidden_size": 6144, "head_dim": 128, "moe_intermediate_size": 2048,
                 "intermediate_size": 18432, "num_experts_per_tok": 8, "sliding_window": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "num_attention_heads",
                                   "num_key_value_heads", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_attention_heads": 64, "num_key_value_heads": 8,
                                "vocab_size": 153600}
    cfg = harness.load_cell(before[1])["config"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["num_attention_heads"],
            cfg["vocab_size"]) == (8, 32, 4, 16160)


def test_the_host_ledgers_eight_metrics_keep_their_entries():
    """What ``test_host_ledger_metrics.py::test_the_benchmark_lists_the_eight_as_the_issue_gives_them``
    asserts, with relative order where it asserts the LAST EIGHT (``tests/conftest.py``
    says why that one is expected to fail once metrics are appended behind PR 38's)."""
    eight = ["tick_host_ms", "tick_host_ms.chat", "tick_stall_ms_per_s", "tick_stall_ms_per_s.chat",
             "gc_pause_ms_per_s", "gc_pause_ms_per_s.chat", "warmup_trace_lower_s", "warmup_cache_load_s"]
    chat_cells, tokens_cells = ["mistral7b.serve_chat"], ["mistral7b.serve_batch", "keye-vl2.serve_long"]
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(eight[0])
    assert names[at:at + 8] == eight and names[at + 8:] == [n for n in names if n.endswith(".assist")]
    for name in eight:                 # together, in their order, and only this PR's behind them
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
    for cell in ("mistral7b.train_4k", "yi34b.train_fsdp2_tp2", "k-exaone.serve_reason",
                 "joyai-flash.serve_docs", CELL):
        assert not {m["name"] for m in harness.load_cell(cell)["per_layer"]} & set(eight)


def test_the_traced_rehearsal_is_correct_and_every_new_reader_runs():
    """The cell's ``--rehearse --trace 1`` run ends in a ``correct`` line; on
    the CPU no op carries a device scope, so the scoped readers return None
    (never raise, never a zero) and the counters' and tick log's readers
    report."""
    out = _run(MAIN)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in ("decode_batch_occupancy.assist", "serve_tick_wall_ms.assist",
                 "expert_load_max_over_mean.assist") + tuple(f"{name}.assist" for name in LEDGER):
        assert name in line["metrics"], name
    assert set(line["metrics"]) <= set(NEW) | {"warmup_compile_s"} | set(SHARED)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none(name):
    reader = harness.load_module("metrics", name)
    assert reader.read({"cfg": {}, "layers": 8}) is None
    assert reader.read({"cfg": {}, "layers": 8, "engine_metrics": {"decode_steps": 5},     # the parent's
                        "peaks": PEAKS, "ticks": [], "num_slots": 128}) is None           # engine


def test_the_controls_and_the_planted_faults_are_not_correct_in_rehearsal():
    """One process: the float32 program reads 0 against the float32
    reference; the faults that move every token by several limits read over
    the rehearsal's limit on every seed; the fp8 control and the faults that
    move fewer positions or by less read over it on SOME seed (which requests
    finish inside a 4 s window on a busy CPU, and so which are sampled,
    differs from run to run).  ``state_bf16`` reads UNDER it on every seed:
    a recurrent state rounded to bfloat16 once a token moves a served logit
    by 1e-4 here and by no more than the bf16 program's own rounding at the
    cell's widths (``limits/qwen3-next.serve_assist.json`` has the readings
    and what follows from them); the test holds the fact, so that whoever
    makes it visible finds this line."""
    out = _run("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import prove\n"
               f"prove.main(['--workload', '{CELL}', '--seeds', '1,2,3', '--control-seeds', '1,2,3',"
               f"  '--control', 'fp8,{','.join(FAULTS)}', '--seconds', '4', '--rehearse'])\n",
               timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith('{"seed"')]
    limit = harness.load_cell(CELL)["limits"]["rehearse"]["served_token_logit_gap"]
    assert len(rows) == 3
    gap = lambda row, control: row[f"control_{control}"]["served_token_logit_gap"]
    for row in rows:
        assert row["program"]["served_token_logit_gap"] <= limit, row
        assert gap(row, "state_bf16") <= limit, row
        for control in EVERY_TOKEN:
            assert gap(row, control) > limit, (control, row)
    for control in ("fp8",) + tuple(f for f in FAULTS if f not in EVERY_TOKEN + ("state_bf16",)):
        assert max(gap(row, control) for row in rows) > limit, control


def test_a_program_without_the_family_fails_at_once_and_cleanly():
    """The parent of PR 40 has no ``models/qwen3_next.py``: given this
    benchmark, it exits non-zero on the family adapter's import, before jax
    is asked for a device."""
    t0 = time.perf_counter()
    out = _run("import sys; sys.path.insert(0, '.')\n"
               "sys.modules['accelerate_tpu.models.qwen3_next'] = None     # as if the file were absent\n"
               + MAIN.replace(", '--rehearse'", ""))
    assert out.returncode != 0 and out.stdout == ""
    assert "qwen3_next" in out.stderr and "no accelerator" not in out.stderr
    assert time.perf_counter() - t0 < 60


def test_roofline_arithmetic():
    # 128 slots live in each of 6 Gated DeltaNet layers; 8 value heads of 128 x 128 held
    slot_layers = 6 * 128
    state = 8 * 128 * 128 * 4                                    # 512 KB a slot-layer, float32
    assert linear_attend.bytes_moved(slot_layers, 8, 128, 128) == \
        slot_layers * (2 * state + 8 * (2 * 128 + 2 * 128 + 2) * 4)      # read + written; q, k, v, o, g, beta
    assert linear_attend.bytes_moved(slot_layers, 8, 128, 128) == pytest.approx(0.818e9, rel=0.01)
    # the decay, S^T k, the rank-one write, S^T q: 7 operations an element of a head's state
    assert linear_attend.operations(1, 8, 128, 128) == 8 * 7 * 128 * 128
    # 0.9 FLOP a byte against the chip's 240: memory-bound; ~1.0 ms a tick at 819 GB/s
    assert linear_attend.least_seconds(PEAKS, slot_layers, 8, 128, 128) == \
        pytest.approx(linear_attend.bytes_moved(slot_layers, 8, 128, 128) / 819e9)
    assert linear_attend.least_seconds(PEAKS, slot_layers, 8, 128, 128) == pytest.approx(1.0e-3, rel=0.01)
    # a 2,048-token chunk through 6 layers: 32 blocks of 64 a head
    a_block = 2 * 64 * 64 * 128 + 10 * 64 ** 3 + 64 * 64 * 256 + 3 * 64 * 128 * 128 + 64 * 64 * 128
    assert linear_attend.chunk_operations(6, 2048, 8, 128, 128) == 6 * 32 * 8 * 2 * a_block
    assert linear_attend.chunk_operations(6, 2000, 8, 128, 128) == linear_attend.chunk_operations(6, 2048, 8, 128, 128)
    assert linear_attend.chunk_bytes_moved(6, 2048, 8, 128, 128) == \
        6 * (2 * state + 2048 * 8 * (4 * 128 + 2) * 4)
    # ~25 GFLOP against ~0.2 GB: 120 FLOP a byte, under the ridge at bf16's peak: memory-bound there,
    # compute-bound at the float32 rate the program really multiplies at
    assert linear_attend.chunk_least_seconds(PEAKS, 6, 2048, 8, 128, 128) == \
        pytest.approx(linear_attend.chunk_bytes_moved(6, 2048, 8, 128, 128) / 819e9)
