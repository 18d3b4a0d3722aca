"""The cell ``joyai-flash.serve_docs`` and what PR 36 added beside it: the
benchmark lists the configuration, the traffic and the metrics as the issue
gives them; the rehearsal is ``correct`` and every new metric's reader runs;
the lower-precision control and each planted fault of the family's own
mechanisms (``reference/joyai_flash.FAULTS``) come out as NOT correct through
the harness's own comparison; a checkout whose program lacks the family fails
at once; the reference's logits are indexed lazily and a tied routing choice
is not judged; the new roofline's counts by hand.  The rehearsal of the cell
itself, traced and untraced, is also ``test_perfbench.py``'s (every cell of
``BENCHMARK.json``)."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from perfbench import harness  # noqa: E402
from perfbench.rooflines import latent_attend  # noqa: E402

CELL = "joyai-flash.serve_docs"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = sorted(m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".docs"))
FAULTS = ("rope", "attn_scale", "kv_norm", "kr_raw", "value_slice", "blind", "expert", "bias",
          "gate_scale", "shared", "share")
EVERY_TOKEN = ("rope", "kv_norm", "kr_raw", "value_slice", "blind", "shared", "share")


def _run(code, timeout=900):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


MAIN = ("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import run\n"
        f"run.main(['--workload', '{CELL}', '--seed', '2147483659', '--seconds', '3', '--trace', '1', "
        "'--rehearse'])\n")


def test_the_benchmark_lists_the_configuration_the_traffic_and_the_metrics_as_the_issue_gives_them():
    assert len(NEW) == 13
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        if m["name"] in ("tick_launch_exposed_ms.batch", "tick_sync_exposed_ms.batch",
                         "paged_write_kv_device_ms.batch"):
            assert CELL in m["workloads"]
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"] and tokens["bound"] == 0.02
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("joyai-llm-flash", "serve_docs", 1)
    loaded = harness.load_cell(CELL)
    cfg, traffic = loaded["config"], loaded["traffic"]
    published = {"hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768,
                 "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64,
                 "num_experts_per_tok": 8, "n_shared_experts": 1, "first_k_dense_replace": 1}
    assert {k: cfg[k] for k in published} == published            # no width is cut
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts", "num_attention_heads",
                                   "num_key_value_heads", "vocab_size"}
    assert set(cfg["reduced"]) == set(next(c for c in BENCH["configs"]
                                           if c["name"] == "joyai-llm-flash")["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256,
                                "num_attention_heads": 32, "num_key_value_heads": 32,
                                "vocab_size": 129280}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["num_attention_heads"],
            cfg["vocab_size"]) == (8, 32, 4, 16160) and cfg["vocab_size"] * 8 == 129280
    assert cfg["share"]["experts_held"] == list(range(32)) and cfg["share"]["chips_per_layer"] == 8
    assert traffic["kind"] == "serve_closed" and traffic["trace_seed"] == 20260936
    assert traffic["num_requests"] == 1024 and traffic["ramp_s"] == 20
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                     "min": 2048, "max": 16384}
    assert traffic["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert traffic["engine"] == {"num_slots": 48, "page_size": 64, "num_pages": 48 * 264,
                                 "pages_per_slot": 264, "prefill_chunk": 2048,
                                 "prefill_buckets": [512, 2048], "max_new_tokens": 512}
    assert 264 * 64 >= 16384 + 512                                # a slot holds its longest request


def test_the_cell_before_this_one_keeps_its_entries():
    """What ``test_k_exaone_cell.py``'s first test asserts, with membership
    where it asserts LAST (``conftest.py`` says why that one is expected to
    fail once a cell is appended behind PR 32's)."""
    before = "k-exaone.serve_reason"
    reason = sorted(m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".reason"))
    assert len(reason) == 13
    for m in BENCH["per_layer"]:
        if m["name"] in reason:
            assert m["workloads"] == [before] and m["moves"] == "serve_tokens_per_s"
        if m["name"] in ("tick_launch_exposed_ms.batch", "tick_sync_exposed_ms.batch",
                         "paged_write_kv_device_ms.batch"):
            assert m["workloads"][-2:] == [before, CELL]           # appended, nothing moved
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-2:] == [before, CELL] and tokens["bound"] == 0.02
    cfg = harness.load_cell(before)["config"]
    published = {"hidden_size": 6144, "head_dim": 128, "moe_intermediate_size": 2048,
                 "intermediate_size": 18432, "num_experts_per_tok": 8, "sliding_window": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "num_attention_heads",
                                   "num_key_value_heads", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_attention_heads": 64, "num_key_value_heads": 8,
                                "vocab_size": 153600}


def test_the_traced_rehearsal_is_correct_and_every_new_reader_runs():
    """The cell's ``--rehearse --trace 1`` run ends in a ``correct`` line; on
    the CPU no op carries a device scope, so the scoped readers return None
    (never raise, never a zero) and the counters' and tick log's readers
    report."""
    out = _run(MAIN)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in ("decode_batch_occupancy.docs", "serve_tick_wall_ms.docs",
                 "expert_load_max_over_mean.docs"):
        assert name in line["metrics"], name
    assert set(line["metrics"]) <= set(NEW) | {"warmup_compile_s", "tick_launch_exposed_ms.batch",
                                               "tick_sync_exposed_ms.batch",
                                               "paged_write_kv_device_ms.batch"}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none(name):
    reader = harness.load_module("metrics", name)
    assert reader.read({"cfg": {}, "layers": 8}) is None
    assert reader.read({"cfg": {}, "layers": 8, "engine_metrics": {"decode_steps": 5},     # the parent's
                        "peaks": PEAKS, "ticks": [], "num_slots": 48}) is None            # engine


def test_the_prefill_chunk_reader_subtracts_a_decode_tick_that_follows_no_prefill_tick():
    """Host clock, whole window: prefill tick start -> end of the decode tick
    behind it, minus the median decode tick that has a decode tick before it
    (most decode ticks here follow a prefill tick: their median holds a chunk)."""
    reader = harness.load_module("metrics", "prefill_chunk_wall_ms.docs")
    tick = lambda kind, start, end, bucket=0: {"kind": kind, "start": start, "end": end, "bucket": bucket}
    ticks = [tick("decode", 0.000, 0.060), tick("prefill", 0.060, 0.063, 2048), tick("decode", 0.063, 0.150),
             tick("prefill", 0.150, 0.153, 2048), tick("decode", 0.153, 0.242), tick("decode", 0.242, 0.304),
             tick("prefill", 0.304, 0.306, 512), tick("decode", 0.306, 0.380), tick("decode", 0.380, 0.440)]
    run = {"ticks": ticks, "host_window_s": 1.0}
    # pairs behind a 2048 chunk: 0.090, 0.092 (median 0.091); decode after decode: 0.062, 0.060 (median 0.061)
    assert reader.read(run) == pytest.approx(30.0)
    assert reader.read({"ticks": ticks[:1], "host_window_s": 1.0}) is None


def test_the_controls_and_the_planted_faults_are_not_correct_in_rehearsal():
    """One process: the float32 program reads 0 against the float32
    reference; the faults that move every token (the latent attention's six,
    the shared expert, the absent experts' rows) read over the rehearsal's
    limit on every seed; the fp8 control, the softmax scale and the faults
    that move only the tokens routed to one expert or whose choice the bias
    decides read over it on SOME seed (which requests finish inside a 4 s
    window on a busy CPU, and so which are sampled, differs from run to run:
    ``limits/joyai-flash.serve_docs.json`` has a quiet run's readings, all
    over the limit)."""
    out = _run("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import prove\n"
               f"prove.main(['--workload', '{CELL}', '--seeds', '1,2,3', '--control-seeds', '1,2,3',"
               f"  '--control', 'fp8,{','.join(FAULTS)}', '--seconds', '4', '--rehearse'])\n",
               timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith('{"seed"')]
    limit = harness.load_cell(CELL)["limits"]["rehearse"]["served_token_logit_gap"]
    assert len(rows) == 3
    for row in rows:
        assert row["program"]["served_token_logit_gap"] <= limit, row
        for control in EVERY_TOKEN:
            assert row[f"control_{control}"]["served_token_logit_gap"] > limit, (control, row)
    for control in ("fp8",) + tuple(f for f in FAULTS if f not in EVERY_TOKEN):
        assert max(row[f"control_{control}"]["served_token_logit_gap"] for row in rows) > limit, control


def test_a_program_without_the_family_fails_at_once_and_cleanly():
    """The parent of PR 36 has no ``models/joyai_flash.py``: given this
    benchmark, it exits non-zero on the family adapter's import, before jax
    is asked for a device."""
    t0 = time.perf_counter()
    out = _run("import sys; sys.path.insert(0, '.')\n"
               "sys.modules['accelerate_tpu.models.joyai_flash'] = None     # as if the file were absent\n"
               + MAIN.replace(", '--rehearse'", ""))
    assert out.returncode != 0 and out.stdout == ""
    assert "joyai_flash" in out.stderr and "no accelerator" not in out.stderr
    assert time.perf_counter() - t0 < 60


def _rehearsal_weights(seed=3):
    from perfbench.families import joyai_flash as family
    from perfbench.weights import make_weights

    cell = harness.load_cell(CELL)
    cfg = {**cell["config"], **cell["traffic"]["rehearse"]["config"]}      # 4 of 16 held, 4 a token
    return cfg, make_weights(family.weight_shapes(cfg, 3), seed=seed)


def test_the_references_logits_are_indexed_lazily_and_the_row_is_cut():
    """At the cell's capacity a float32 [4, 16896, 16160] array is 4.4 GB:
    ``forward_logits`` hands back an object that runs one row up to the last
    position asked for (cut to it, rounded up) and applies the head to the
    span only."""
    import jax.numpy as jnp

    from perfbench.reference import joyai_flash as reference

    cfg, weights = _rehearsal_weights()
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 64)).astype(np.int32)
    ids[1, 40:] = 0                                               # padded, as the benchmark pads
    lazy = reference.forward_logits(weights, cfg, 3, jnp.asarray(ids))
    assert lazy.shape == (2, 64, cfg["vocab_size"]) and not hasattr(lazy, "dtype")
    part = lazy[1, slice(30, 40)]
    whole = reference.row_logits(weights, cfg, 3, ids[1, :40])
    assert part.shape == (10, cfg["vocab_size"])
    tied = np.asarray(reference.row_hidden(weights, cfg, 3, ids[1, :40], ties=True)[1])[30:40]
    assert tied.sum() <= 3 and not np.asarray(part)[tied].any()   # a tied position reads flat
    np.testing.assert_allclose(np.asarray(part)[~tied], np.asarray(whole[30:40])[~tied],
                               rtol=1e-5, atol=1e-5)
    assert reference.row_hidden(weights, cfg, 3, ids[0], need=20).shape[0] == 20   # cut, not 64


def test_a_tied_choice_of_experts_is_not_judged():
    """The tie rule is ``reference/k_exaone.py``'s, reached through this
    family's reference: with a wide margin many positions are ties and read
    flat in the sound forward; a planted fault's forward is never masked."""
    import jax.numpy as jnp

    from perfbench.reference import joyai_flash as reference

    cfg, weights = _rehearsal_weights()
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (1, 48)).astype(np.int32)
    everything = {**cfg, "assumed": {**cfg["assumed"], "tie_margin": 0.01}}    # a wide margin: many ties
    lazy = reference.forward_logits(weights, everything, 3, jnp.asarray(ids))
    got = np.asarray(lazy[0, slice(0, 48)])
    _, tied = reference.row_hidden(weights, everything, 3, ids[0], ties=True)
    tied = np.asarray(tied)[:48]
    assert 0 < tied.sum() < 48 and lazy.tied == tied.sum()
    whole = np.asarray(reference.row_logits(weights, cfg, 3, ids[0]))
    assert not got[tied].any()
    np.testing.assert_allclose(got[~tied], whole[~tied], rtol=1e-5, atol=1e-5)
    faulty = reference.forward_logits(weights, everything, 3, jnp.asarray(ids), quant="shared")
    assert np.asarray(faulty[0, slice(0, 48)])[tied].any() and faulty.tied == 0
    # this family's names for the expert layer's faults reach reference/k_exaone.py's flags
    flags = reference._kx_flags(reference.split_control("gate_scale")[1])
    assert dict(zip(reference.kx.FAULTS, flags))["scale"] and flags.sum() == 1
    assert not reference._kx_flags(reference.split_control("rope")[1]).any()


def test_roofline_arithmetic():
    # 48 slots at a mean context of 9,000 in each of 8 layers; 4 heads held; a row is 512 + 64 values
    visible, queries = 8 * 48 * 9000, 8 * 48
    rows = visible * 576 * 2                                       # 1,152 B a visible key-layer, once
    assert latent_attend.bytes_moved(visible, queries, 4, 512, 64) == \
        rows + queries * 4 * (576 + 512) * 2                      # + q [4 x 576] in, u [4 x 512] out
    assert latent_attend.operations(visible, 4, 512, 64) == visible * 4 * (576 + 512) * 2
    # 8,704 FLOP against 1,152 B a key: 7.6 FLOP a byte, far under the chip's 240: memory-bound
    assert latent_attend.operations(1, 4, 512, 64) == 8704
    assert latent_attend.least_seconds(PEAKS, visible, queries, 4, 512, 64) == \
        pytest.approx(latent_attend.bytes_moved(visible, queries, 4, 512, 64) / 819e9)
    # all 128 heads of a DeepSeek-V3 on one chip sit AT the ridge (242 FLOP a byte against 240); twice
    # that many would be compute-bound: the roofline takes the larger
    assert latent_attend.operations(1, 128, 512, 64) / 1152 == pytest.approx(241.8, abs=0.1)
    assert latent_attend.least_seconds(PEAKS, visible, queries, 256, 512, 64) == \
        pytest.approx(latent_attend.operations(visible, 256, 512, 64) / 197e12)
