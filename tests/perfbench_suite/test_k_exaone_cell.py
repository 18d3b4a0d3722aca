"""The cell ``k-exaone.serve_reason`` and what PR 32 added beside it: the
rehearsal is ``correct`` and every new metric's reader runs; the
lower-precision control and each planted fault of the family's own mechanisms
(``reference/k_exaone.FAULTS``) come out as NOT correct through the harness's
own comparison; a checkout whose program lacks the family fails at once; the
reference's logits are indexed lazily; the new roofline's arithmetic.  The
rehearsal of the cell itself, traced and untraced, is also
``test_perfbench.py``'s (every cell of ``BENCHMARK.json``)."""

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
from perfbench.rooflines import moe_experts, paged_attend  # noqa: E402

CELL = "k-exaone.serve_reason"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NEW = sorted(m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".reason"))
FAULTS = ("window", "rope_global", "shared", "expert", "share", "softmax", "bias", "scale")


def _run(code, timeout=900):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


MAIN = ("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import run\n"
        f"run.main(['--workload', '{CELL}', '--seed', '2147483659', '--seconds', '3', '--trace', '1', "
        "'--rehearse'])\n")


def test_the_benchmark_lists_the_thirteen_metrics_and_the_cell_where_the_issue_says():
    assert len(NEW) == 13
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        if m["name"] in ("tick_launch_exposed_ms.batch", "tick_sync_exposed_ms.batch",
                         "paged_write_kv_device_ms.batch"):
            assert m["workloads"][-1] == CELL
    tokens = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.02
    cfg = harness.load_cell(CELL)["config"]
    published = {"hidden_size": 6144, "head_dim": 128, "moe_intermediate_size": 2048,
                 "intermediate_size": 18432, "num_experts_per_tok": 8, "sliding_window": 128}
    assert {k: cfg[k] for k in published} == published            # no width is cut
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
    for name in ("decode_batch_occupancy.reason", "serve_tick_wall_ms.reason",
                 "expert_load_max_over_mean.reason", "prefill_chunk_wall_ms.reason"):
        assert name in line["metrics"], name
    assert set(line["metrics"]) <= set(NEW) | {"warmup_compile_s", "tick_launch_exposed_ms.batch",
                                               "tick_sync_exposed_ms.batch",
                                               "paged_write_kv_device_ms.batch"}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none(name):
    reader = harness.load_module("metrics", name)
    assert reader.read({"cfg": {}, "layers": 8}) is None
    assert reader.read({"cfg": {}, "layers": 8, "engine_metrics": {"decode_steps": 5},     # the parent's
                        "peaks": PEAKS, "ticks": [], "num_slots": 64}) is None            # engine


def test_the_controls_and_the_planted_faults_are_not_correct_in_rehearsal():
    """One process: the float32 program reads 0 against the float32
    reference, the fp8 control and every planted fault read over the
    rehearsal's limit on every seed — except the two the limits file names:
    ``bias`` (the selection ignoring ``b`` changes the choice of a token only
    where ``b`` outweighs the gap between its fourth and fifth scores: seldom
    at the rehearsal's 16 experts) must read over the limit on SOME seed."""
    out = _run("import sys; sys.path.insert(0, '.'); sys.path.insert(0, 'perfbench'); import prove\n"
               f"prove.main(['--workload', '{CELL}', '--seeds', '1,2,3', '--control-seeds', '1,2,3',"
               f"  '--control', 'fp8,{','.join(FAULTS)}', '--seconds', '4', '--rehearse'])\n",
               timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith('{"seed"')]
    limit = harness.load_cell(CELL)["limits"]["rehearse"]["served_token_logit_gap"]
    assert len(rows) == 3
    read = lambda row, name: row[f"control_{name}"]["served_token_logit_gap"]
    for row in rows:
        assert row["program"]["served_token_logit_gap"] <= limit, row
        for control in ("fp8",) + tuple(f for f in FAULTS if f != "bias"):
            assert read(row, control) > limit, (control, row)
    assert max(read(row, "bias") for row in rows) > limit


def test_a_program_without_the_family_fails_at_once_and_cleanly():
    """The parent of PR 32 has no ``models/k_exaone.py``: given this
    benchmark, it exits non-zero on the family adapter's import, before jax
    is asked for a device."""
    t0 = time.perf_counter()
    out = _run("import sys; sys.path.insert(0, '.')\n"
               "sys.modules['accelerate_tpu.models.k_exaone'] = None     # as if the file were absent\n"
               + MAIN.replace(", '--rehearse'", ""))
    assert out.returncode != 0 and out.stdout == ""
    assert "k_exaone" in out.stderr and "no accelerator" not in out.stderr
    assert time.perf_counter() - t0 < 60


def test_the_references_logits_are_indexed_lazily_and_the_row_is_cut():
    """At the cell's capacity a float32 [4, 18432, 19200] array is 5.7 GB:
    ``forward_logits`` hands back an object that runs one row up to the last
    position asked for (cut to it, rounded up) and applies the head to the
    span only."""
    import jax.numpy as jnp

    from perfbench.families import k_exaone as family
    from perfbench.reference import k_exaone as reference
    from perfbench.weights import make_weights

    cell = harness.load_cell(CELL)
    cfg = {**cell["config"], **cell["traffic"]["rehearse"]["config"]}
    weights = make_weights(family.weight_shapes(cfg, 8), seed=3)
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 64)).astype(np.int32)
    ids[1, 40:] = 0                                               # padded, as the benchmark pads
    lazy = reference.forward_logits(weights, cfg, 8, jnp.asarray(ids))
    assert lazy.shape == (2, 64, cfg["vocab_size"]) and not hasattr(lazy, "dtype")
    part = lazy[1, slice(30, 40)]
    whole = reference.row_logits(weights, cfg, 8, ids[1, :40])
    assert part.shape == (10, cfg["vocab_size"])
    np.testing.assert_allclose(part, whole[30:40], rtol=1e-5, atol=1e-5)
    assert reference.row_hidden(weights, cfg, 8, ids[0], need=20).shape[0] == 20   # cut, not 64


def test_a_tied_choice_of_experts_is_not_judged():
    """Where the last expert chosen and the first left out score within the
    margin and either is held, the sound forward's logits read flat at that
    position (gap 0 whatever was served); with both absent, or a wide margin,
    the position is judged; a planted fault's forward is never masked."""
    import jax.numpy as jnp

    from perfbench.families import k_exaone as family
    from perfbench.reference import k_exaone as reference
    from perfbench.weights import make_weights

    cell = harness.load_cell(CELL)
    cfg = {**cell["config"], **cell["traffic"]["rehearse"]["config"]}      # 4 of 16 held, 4 a token
    key = reference.cfg_key({**cfg, "assumed": {"tie_margin": 1e-3}})
    def scored(order):           # logits that rank the experts in ``order``, a quarter apart
        row = np.zeros((16,), np.float32)
        row[list(order)] = 0.25 * np.arange(16, 0, -1)
        return row
    absent = scored([15, 14, 13, 12, 11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0])   # 4th and 5th: 12, 11
    held = scored([15, 14, 13, 3, 2, 12, 11, 10, 9, 8, 7, 6, 5, 4, 1, 0])     # 4th and 5th: 3, 2 (held)
    n = np.stack([absent, held, held])
    n[0, 11], n[1, 2] = n[0, 12] - 1e-4, n[1, 3] - 1e-4         # rows 0 and 1: the edge is a tie
    n = jnp.asarray(n)                                           # logits = n @ I
    _, _, _, tie = reference._route(n, jnp.ones((3,), bool), jnp.eye(16), jnp.zeros((16,)),
                                    reference.NO_FAULT, key=key)
    assert tie.tolist() == [False, True, False]                  # absent pair; held edge; wide margin

    weights = make_weights(family.weight_shapes(cfg, 8), seed=3)
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (1, 48)).astype(np.int32)
    everything = {**cfg, "assumed": {**cfg["assumed"], "tie_margin": 0.01}}    # a wide margin: many ties
    lazy = reference.forward_logits(weights, everything, 8, jnp.asarray(ids))
    got = np.asarray(lazy[0, slice(0, 48)])
    _, tied = reference.row_hidden(weights, everything, 8, ids[0], ties=True)
    tied = np.asarray(tied)[:48]
    assert 0 < tied.sum() < 48 and lazy.tied == tied.sum()
    whole = np.asarray(reference.row_logits(weights, cfg, 8, ids[0]))
    assert not got[tied].any()
    np.testing.assert_allclose(got[~tied], whole[~tied], rtol=1e-5, atol=1e-5)
    faulty = reference.forward_logits(weights, everything, 8, jnp.asarray(ids), quant="shared")
    assert np.asarray(faulty[0, slice(0, 48)])[tied].any() and faulty.tied == 0


def test_roofline_arithmetic():
    # 64 slots at a mean context of 4,000 in each of 2 full-attention layers; 1 KV head x 128
    visible, queries = 2 * 64 * 4000, 2 * 64
    kv = 2 * visible * 1 * 128 * 2
    assert paged_attend.bytes_moved(visible, queries, 8, 1, 128) == kv + 2 * queries * 8 * 128 * 2
    assert paged_attend.operations(visible, 8, 128) == 4 * visible * 8 * 128
    # 8 query heads read one KV head: 16 flops a byte, far under the chip's 240: memory-bound
    assert paged_attend.least_seconds(PEAKS, visible, queries, 8, 1, 128) == \
        pytest.approx(paged_attend.bytes_moved(visible, queries, 8, 1, 128) / 819e9)
    # the held experts at decode: 16 hit, 64 rows, hidden 6144, width 2048: the 1.2 GB of weights
    weights = 16 * 3 * 6144 * 2048 * 2
    assert moe_experts.least_seconds(PEAKS, 16, 64, 6144, 2048) == \
        pytest.approx((weights + 64 * (6144 * 2 + 4 * 2048 * 2 + 6144 * 4)) / 819e9)
