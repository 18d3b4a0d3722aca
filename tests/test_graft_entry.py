"""Driver-entry legs exercised as unit tests on the 8-device CPU mesh.

``dryrun_multichip`` itself is run by the driver; these tests pin the two
round-3 legs (composed dp×tp×pp multi-step training with save/restore, and
the sharded over-HBM checkpoint-to-decode path) so a regression shows up in
the suite before the driver artifact."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402


@pytest.mark.slow
def test_composed_dp_tp_pp_leg():
    losses_and_cont, restore_ok = graft._composed_dp_tp_pp_leg(
        8, np.random.default_rng(0)
    )
    assert restore_ok
    losses = losses_and_cont[:3]
    assert all(np.isfinite(losses))
    assert losses[2] < losses[1] < losses[0]


@pytest.mark.slow
def test_sharded_over_hbm_decode_leg():
    info = graft._sharded_over_hbm_decode_leg(8, np.random.default_rng(0))
    assert "tokens ok" in info
    assert "tp" in info  # params actually tp-sharded


@pytest.mark.slow
def test_resilience_leg():
    info = graft._resilience_leg(np.random.default_rng(0))
    assert "parity ok" in info
    assert "exit75" in info and "fallback" in info


@pytest.mark.slow
def test_launch_leg():
    """The multi-host launch story across REAL process boundaries: 2-proc
    bitwise loss parity vs the single-process mesh, SIGTERM on rank 1 →
    agreed stop → exit 75 → `launch --resume` onto 1 process with exact
    continuation parity (hierarchical ICI→DCN sync engaged throughout)."""
    info = graft._launch_leg()
    assert "bitwise parity ok" in info
    assert "resume@1proc" in info and "exact" in info


@pytest.mark.slow
def test_telemetry_leg():
    info = graft._telemetry_leg(np.random.default_rng(0))
    assert "tokens bitwise" in info and "schema valid" in info


@pytest.mark.slow
def test_prefix_leg():
    """tp=2 prefix-cached serve over shared-system-prompt traffic: hit
    rate > 0 with the scheduler-replay twin in exact agreement, survivors
    bitwise vs the reuse-off replay, zero post-warmup compiles, refcounted
    invariants green (the leg itself raises on any of these failing)."""
    info = graft._prefix_leg(np.random.default_rng(0))
    assert "parity ok" in info and "compiles=0" in info
    assert "hit_rate=" in info and "tp" in info


@pytest.mark.slow
def test_speculate_leg():
    """tp=2 speculative serve: token parity vs generate() over the same
    TP-sharded params, strict_compiles post-warmup, and a real tokens/step
    win (the leg itself raises on any of these failing)."""
    info = graft._speculate_leg(np.random.default_rng(0))
    assert "parity ok" in info and "compiles=0" in info
    assert "tp" in info  # params actually tp-sharded
