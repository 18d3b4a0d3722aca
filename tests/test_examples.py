"""Example scripts run end-to-end (reference tests/test_examples.py — the
feature examples are executed, not just diffed; SURVEY §4)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # subprocess example launches, minutes

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"


def _run(script, *extra, timeout=420):
    env = {k: v for k, v in os.environ.items() if not k.startswith("ACCELERATE_")}
    env["PYTHONPATH"] = str(REPO)
    cmd = [
        sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
        "--cpu", "--num_cpu_devices", "4", str(script), *extra,
    ]
    result = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO
    )
    assert result.returncode == 0, f"{script}:\n{result.stdout}\n{result.stderr}"
    return result.stdout


def test_nlp_example():
    out = _run(EXAMPLES / "nlp_example.py", "--num_epochs", "2")
    assert "accuracy" in out
    acc = float(out.strip().splitlines()[-1].rsplit("accuracy ", 1)[1].split()[0])
    assert acc > 0.8, out  # signal-token task is nearly separable


def test_cv_example():
    out = _run(EXAMPLES / "cv_example.py", "--num_epochs", "1")
    assert "loss" in out


def test_complete_cv_example(tmp_path):
    out = _run(
        EXAMPLES / "complete_cv_example.py", "--num_epochs", "2",
        "--with_tracking", "--checkpointing_steps", "epoch",
        "--project_dir", str(tmp_path / "run"),
    )
    assert "accuracy" in out
    resumed = _run(
        EXAMPLES / "complete_cv_example.py", "--num_epochs", "3",
        "--resume_from_checkpoint", "--checkpointing_steps", "never",
        "--project_dir", str(tmp_path / "run"),
    )
    assert "resumed at epoch 2" in resumed


def test_complete_nlp_example(tmp_path):
    """The canonical full-featured script: every composed feature active in
    one run (tracking, epoch checkpointing, accumulation, schedule, mixed
    precision, gathered metrics), then a resume run from its checkpoints."""
    out = _run(
        EXAMPLES / "complete_nlp_example.py", "--num_epochs", "2",
        "--with_tracking", "--checkpointing_steps", "epoch",
        "--gradient_accumulation_steps", "2",
        "--project_dir", str(tmp_path / "run"),
    )
    assert "accuracy" in out
    resumed = _run(
        EXAMPLES / "complete_nlp_example.py", "--num_epochs", "3",
        "--resume_from_checkpoint", "--checkpointing_steps", "never",
        "--gradient_accumulation_steps", "2",  # epoch accounting needs the
        "--project_dir", str(tmp_path / "run"),  # same loader batch size
    )
    assert "resumed at epoch 2" in resumed and "accuracy" in resumed


@pytest.mark.parametrize(
    "script,needle",
    [
        ("checkpointing.py", "resumed fine"),
        ("gradient_accumulation.py", "loss"),
        ("tracking.py", "logged"),
        ("profiler.py", "profile traced steps"),
        ("memory.py", "attempted batch sizes [128, 64, 32]"),
        ("local_sgd.py", "final loss"),
        ("pipeline_inference.py", "pipeline over 2 stage(s)"),
        ("generation.py", "generated (2, 16) tokens"),
        ("early_stopping.py", "stopped at epoch"),
        ("multi_process_metrics.py", "eval on exactly 77 samples"),
        ("automatic_gradient_accumulation.py", "physical batch 16 x 4 accumulation"),
        ("cross_validation.py", "4-fold mse"),
        ("schedule_free.py", "schedule-free averaged params"),
        ("fsdp_with_peak_mem_tracking.py", "q_proj sharding"),
        ("gradient_accumulation_for_autoregressive_models.py", "max param diff"),
        ("grad_comm_compression.py", "bf16 gradient collectives"),
        ("zero_offload.py", "targets 2, 3"),
        ("bf16_master_sr.py", "x smaller with SR"),
    ],
)
def test_by_feature_examples(script, needle):
    out = _run(EXAMPLES / "by_feature" / script)
    assert needle in out, out
