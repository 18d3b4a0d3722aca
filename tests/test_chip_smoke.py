"""``chip_smoke.py`` contract, rehearsed on the CPU in child processes (the
script owns its process' jax config, so it never runs in the test's own).
The chip run itself is the builders' (``chiprun -- python chip_smoke.py``)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(*args, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-c", code, *args] if code else [sys.executable, "chip_smoke.py", *args]
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("chips,phases", [
    (1, ["setup", "train", "serve"]),
    (4, ["setup", "sharded_train"]),
], ids=["one_chip", "four_chips"])
def test_rehearsal_runs_every_phase_and_reports_the_platform_it_ran_on(chips, phases):
    out = _run("--rehearse", "--chips", str(chips))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert lines[-1] == {"ok": True,
                         "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    assert [l["phase"] for l in lines[:-1]] == phases
    for line in lines[1:-1]:
        assert line["platform"] == "cpu" and line["compiles_after_warmup"] == 0
    if chips == 1:
        assert lines[2]["decode_kernel"] == "flash" and lines[2]["invariant_violations"] == 0
        assert lines[2]["finished"] == lines[2]["requests"]
    else:
        assert all(s["devices"] == [0, 1, 2, 3] for s in lines[1]["shards"])
        assert lines[1]["collectives"]


def test_without_a_chip_and_without_the_option_it_refuses():
    out = _run()
    assert out.returncode != 0
    assert out.stdout == ""  # no result line, no "ok"
    assert "no accelerator" in out.stderr


def test_a_phase_that_raises_is_a_nonzero_exit_with_no_ok_line():
    out = _run("--rehearse", code=(
        "import sys, chip_smoke\n"
        "def boom(*a, **k): raise RuntimeError('forced phase failure')\n"
        "chip_smoke.train_phase = boom\n"
        "sys.argv = ['chip_smoke.py'] + sys.argv[1:]\n"
        "chip_smoke.main()\n"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "forced phase failure" in out.stderr
