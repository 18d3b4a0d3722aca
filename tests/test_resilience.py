"""The resilience acceptance matrix (docs/resilience.md), green on CPU:

(a) SIGTERM mid-run → emergency checkpoint → a FRESH PROCESS resumes with
    bit-exact params/opt-state/RNG/dataloader position vs an uninterrupted
    run (subprocess e2e);
(b) corrupt/truncated latest checkpoint → ``load_state`` falls back to the
    newest valid one with a warning, no crash;
(c) injected NaN grad → step skipped, params bitwise unchanged, counters
    advance, abort after K consecutive;
(d) transient transfer failure → bounded retry/backoff, result identical to
    the no-fault run;

plus the satellites: async-save orphan flush at interpreter exit, retention
GC vs the fallback scan, mid-epoch dataloader resume bit-parity, and the
fault-plan/goodput machinery itself."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu import Accelerator
from accelerate_tpu.checkpointing import (
    CheckpointCorruptError,
    list_checkpoints,
    verify_checkpoint,
    write_checkpoint_manifest,
)
from accelerate_tpu.resilience import (
    CORRUPTION_MODES,
    FAULT_KINDS,
    RESUME_EXIT_CODE,
    FaultEvent,
    FaultPlan,
    GoodputTracker,
    InjectedTransferError,
    NanGuardAbort,
    PeerSchemaError,
    PeerSnapshotter,
    PreemptionHandler,
    RankLostError,
    RetryPolicy,
    capture_host_snapshot,
    check_snapshot_schemas,
    corrupt_checkpoint,
    fault_plan,
    goodput_accounting,
    install_fault_plan,
    peer_ckpt_accounting,
    restore_host_snapshot,
    snapshot_schema,
    with_retries,
)
from accelerate_tpu.resilience.faults import KIND_DEFAULT_SITE
from accelerate_tpu.test_utils.training import (
    make_regression_loader,
    regression_init_params,
    regression_loss_fn,
)
from accelerate_tpu.utils.dataclasses import ProjectConfiguration, ResiliencePlugin

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _disarm_fault_plan():
    """No fault plan may leak across tests (the hooks are process-global)."""
    yield
    install_fault_plan(None)


def _setup(tmp_path, *, plugin=None, total_limit=None):
    acc = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(tmp_path), automatic_checkpoint_naming=True,
            total_limit=total_limit,
        ),
        resilience_plugin=plugin,
    )
    dl = acc.prepare(make_regression_loader(batch_size=16))
    state = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    step = acc.prepare_train_step(regression_loss_fn)
    return acc, dl, state, step


def _bytes_of(x) -> bytes:
    return np.asarray(x).tobytes()


# ---------------------------------------------------------------------------
# (a) SIGTERM → emergency checkpoint → fresh-process resume, bit-exact
# ---------------------------------------------------------------------------


_TRAIN_SCRIPT = textwrap.dedent('''
    """Fault-matrix training subprocess: N regression steps with periodic-free
    checkpointing discipline — resume state comes only from the emergency
    checkpoint a preemption writes."""
    import json, random, sys

    import numpy as np
    import optax
    import jax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils.training import (
        make_regression_loader, regression_init_params, regression_loss_fn,
    )
    from accelerate_tpu.utils.dataclasses import ProjectConfiguration, ResiliencePlugin
    from accelerate_tpu.utils.random import set_seed

    project_dir, result_file = sys.argv[1], sys.argv[2]
    TOTAL_STEPS = 6  # epoch = 4 batches, so the run crosses an epoch boundary

    set_seed(123)  # a known host-RNG stream (captured/restored by checkpoints)
    acc = Accelerator(
        project_config=ProjectConfiguration(
            project_dir=project_dir, automatic_checkpoint_naming=True
        ),
        resilience_plugin=ResiliencePlugin(handle_preemption=True, nan_guard=False),
    )
    dl = acc.prepare(make_regression_loader(batch_size=16))
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    state = acc.maybe_resume(train_state=template)
    if state is None:
        state = template
    step = acc.prepare_train_step(regression_loss_fn)

    consumed = []  # batch fingerprints, in training order
    while acc.step_count < TOTAL_STEPS:
        for batch in dl:
            consumed.append(np.asarray(batch["x"]).tobytes().hex())
            state, metrics = step(state, batch)
            if acc.step_count >= TOTAL_STEPS:
                break

    acc.end_training()
    result = {
        "a": np.asarray(state.params["a"]).tobytes().hex(),
        "b": np.asarray(state.params["b"]).tobytes().hex(),
        "mu_a": np.asarray(state.opt_state[0].mu["a"]).tobytes().hex(),
        "nu_a": np.asarray(state.opt_state[0].nu["a"]).tobytes().hex(),
        "step": int(state.step),
        "step_count": acc.step_count,
        "rng_key": np.asarray(jax.random.key_data(state.rng)).tobytes().hex(),
        "py_rand": random.random(),
        "np_rand": float(np.random.rand()),
        "restarts": acc.goodput.restarts,
        "consumed": consumed,
    }
    with open(result_file, "w") as f:
        json.dump(result, f)
''')


def _run_subprocess(script: str, args, extra_env=None, expect_code=0):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, "-c", script, *map(str, args)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == expect_code, (
        f"exit {out.returncode} (want {expect_code})\n{out.stderr[-3000:]}"
    )
    return out


def test_sigterm_preemption_fresh_process_resume_bit_exact(tmp_path):
    """Acceptance (a): the whole flow across REAL process boundaries.  The
    preempted run gets a SIGTERM during step 3 (via the deterministic fault
    plan → os.kill through the installed handler), exits 75 after writing
    the emergency checkpoint; a fresh process auto-resumes and must finish
    with bit-identical params/opt-state/RNG — and the concatenated batch
    stream must equal the uninterrupted run's exactly."""
    clean_dir, faulted_dir = tmp_path / "clean", tmp_path / "faulted"
    clean_res, res1, res2 = (tmp_path / f"r{i}.json" for i in range(3))

    _run_subprocess(_TRAIN_SCRIPT, [clean_dir, clean_res])
    clean = json.loads(clean_res.read_text())
    assert clean["step_count"] == 6 and len(clean["consumed"]) == 6

    # run 1: preempted during step 3 → resume exit code, no result file
    _run_subprocess(
        _TRAIN_SCRIPT, [faulted_dir, res1],
        extra_env={"ACCELERATE_FAULT_PLAN": json.dumps(
            {"events": [{"kind": "preempt", "at": 3}]}
        )},
        expect_code=RESUME_EXIT_CODE,
    )
    assert not res1.exists()
    ckpts = list_checkpoints(str(faulted_dir))
    assert len(ckpts) == 1, "exactly the emergency checkpoint"
    ok, problems = verify_checkpoint(ckpts[0])
    assert ok, problems

    # run 2: fresh process, auto-resume, finish the budget
    _run_subprocess(_TRAIN_SCRIPT, [faulted_dir, res2])
    resumed = json.loads(res2.read_text())

    assert resumed["restarts"] == 1
    assert resumed["step"] == clean["step"] == 6
    # bit-exact state: params, optimizer moments, the traced RNG key
    for key in ("a", "b", "mu_a", "nu_a", "rng_key"):
        assert resumed[key] == clean[key], key
    # host RNG streams restored from the emergency checkpoint
    assert resumed["py_rand"] == clean["py_rand"]
    assert resumed["np_rand"] == clean["np_rand"]
    # dataloader position: 3 batches before the preemption + 3 after == the
    # uninterrupted stream, nothing replayed, nothing skipped
    assert len(resumed["consumed"]) == 3
    assert clean["consumed"][3:] == resumed["consumed"]


def test_preemption_in_process_exit_and_emergency_checkpoint(tmp_path):
    """The in-process half of (a): request → boundary stop → verified
    emergency checkpoint → SystemExit(75) → resume restores the state."""
    plugin = ResiliencePlugin(handle_preemption=True, nan_guard=False)
    acc, dl, state, step = _setup(tmp_path, plugin=plugin)
    batch = next(iter(dl))
    state, _ = step(state, batch)
    acc._preemption.request()
    with pytest.raises(SystemExit) as exc:
        step(state, batch)
    assert exc.value.code == RESUME_EXIT_CODE
    assert acc.goodput.preemptions == 1
    ckpts = list_checkpoints(str(tmp_path))
    assert len(ckpts) == 1
    ok, problems = verify_checkpoint(ckpts[0])
    assert ok, problems

    acc._preemption.clear()
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    restored = acc.maybe_resume(train_state=template)
    assert restored is not None and int(restored.step) == 2
    assert acc.goodput.restarts == 1


def test_preemption_handler_real_signal_delivery():
    import signal

    handler = PreemptionHandler(("SIGTERM",)).install()
    try:
        assert not handler.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert handler.requested
        handler.clear()
        assert not handler.requested
    finally:
        handler.uninstall()


# ---------------------------------------------------------------------------
# (b) corrupt latest checkpoint → verified fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_corrupt_latest_falls_back_to_newest_valid(tmp_path, mode, caplog):
    acc, dl, state, step = _setup(tmp_path)
    for batch in dl:
        state, _ = step(state, batch)
        acc.save_state(train_state=state)
    ckpts = list_checkpoints(str(tmp_path))
    assert len(ckpts) >= 2
    good_state_a = None
    # remember the params the second-newest checkpoint holds
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    good_state_a = float(np.asarray(acc.load_state(ckpts[-2], train_state=template).params["a"]))

    corrupt_checkpoint(ckpts[-1], mode=mode, seed=3)
    ok, problems = verify_checkpoint(ckpts[-1])
    assert not ok and problems

    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    with caplog.at_level("WARNING"):
        restored = acc.load_state(train_state=template)  # auto path: no crash
    assert any("failed verification" in r.message for r in caplog.records)
    assert float(np.asarray(restored.params["a"])) == good_state_a


def test_corrupt_explicit_dir_raises(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    ckpt = acc.save_state(train_state=state)
    corrupt_checkpoint(ckpt, mode="truncate", seed=0)
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    with pytest.raises(CheckpointCorruptError):
        acc.load_state(ckpt, train_state=template)


def test_all_checkpoints_corrupt_raises_loudly(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    for _ in range(2):
        acc.save_state(train_state=state)
    for c in list_checkpoints(str(tmp_path)):
        corrupt_checkpoint(c, mode="truncate", seed=1)
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    with pytest.raises(CheckpointCorruptError, match="no valid checkpoint"):
        acc.load_state(train_state=template)


def test_verify_checkpoint_contract(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    ckpt = Path(acc.save_state(train_state=state))
    ok, problems = verify_checkpoint(ckpt)
    assert ok and not problems
    # legacy dir (no manifest): valid-but-unverified, with a note
    manifest = ckpt / "checkpoint_manifest.json"
    manifest.unlink()
    ok, problems = verify_checkpoint(ckpt)
    assert ok and "no manifest" in problems[0]
    write_checkpoint_manifest(ckpt)
    # a deleted payload file is a hard failure
    victim = next(p for p in sorted((ckpt / "train_state").rglob("*")) if p.is_file())
    victim.unlink()
    ok, problems = verify_checkpoint(ckpt)
    assert not ok and any("missing file" in p for p in problems)
    # so are .tmp staging dirs and absent paths
    assert verify_checkpoint(str(ckpt) + ".tmp")[0] is False
    assert verify_checkpoint(tmp_path / "nope")[0] is False


def test_legacy_torn_checkpoint_falls_back_without_manifest(tmp_path):
    """A pre-resilience (manifest-less) torn checkpoint passes verification
    as 'unverified' but fails to restore — the auto-resume scan must walk on
    to the previous candidate instead of crashing (the FileNotFoundError a
    missing shard raises is a restore failure like any other here)."""
    acc, dl, state, step = _setup(tmp_path)
    state, _ = step(state, next(iter(dl)))
    acc.save_state(train_state=state)
    a_valid = float(np.asarray(state.params["a"]))
    acc.save_state(train_state=state)
    ckpts = [Path(c) for c in list_checkpoints(str(tmp_path))]
    for c in ckpts:  # both legacy: no manifests to verify against
        (c / "checkpoint_manifest.json").unlink()
    # tear the newest: its train_state payload disappears entirely
    import shutil
    shutil.rmtree(ckpts[-1] / "train_state")

    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    restored = acc.load_state(train_state=template)  # auto path: no crash
    assert float(np.asarray(restored.params["a"])) == a_valid


def test_preemption_exit_code_survives_failed_emergency_save(tmp_path):
    """An I/O failure during the emergency save (retry budget exhausted)
    must not turn the preemption into a crash code: the supervisor contract
    is 're-queue on 75', and older checkpoints still exist to resume from."""
    plugin = ResiliencePlugin(handle_preemption=True, nan_guard=False,
                              io_retries=1, io_backoff_s=0.001)
    acc, dl, state, step = _setup(tmp_path, plugin=plugin)
    batch = next(iter(dl))
    state, _ = step(state, batch)
    acc._preemption.request()
    # every checkpoint-I/O attempt fails — past the bounded budget
    with fault_plan(FaultPlan([FaultEvent("transfer", at=1, count=10,
                                          site="checkpoint_io")])):
        with pytest.raises(SystemExit) as exc:
            step(state, batch)
    assert exc.value.code == RESUME_EXIT_CODE


def test_fault_plan_injected_corruption_via_post_save_hook(tmp_path):
    """corrupt_ckpt events fire through the real save path (post-publish)."""
    acc, dl, state, step = _setup(tmp_path)
    with fault_plan(FaultPlan([FaultEvent("corrupt_ckpt", at=1, mode="bitflip")])):
        ckpt = acc.save_state(train_state=state)
    ok, problems = verify_checkpoint(ckpt)
    assert not ok and any("checksum mismatch" in p for p in problems)


# ---------------------------------------------------------------------------
# (c) NaN guard
# ---------------------------------------------------------------------------


def _guard_setup(tmp_path, max_consecutive=3):
    plugin = ResiliencePlugin(
        nan_guard=True, max_consecutive_nan_skips=max_consecutive,
        handle_preemption=False,
    )
    return _setup(tmp_path, plugin=plugin)


def test_nan_guard_skips_step_params_bitwise_unchanged(tmp_path):
    acc, dl, state, step = _guard_setup(tmp_path)
    batch = next(iter(dl))
    with fault_plan(FaultPlan([FaultEvent("nan_grad", at=2)])):
        state, m = step(state, batch)
        assert bool(m["nan_skipped"]) is False
        params_before = {k: _bytes_of(v) for k, v in state.params.items()}
        mu_before = _bytes_of(state.opt_state[0].mu["a"])
        state, m = step(state, batch)
        # skipped: counters advance, state held bitwise
        assert bool(m["nan_skipped"]) is True
        assert int(m["nan_skips"]) == 1
        assert int(m["consecutive_nan_skips"]) == 1
        for k, v in params_before.items():
            assert _bytes_of(state.params[k]) == v, f"params[{k}] changed on a skipped step"
        assert _bytes_of(state.opt_state[0].mu["a"]) == mu_before
        # next clean step resets the consecutive counter and trains on
        state, m = step(state, batch)
        assert bool(m["nan_skipped"]) is False
        assert int(m["consecutive_nan_skips"]) == 0
        assert int(m["nan_skips"]) == 1
        assert np.isfinite(float(m["loss"]))
    assert acc.goodput.nan_skips == 1


def test_nan_guard_aborts_after_consecutive_skips(tmp_path):
    acc, dl, state, step = _guard_setup(tmp_path, max_consecutive=2)
    batch = next(iter(dl))
    with fault_plan(FaultPlan([FaultEvent("nan_grad", at=1, count=3)])):
        state, m = step(state, batch)
        assert int(m["consecutive_nan_skips"]) == 1
        with pytest.raises(NanGuardAbort, match="2 consecutive"):
            step(state, batch)


def test_nan_guard_counts_skips_with_abort_disabled(tmp_path):
    """max_consecutive_nan_skips=0 disables only the abort: skips still land
    in the goodput counters ``GoodputTracker.report`` always carries."""
    acc, dl, state, step = _guard_setup(tmp_path, max_consecutive=0)
    batch = next(iter(dl))
    with fault_plan(FaultPlan([FaultEvent("nan_grad", at=1, count=2)])):
        for _ in range(3):
            state, m = step(state, batch)  # never aborts
    assert acc.goodput.nan_skips == 2
    assert int(m["nan_skips"]) == 2


def test_nan_guard_counters_survive_checkpoint_resume(tmp_path):
    acc, dl, state, step = _guard_setup(tmp_path)
    batch = next(iter(dl))
    with fault_plan(FaultPlan([FaultEvent("nan_grad", at=1)])):
        state, m = step(state, batch)
    assert int(m["nan_skips"]) == 1
    ckpt = acc.save_state(train_state=state)
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    restored = acc.load_state(ckpt, train_state=template)
    assert int(restored.guard_state["nan_skips"]) == 1


def test_nan_guard_off_keeps_state_shape(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    assert state.guard_state is None
    batch = next(iter(dl))
    state, m = step(state, batch)
    assert "nan_skipped" not in m


# ---------------------------------------------------------------------------
# (d) transient transfer failures → bounded retry, identical results
# ---------------------------------------------------------------------------


def test_layer_prefetcher_retries_transient_failures():
    from accelerate_tpu.ops.streaming import LayerPrefetcher, StreamStats

    layers = [{"w": jnp.full((4, 4), i, jnp.float32)} for i in range(4)]
    calls = []

    def fetch(i):
        calls.append(i)
        return layers[i]

    def run(plan):
        stats = StreamStats()
        pf = LayerPrefetcher(fetch, len(layers), depth=1, stats=stats,
                             retry_policy=RetryPolicy(retries=3, backoff_s=0.001))
        with fault_plan(plan):
            out = [np.asarray(pf.get(i)["w"]).copy() for i in range(len(layers))]
        return out, stats

    clean, _ = run(None)
    # two consecutive injected failures at the 2nd transfer attempt: within
    # the bounded budget, absorbed, decode identical
    faulted, stats = run(FaultPlan([FaultEvent("transfer", at=2, count=2)]))
    for a, b in zip(clean, faulted):
        np.testing.assert_array_equal(a, b)
    assert stats.transfer_retries == 2
    assert stats.overlap_report()["transfer_retries"] == 2


def test_layer_prefetcher_exhausted_budget_raises():
    from accelerate_tpu.ops.streaming import LayerPrefetcher

    pf = LayerPrefetcher(lambda i: {"w": jnp.zeros(2)}, 2,
                         retry_policy=RetryPolicy(retries=1, backoff_s=0.001))
    with fault_plan(FaultPlan([FaultEvent("transfer", at=1, count=5)])):
        with pytest.raises(InjectedTransferError):
            pf.get(0)


def test_dataloader_h2d_retry_identical_stream(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    clean = [np.asarray(b["x"]).copy() for b in dl]
    with fault_plan(FaultPlan([FaultEvent("transfer", at=2, count=2)])):
        faulted = [np.asarray(b["x"]).copy() for b in dl]
    assert len(clean) == len(faulted)
    for a, b in zip(clean, faulted):
        np.testing.assert_array_equal(a, b)
    # retries flowed into the goodput counters (the loaders carry the
    # accelerator's ResiliencePlugin budget + hook)
    assert acc.goodput.transfer_retries == 2


def test_dataloader_h2d_retry_training_identical(tmp_path):
    """The full (d) criterion: training through an injected transient H2D
    failure must produce the same result as the no-fault run."""
    acc, dl, state, step = _setup(tmp_path)
    for batch in dl:
        state, _ = step(state, batch)
    clean_a = _bytes_of(state.params["a"])

    from accelerate_tpu.state import AcceleratorState, GradientState
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc2, dl2, state2, step2 = _setup(tmp_path / "f")
    with fault_plan(FaultPlan([FaultEvent("transfer", at=3)])):
        for batch in dl2:
            state2, _ = step2(state2, batch)
    assert _bytes_of(state2.params["a"]) == clean_a


def test_checkpoint_io_retry_and_goodput_counter(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    plan = FaultPlan([FaultEvent("transfer", at=1, count=2, site="checkpoint_io")])
    with fault_plan(plan):
        ckpt = acc.save_state(train_state=state)
    assert verify_checkpoint(ckpt)[0]
    assert acc.goodput.io_retries == 2
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    with fault_plan(FaultPlan([FaultEvent("transfer", at=1, site="checkpoint_io")])):
        restored = acc.load_state(ckpt, train_state=template)
    assert float(np.asarray(restored.params["a"])) == float(np.asarray(state.params["a"]))


def test_retry_budget_is_bounded_and_fatal_errors_skip_it():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise InjectedTransferError("always down")

    with pytest.raises(InjectedTransferError):
        with_retries(flaky, policy=RetryPolicy(retries=2, backoff_s=0.001))
    assert calls["n"] == 3  # 1 try + 2 bounded re-attempts, never infinite

    calls["n"] = 0

    def missing():
        calls["n"] += 1
        raise FileNotFoundError("gone")

    with pytest.raises(FileNotFoundError):
        with_retries(missing, policy=RetryPolicy(retries=5, backoff_s=0.001))
    assert calls["n"] == 1  # fatal: retrying cannot change the answer


# ---------------------------------------------------------------------------
# satellite: async-save orphan flush at interpreter exit
# ---------------------------------------------------------------------------


_ORPHAN_SCRIPT = textwrap.dedent('''
    """async save, then exit WITHOUT end_training/wait: the interpreter-exit
    flush must drain the write AND publish the atomic rename."""
    import sys
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.test_utils.training import regression_init_params
    from accelerate_tpu.utils.dataclasses import ProjectConfiguration

    acc = Accelerator(project_config=ProjectConfiguration(
        project_dir=sys.argv[1], automatic_checkpoint_naming=True))
    state = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    acc.save_state(train_state=state, async_save=True)
    # fall off the end: no end_training(), no wait_for_checkpoint()
''')


def test_interpreter_exit_never_orphans_async_save(tmp_path):
    _run_subprocess(_ORPHAN_SCRIPT, [tmp_path])
    base = tmp_path / "checkpoints"
    tmps = list(base.glob("*.tmp"))
    assert not tmps, f"half-written staging dirs left behind: {tmps}"
    ckpts = list_checkpoints(str(tmp_path))
    assert len(ckpts) == 1
    ok, problems = verify_checkpoint(ckpts[0])
    assert ok, problems


# ---------------------------------------------------------------------------
# satellite: mid-epoch dataloader resume — bit parity with the clean run
# ---------------------------------------------------------------------------


def _torch_loader(n=32, bs=4):
    import torch
    import torch.utils.data as tud

    class DS(tud.Dataset):
        def __len__(self):
            return n

        def __getitem__(self, i):
            return {"x": torch.arange(i * 8, (i + 1) * 8, dtype=torch.float32)}

    return tud.DataLoader(DS(), batch_size=bs, shuffle=False)


def test_shard_loader_mid_epoch_resume_bit_parity(tmp_path):
    """data_loader.py DataLoaderShard.load_state_dict: batches after a
    resume-at-batch-k must bit-match the uninterrupted run — across the
    epoch boundary too."""
    from accelerate_tpu.data_loader import prepare_data_loader

    ref_dl = prepare_data_loader(_torch_loader())
    reference = [np.asarray(b["x"]).copy() for b in ref_dl]      # epoch 0
    reference += [np.asarray(b["x"]).copy() for b in ref_dl]     # epoch 1

    live = prepare_data_loader(_torch_loader())
    it = iter(live)
    for _ in range(3):
        next(it)
    sd = live.state_dict()
    assert sd == {"batches_yielded": 3, "iteration": 0}

    resumed = prepare_data_loader(_torch_loader())
    resumed.load_state_dict(sd)
    stream = [np.asarray(b["x"]).copy() for b in resumed]        # rest of epoch 0
    stream += [np.asarray(b["x"]).copy() for b in resumed]       # full epoch 1
    assert len(stream) == len(reference) - 3
    for got, want in zip(stream, reference[3:]):
        np.testing.assert_array_equal(got, want)


def test_dispatcher_mid_epoch_resume_bit_parity():
    """Same contract through DataLoaderDispatcher.load_state_dict."""
    from accelerate_tpu.data_loader import DataLoaderDispatcher

    reference = [np.asarray(b["x"]).copy() for b in DataLoaderDispatcher(_torch_loader())]

    live = DataLoaderDispatcher(_torch_loader())
    it = iter(live)
    for _ in range(5):
        next(it)
    sd = live.state_dict()
    assert sd["batches_yielded"] == 5

    resumed = DataLoaderDispatcher(_torch_loader())
    resumed.load_state_dict(sd)
    stream = [np.asarray(b["x"]).copy() for b in resumed]
    assert len(stream) == len(reference) - 5
    for got, want in zip(stream, reference[5:]):
        np.testing.assert_array_equal(got, want)


def test_mid_epoch_resume_through_checkpoint_bit_parity(tmp_path):
    """End-to-end through save_state/load_state: the restored loader's
    remaining batches bit-match the uninterrupted stream (the
    data_loader.load_state_dict path driven by the checkpoint files)."""
    acc, dl, state, step = _setup(tmp_path)
    reference = [np.asarray(b["x"]).copy() for b in dl]

    from accelerate_tpu.state import AcceleratorState, GradientState
    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc2, dl2, state2, step2 = _setup(tmp_path)
    it = iter(dl2)
    next(it)
    next(it)
    ckpt = acc2.save_state(train_state=state2)

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc3, dl3, state3, step3 = _setup(tmp_path)
    acc3.load_state(ckpt)
    remaining = [np.asarray(b["x"]).copy() for b in dl3]
    assert len(remaining) == len(reference) - 2
    for got, want in zip(remaining, reference[2:]):
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# machinery: fault plans, goodput, handler hygiene
# ---------------------------------------------------------------------------


def test_fault_plan_determinism_and_occurrence_semantics():
    plan_a = FaultPlan.from_seed(7, 50, p_preempt=0.05, p_nan=0.1, p_transfer=0.1)
    plan_b = FaultPlan.from_seed(7, 50, p_preempt=0.05, p_nan=0.1, p_transfer=0.1)
    assert plan_a.events == plan_b.events
    assert plan_a.events != FaultPlan.from_seed(8, 50, p_nan=0.1).events

    plan = FaultPlan([FaultEvent("nan_grad", at=2, count=2)])
    assert plan.fire("step") == ()
    assert [e.kind for e in plan.fire("step")] == ["nan_grad"]
    assert [e.kind for e in plan.fire("step")] == ["nan_grad"]
    assert plan.fire("step") == ()
    assert len(plan.fired) == 2

    spec = plan.to_spec()
    assert FaultPlan.from_spec(spec).events == plan.events

    with pytest.raises(ValueError):
        FaultEvent("meteor", at=1)
    with pytest.raises(ValueError):
        FaultEvent("corrupt_ckpt", mode="melt")


def test_goodput_tracker_and_predicted_model():
    t = GoodputTracker()
    assert t.report()["goodput_frac"] == 1.0
    for _ in range(10):
        t.record_step()
    t.record_nan_skip()
    t.record_restart(steps_recomputed=1)
    rep = t.report()
    assert rep["steps"] == 10 and rep["nan_skips"] == 1 and rep["restarts"] == 1
    assert rep["goodput_frac"] == pytest.approx(0.8, abs=0.01)

    pred = goodput_accounting(1.0, 100, save_overhead_s=2.0,
                              preemption_rate_per_hour=1.0)
    assert pred["kind"] == "predicted"
    assert 0.0 < pred["goodput_frac"] < 1.0
    # more frequent checkpoints under heavy preemption → better goodput
    heavy = dict(save_overhead_s=0.5, preemption_rate_per_hour=20.0)
    assert (goodput_accounting(1.0, 20, **heavy)["goodput_frac"]
            > goodput_accounting(1.0, 500, **heavy)["goodput_frac"])


def test_resilience_plugin_env_defaults(monkeypatch):
    plugin = ResiliencePlugin()
    assert plugin.nan_guard is False and plugin.handle_preemption is False
    monkeypatch.setenv("ACCELERATE_RESILIENCE", "1")
    armed = ResiliencePlugin()
    assert armed.nan_guard is True and armed.handle_preemption is True
    monkeypatch.setenv("ACCELERATE_NAN_GUARD", "0")
    mixed = ResiliencePlugin()
    assert mixed.nan_guard is False and mixed.handle_preemption is True
    with pytest.raises(ValueError):
        ResiliencePlugin(max_consecutive_nan_skips=-1)


@pytest.mark.parametrize("mode", CORRUPTION_MODES)
def test_retention_gc_vs_fallback_scan(tmp_path, mode):
    """Satellite: rank-0 GC must never delete the checkpoint a fallback
    load_state scan could still select — with the latest corrupt (every
    CORRUPTION_MODES entry), the previous valid one survives retention and
    the resume lands on it."""
    acc, dl, state, step = _setup(tmp_path, total_limit=2)
    it = iter(dl)
    state, _ = step(state, next(it))
    acc.save_state(train_state=state)          # checkpoint_0 (valid)
    a_valid = float(np.asarray(state.params["a"]))
    state, _ = step(state, next(it))
    acc.save_state(train_state=state)          # checkpoint_1
    ckpts = list_checkpoints(str(tmp_path))
    corrupt_checkpoint(ckpts[-1], mode=mode, seed=0)  # newest now corrupt

    # next save triggers GC at total_limit=2: the naive victim is
    # checkpoint_0 — but it is the only valid fallback candidate
    state, _ = step(state, next(it))
    acc.save_state(train_state=state)          # checkpoint_2
    survivors = [os.path.basename(c) for c in list_checkpoints(str(tmp_path))]
    assert "checkpoint_0" in survivors, "GC deleted the only valid fallback"

    # and once a newer valid checkpoint exists, the spared one is collectable
    state, _ = step(state, next(it))
    acc.save_state(train_state=state)          # checkpoint_3 (valid)
    survivors = [os.path.basename(c) for c in list_checkpoints(str(tmp_path))]
    assert "checkpoint_0" not in survivors
    assert "checkpoint_3" in survivors


@pytest.mark.parametrize("mode", CORRUPTION_MODES)
def test_presumed_valid_for_gc_every_corruption_mode(tmp_path, mode):
    """GC's validity oracle agrees with the full verify for every
    corruption flavor: valid → True (and stat-snapshot refreshed), then
    corrupted in place → the stat drift forces the crc re-verify → False."""
    from accelerate_tpu.checkpointing import _presumed_valid_for_gc

    acc, dl, state, step = _setup(tmp_path)
    ckpt = Path(acc.save_state(train_state=state))
    assert _presumed_valid_for_gc(ckpt) is True
    corrupt_checkpoint(ckpt, mode=mode, seed=2)
    assert verify_checkpoint(ckpt)[0] is False
    assert _presumed_valid_for_gc(ckpt) is False
    # still False on re-ask: a failed verify must not poison the snapshot
    # cache into presuming the corrupt dir valid next round
    assert _presumed_valid_for_gc(ckpt) is False


# ---------------------------------------------------------------------------
# peer-redundant hot checkpoints + the recovery ladder (single process; the
# cross-rank legs live in tests/test_train_fabric.py, slow tier)
# ---------------------------------------------------------------------------


def test_new_fault_kinds_registered():
    for kind in ("rank_loss", "straggler", "partial_ckpt"):
        assert kind in FAULT_KINDS
    assert KIND_DEFAULT_SITE["rank_loss"] == "step"
    assert KIND_DEFAULT_SITE["straggler"] == "step"
    assert KIND_DEFAULT_SITE["partial_ckpt"] == "peer_snapshot"
    # the default-site table covers every kind — a new kind without a site
    # would silently never fire
    assert set(KIND_DEFAULT_SITE) == set(FAULT_KINDS)
    assert issubclass(RankLostError, RuntimeError)


def test_goodput_state_dict_roundtrip():
    t = GoodputTracker()
    for _ in range(5):
        t.record_step()
    t.record_nan_skip(2)
    t.record_restart(steps_recomputed=3, time_lost_s=1.5)
    t.record_preemption()
    sd = t.state_dict()
    assert sd["steps"] == 5 and sd["preemptions"] == 1
    assert "started_at" not in sd  # per-incarnation on purpose

    fresh = GoodputTracker()
    fresh.load_state_dict(sd)
    assert fresh.state_dict() == sd
    # partial dicts (older checkpoints) load what they have, keep the rest
    partial = GoodputTracker()
    partial.load_state_dict({"steps": 7})
    assert partial.steps == 7 and partial.restarts == 0


def test_goodput_counters_persist_through_save_load(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    acc.goodput.record_nan_skip(3)
    acc.goodput.record_restart(steps_recomputed=2)
    ckpt = acc.save_state(train_state=state)

    acc.goodput.load_state_dict({k: 0 for k in acc.goodput.state_dict()})
    template = acc.create_train_state(regression_init_params(), optax.adam(0.05))
    acc.load_state(ckpt, train_state=template)
    assert acc.goodput.nan_skips == 3
    assert acc.goodput.restarts == 1
    assert acc.goodput.steps_recomputed == 2


def test_host_snapshot_roundtrip_and_schema_gate(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    state, _ = step(state, next(iter(dl)))
    snap = capture_host_snapshot(state, step=1)
    assert snap.verify()
    assert snap.nbytes == snapshot_schema(state)["snapshot_bytes"]
    # the accounting model predicts exactly what capture measures
    assert peer_ckpt_accounting(state)["snapshot_bytes"] == snap.nbytes

    restored = restore_host_snapshot(snap, state)
    assert _bytes_of(restored.params["a"]) == _bytes_of(state.params["a"])
    assert _bytes_of(jax.random.key_data(restored.rng)) == _bytes_of(
        jax.random.key_data(state.rng))

    other = acc.create_train_state({"a": jnp.zeros((3,))}, optax.sgd(0.1))
    with pytest.raises(PeerSchemaError):
        check_snapshot_schemas(snapshot_schema(state), snapshot_schema(other))


def test_peer_snapshotter_crc_gate_and_recover_single_process(tmp_path):
    acc, dl, state, step = _setup(tmp_path)
    snapper = PeerSnapshotter(state, 1)
    state, _ = step(state, next(iter(dl)))
    snapper.maybe_snapshot(state, 1)
    assert snapper.newest_restorable_step() == 1
    # the prepared step donates its input: read wave-1's expectation NOW,
    # before state's buffers are reused in place by the next step
    want = _bytes_of(state.params["a"])

    # torn wave: the injected partial_ckpt flips a stored byte — verify()
    # catches it and recover() skips the wave (at=1: the occurrence counter
    # is per-plan, and this plan sees only the second snapshot)
    install_fault_plan(FaultPlan([FaultEvent("partial_ckpt", at=1)]))
    state2, _ = step(state, next(iter(dl)))
    snapper.maybe_snapshot(state2, 2)
    got, agreed = snapper.recover(state2)
    assert agreed == 1  # wave 2 dropped by the crc gate
    assert _bytes_of(got.params["a"]) == want


def test_accelerator_recover_ladder_single_process(tmp_path):
    """The three rungs in order: peer RAM (newest, fewest steps replayed),
    verified disk, fresh start — with the report the bench surface emits."""
    plugin = ResiliencePlugin(peer_snapshot_every=2)
    acc, dl, state, step = _setup(tmp_path, plugin=plugin)
    it = iter(dl)
    for i in range(3):
        state, _ = step(state, next(it))
        if acc.step_count == 1:
            acc.save_state(train_state=state)        # disk @ step 1
    assert acc.peer_snapshotter.newest_restorable_step() == 2

    restored, report = acc.recover(train_state=state, load_sampler_states=False)
    assert report["restore_path"] == "peer"
    assert report["restored_step"] == 2 and acc.step_count == 2
    assert report["peer_snapshot_bytes"] > 0

    acc.peer_snapshotter.forget_local()              # rank-local RAM gone
    restored, report = acc.recover(train_state=state, load_sampler_states=False)
    assert report["restore_path"] == "disk"
    assert report["restored_step"] == 1 and acc.step_count == 1
    assert report["steps_recomputed"] == 1           # step 2 replayed

    acc.peer_snapshotter.reset()
    import shutil
    shutil.rmtree(Path(tmp_path) / "checkpoints")
    restored, report = acc.recover(train_state=state, load_sampler_states=False)
    assert report["restore_path"] == "fresh"
    assert restored is None and acc.step_count == 0
    # peer rung counted a restart; the disk rung RESTORED the persisted
    # counters (saved with restarts=0) before counting its own; fresh adds 1
    assert acc.goodput.restarts == 2
