"""Tests for state singletons (mirror of reference tests/test_state_checkpointing
+ test_accelerator state behaviors)."""

import jax
import numpy as np
import pytest

from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
from accelerate_tpu.parallelism_config import ParallelismConfig
from accelerate_tpu.utils.dataclasses import DistributedType, GradientAccumulationPlugin


def test_partial_state_singleton():
    s1 = PartialState()
    s2 = PartialState()
    assert s1.__dict__ is s2.__dict__
    assert s1.num_devices == len(jax.devices())
    assert s1.num_processes == 1
    assert s1.is_main_process
    assert s1.distributed_type in (DistributedType.MULTI_DEVICE, DistributedType.NO)


def test_partial_state_reset():
    s = PartialState()
    assert s.initialized
    PartialState._reset_state()
    # borg dict is shared: clearing it de-initializes existing instances too
    assert not s.initialized
    s2 = PartialState()
    assert s2.initialized


def test_accelerator_state_mixed_precision():
    state = AcceleratorState(mixed_precision="bf16")
    assert state.mixed_precision == "bf16"
    # borg: second construction keeps first config
    state2 = AcceleratorState()
    assert state2.mixed_precision == "bf16"


def test_accelerator_state_invalid_precision():
    with pytest.raises(ValueError):
        AcceleratorState(mixed_precision="int3")


@pytest.mark.parametrize("by", ["argument", "environment"])
def test_fp8_is_refused_by_name_and_the_message_names_bf16(by, monkeypatch):
    """``mixed_precision="fp8"`` is not a precision of this package: the same
    ValueError an unknown one raises, with the one sentence that says why."""
    AcceleratorState._reset_state(reset_partial_state=True)
    if by == "environment":
        monkeypatch.setenv("ACCELERATE_MIXED_PRECISION", "fp8")
    with pytest.raises(ValueError, match="no fp8 matmul units; use 'bf16'"):
        AcceleratorState(mixed_precision="fp8" if by == "argument" else None)
    monkeypatch.delenv("ACCELERATE_MIXED_PRECISION", raising=False)
    assert AcceleratorState(mixed_precision="bf16").mixed_precision == "bf16"   # nothing poisoned


def test_accelerator_state_default_mesh():
    state = AcceleratorState()
    mesh = state.mesh
    assert mesh.devices.size == len(jax.devices())
    assert mesh.shape["dp_shard"] == len(jax.devices())


def test_state_delegation():
    state = AcceleratorState()
    assert state.num_processes == 1
    assert state.is_main_process
    assert state.device is jax.local_devices()[0]


def test_split_between_processes_single():
    s = PartialState()
    with s.split_between_processes([1, 2, 3]) as inputs:
        assert inputs == [1, 2, 3]


def test_main_process_first():
    s = PartialState()
    with s.main_process_first():
        pass  # single process: no deadlock, no-op barrier


def test_on_main_process_decorator():
    s = PartialState()
    calls = []

    @s.on_main_process
    def fn(x):
        calls.append(x)
        return x

    fn(5)
    assert calls == [5]


def test_gradient_state():
    gs = GradientState(GradientAccumulationPlugin(num_steps=4))
    assert gs.num_steps == 4
    assert gs.sync_gradients
    assert not gs.end_of_dataloader
    assert gs.remainder == -1
    gs2 = GradientState()
    assert gs2.num_steps == 4  # borg
    gs._set_sync_gradients(False)
    assert not gs2.sync_gradients


def test_gradient_accumulation_plugin_validation():
    with pytest.raises(ValueError):
        GradientAccumulationPlugin(num_steps=0)
    with pytest.raises(ValueError):
        GradientAccumulationPlugin(mode="bogus")


def test_failed_init_does_not_poison_singleton():
    """A construction that fails validation must roll the borg state back:
    the user's corrected retry gets a clean init, not 'already initialized
    with a different parallelism_config' (or a silently skipped
    mixed_precision check)."""
    AcceleratorState._reset_state(reset_partial_state=True)
    with pytest.raises(ValueError):
        AcceleratorState(parallelism_config=ParallelismConfig(cp_size=2, sp_size=2))
    with pytest.raises(ValueError, match="mixed_precision"):
        AcceleratorState(mixed_precision="fp4")
    # corrected retry succeeds with the requested config
    st = AcceleratorState(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
    assert st.mesh.shape["tp"] == 2
    AcceleratorState._reset_state(reset_partial_state=True)
