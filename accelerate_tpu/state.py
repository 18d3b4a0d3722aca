"""Process/device state singletons — the L2 kernel of the framework.

TPU-native re-design of reference ``state.py`` (1,365 LoC):

- :class:`PartialState` (reference :122) — borg singleton holding process
  rank/world/devices; initializes the collective runtime.  On JAX the
  collective runtime is ``jax.distributed.initialize`` (one process per host)
  instead of ``torch.distributed.init_process_group`` (reference :243), and
  the "backend zoo" (reference ``_prepare_backend`` :753) collapses to the
  XLA platform probe.
- :class:`AcceleratorState` (reference :863) — layers mixed-precision and
  parallelism/mesh resolution on top.
- :class:`GradientState` (reference :1225) — gradient-accumulation bookkeeping
  shared by dataloader/optimizer/scheduler wrappers.

Process-control helpers (``main_process_first``, ``split_between_processes``,
``wait_for_everyone`` — reference :376-560) are preserved with identical
semantics; barriers use ``multihost_utils.sync_global_devices``.
"""

from __future__ import annotations

import logging
import math
import os
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, Optional

import jax
import numpy as np

from .parallelism_config import ParallelismConfig
from .utils.constants import FP8_REFUSED
from .utils.dataclasses import (
    DistributedType,
    GradientAccumulationPlugin,
    InitProcessGroupKwargs,
    MixedPrecisionType,
)
from .utils.environment import parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)

_jax_distributed_initialized = False


def _maybe_init_jax_distributed(kwargs: Optional[InitProcessGroupKwargs]) -> None:
    """Bring up the multi-host collective runtime exactly once.

    Analog of ``torch.distributed.init_process_group`` (reference state.py:243).
    A coordinator address in env/kwargs signals a multi-host launch; otherwise
    JAX's single-process world is already live.
    """
    global _jax_distributed_initialized
    if _jax_distributed_initialized:
        return
    # NOTE: do NOT touch jax.process_count()/jax.devices() here — any backend
    # query initializes JAX and makes jax.distributed.initialize impossible.
    coordinator = None
    num_processes = process_id = None
    if kwargs is not None and kwargs.coordinator_address:
        coordinator = kwargs.coordinator_address
        num_processes = kwargs.num_processes
        process_id = kwargs.process_id
    elif os.environ.get("ACCELERATE_COORDINATOR_ADDRESS"):
        coordinator = os.environ["ACCELERATE_COORDINATOR_ADDRESS"]
        num_processes = int(os.environ.get("ACCELERATE_NUM_PROCESSES", "0")) or None
        process_id = int(os.environ.get("ACCELERATE_PROCESS_ID", "-1"))
        process_id = None if process_id < 0 else process_id
    if coordinator is None:
        return
    # CPU gangs need an explicit collectives backend: without it the CPU
    # backend REJECTS any cross-process computation ("Multiprocess
    # computations aren't implemented on the CPU backend"), which silently
    # reduced every `launch --cpu` gang to collectives-free scripts.  Gloo
    # ships in jaxlib; set it BEFORE initialize (it is read at client
    # construction).  ACCELERATE_CPU_COLLECTIVES overrides ("none" opts
    # out); harmless on TPU, where the TPU backend owns the collectives.
    impl = os.environ.get("ACCELERATE_CPU_COLLECTIVES", "gloo")
    if impl and impl != "none":
        try:
            jax.config.update("jax_cpu_collectives_implementation", impl)
        except (AttributeError, ValueError):  # jax without the knob/impl
            pass
    init_kwargs: dict[str, Any] = {"coordinator_address": coordinator}
    if num_processes is not None:
        init_kwargs["num_processes"] = num_processes
    if process_id is not None:
        init_kwargs["process_id"] = process_id
    if kwargs is not None:
        timeout = kwargs.initialization_timeout
        if timeout is None and kwargs.timeout is not None:
            timeout = int(kwargs.timeout.total_seconds())
        if timeout:
            init_kwargs["initialization_timeout"] = timeout
    jax.distributed.initialize(**init_kwargs)
    _jax_distributed_initialized = True


class PartialState:
    """Singleton with information about the current process/device world.

    reference state.py:122 — same borg pattern (``_shared_state``), same public
    attribute names (``process_index``, ``num_processes``, ``device``,
    ``distributed_type``, ``debug``), same process-control context managers.
    """

    _shared_state: dict = {}

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        init_pg_kwargs = kwargs.pop("init_process_group_kwargs", None)
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        if parse_flag_from_env("ACCELERATE_CPU_AFFINITY"):
            # opt-in (reference state.py:314).  MUST run before the first
            # backend touch: XLA's thread pools inherit the calling thread's
            # mask only at spawn, so the rank/world come from the launcher's
            # env, not from jax.  Only co-located ranks partition — on a real
            # pod (TPU_WORKER_ID set, one process per host) every host owns
            # all of its cores and there is nothing to split.
            from .utils.environment import get_int_from_env, set_cpu_affinity

            _n_local = get_int_from_env(["ACCELERATE_NUM_PROCESSES"], 1)
            _on_pod = os.environ.get("TPU_WORKER_ID") or os.environ.get("CLOUD_TPU_TASK_ID")
            if _n_local > 1 and not _on_pod:
                set_cpu_affinity(
                    get_int_from_env(["ACCELERATE_PROCESS_ID"], 0),
                    total_local_processes=_n_local,
                )
        if cpu or parse_flag_from_env("ACCELERATE_USE_CPU"):
            jax.config.update("jax_platforms", "cpu")
        _maybe_init_jax_distributed(init_pg_kwargs)

        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.num_devices = len(self.devices)
        self.num_local_devices = len(self.local_devices)
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.local_process_index = self.process_index  # one process per host
        self.device = self.local_devices[0]
        self.platform = self.device.platform

        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif self.num_devices > 1:
            self.distributed_type = DistributedType.MULTI_DEVICE
        else:
            self.distributed_type = DistributedType.NO
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED", False)

    def __repr__(self):
        return (
            f"Distributed environment: {self.distributed_type}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local process index: {self.local_process_index}\n"
            f"Num devices: {self.num_devices} ({self.platform})\n"
            f"Device: {self.device}\n"
        )

    @property
    def initialized(self) -> bool:
        return "distributed_type" in self.__dict__

    @staticmethod
    def _reset_state():
        """Reset borg state — test hygiene (reference state.py:855)."""
        PartialState._shared_state.clear()

    @property
    def use_distributed(self) -> bool:
        return self.distributed_type != DistributedType.NO

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # -- barriers & ordering (reference state.py:376-560) -------------------

    def wait_for_everyone(self):
        """Cross-host barrier (reference :376).  No-op single-process."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self):
        """Main process runs the block first, others wait (reference :515)."""
        yield from self._goes_first(self.is_main_process)

    @contextmanager
    def local_main_process_first(self):
        yield from self._goes_first(self.is_local_main_process)

    def _goes_first(self, is_main: bool):
        if not is_main:
            self.wait_for_everyone()
        yield
        if is_main:
            self.wait_for_everyone()

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array evenly across processes
        (reference state.py:424-513 — same tail/padding semantics: uneven
        remainders go to the first processes; ``apply_padding`` repeats the
        last element so every process gets the same count)."""
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        if isinstance(inputs, dict):
            lengths = {len(v) for v in inputs.values()}
            if len(lengths) != 1:
                raise ValueError("All dict values must have the same length to split between processes")
            length = lengths.pop()

        num_samples_per_process = math.ceil(length / self.num_processes)
        start = self.process_index * num_samples_per_process
        end = start + num_samples_per_process

        def _split(obj):
            if isinstance(obj, (list, tuple, np.ndarray)) or hasattr(obj, "shape"):
                sliced = obj[start:end]
                if apply_padding and len(sliced) < num_samples_per_process and len(obj) > 0:
                    pad = [obj[-1]] * (num_samples_per_process - len(sliced))
                    if isinstance(obj, np.ndarray) or hasattr(obj, "shape"):
                        sliced = np.concatenate([np.asarray(sliced), np.stack(pad)], axis=0)
                    else:
                        sliced = list(sliced) + pad
                return sliced
            return obj

        if isinstance(inputs, dict):
            yield {k: _split(v) for k, v in inputs.items()}
        else:
            yield _split(inputs)

    # -- decorators (reference state.py:565-640) ----------------------------

    def on_main_process(self, function: Callable = None):
        if function is None:
            return partial(self.on_main_process)

        def _inner(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return _inner

    def on_local_main_process(self, function: Callable = None):
        if function is None:
            return partial(self.on_local_main_process)

        def _inner(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return _inner

    def on_last_process(self, function: Callable):
        def _inner(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return _inner

    def on_process(self, function: Callable = None, process_index: int = None):
        if function is None:
            return partial(self.on_process, process_index=process_index)

        def _inner(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return _inner

    def print(self, *args, **kwargs):
        """Print once per node-0 (reference state.py:644)."""
        if self.is_main_process:
            print(*args, **kwargs)

    def destroy_process_group(self):
        """Tear down the multi-host runtime (reference state.py:700-715).

        Barriers first: without it the first process to exit kills the
        coordination service while peers still heartbeat, turning a clean run
        into a fatal "Socket closed" on the laggards."""
        global _jax_distributed_initialized
        if _jax_distributed_initialized:
            self.wait_for_everyone()
            jax.distributed.shutdown()
            _jax_distributed_initialized = False


class AcceleratorState:
    """Adds precision + parallelism/mesh resolution on top of PartialState
    (reference state.py:863)."""

    _shared_state: dict = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        parallelism_config: Optional[ParallelismConfig] = None,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if parallelism_config is not None and self.parallelism_config != parallelism_config:
                raise ValueError(
                    "AcceleratorState already initialized with a different parallelism_config; "
                    "call AcceleratorState._reset_state() first (test hygiene, reference testing.py:650)."
                )
            return
        # Everything below may raise (bad mixed_precision, invalid mesh
        # config).  ``initialized`` is true as soon as ``_partial`` lands, so
        # a failed construction must roll the borg dicts back — otherwise the
        # next (corrected) AcceleratorState returns the poisoned state early
        # or rejects it as "already initialized with a different
        # parallelism_config".  PartialState rolls back only if THIS call
        # created it (a pre-existing one is the user's, and valid).
        partial_preexisting = bool(PartialState._shared_state)
        try:
            self._init_validated(mixed_precision, cpu, parallelism_config, kwargs)
        except Exception:
            self._shared_state.clear()
            if not partial_preexisting:
                PartialState._reset_state()
            raise

    def _init_validated(self, mixed_precision, cpu, parallelism_config, kwargs):
        self._partial = PartialState(cpu=cpu, **kwargs)
        mixed_precision = (
            parse_choice_from_env("ACCELERATE_MIXED_PRECISION", "no")
            if mixed_precision is None
            else mixed_precision.lower()
        )
        if mixed_precision not in MixedPrecisionType:
            no_fp8 = f" ({FP8_REFUSED})" if mixed_precision == "fp8" else ""
            raise ValueError(
                f"mixed_precision must be one of {MixedPrecisionType.list()}, "
                f"got {mixed_precision!r}{no_fp8}"
            )
        self.mixed_precision = mixed_precision
        if parallelism_config is None and os.environ.get("PARALLELISM_CONFIG_DP_SHARD_SIZE"):
            parallelism_config = ParallelismConfig.from_env()
        self.parallelism_config = parallelism_config
        self._mesh: Optional[jax.sharding.Mesh] = None
        if parallelism_config is not None:
            # surface mesh-shape errors at construction (same check the lazy
            # mesh build runs) so they hit the rollback above instead of
            # poisoning the singleton from inside the first .mesh access.
            # An explicit device subset (ParallelismConfig.devices) validates
            # against ITS size — sub-meshes are legal (dryrun legs, tests).
            parallelism_config._validate(
                len(parallelism_config.devices)
                if parallelism_config.devices is not None
                else self.num_devices
            )

    # Delegate the PartialState surface ------------------------------------

    def __getattr__(self, name):
        partial_state = self.__dict__.get("_partial")
        if partial_state is not None and hasattr(partial_state, name):
            return getattr(partial_state, name)
        raise AttributeError(f"AcceleratorState has no attribute {name!r}")

    @property
    def initialized(self) -> bool:
        return "_partial" in self.__dict__

    @staticmethod
    def _reset_state(reset_partial_state: bool = False):
        AcceleratorState._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()
        # ambient trace-time knobs owned by an Accelerator die with its
        # state: a stale ring-matmul override must not leak into the next
        # (possibly plugin-less) construction
        from .ops.collective_matmul import set_collective_matmul

        set_collective_matmul(None)

    @property
    def mesh(self) -> jax.sharding.Mesh:
        """The device mesh.  Built lazily; defaults to pure data-parallel over
        all devices when no parallelism_config was given."""
        if self._mesh is None:
            cfg = self.parallelism_config
            if cfg is None:
                cfg = ParallelismConfig(dp_shard_size=self.num_devices)
                self.parallelism_config = cfg
            self._mesh = cfg.build_device_mesh()
        return self._mesh

    @mesh.setter
    def mesh(self, value):
        self._mesh = value


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference state.py:1225).

    ``sync_gradients`` flips at accumulation boundaries; dataloader wrappers
    flip ``end_of_dataloader``/``remainder`` so ``gather_for_metrics`` can drop
    duplicate tail samples (reference accelerator.py:3040).  Under the
    TPU-native ``in_step`` accumulation mode this object only serves the
    *outer-loop* bookkeeping — the actual accumulation is a ``lax.scan`` inside
    the jitted step (see ``accelerator.py``).
    """

    _shared_state: dict = {}

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = {}
            self.plugin = GradientAccumulationPlugin()
            self._is_xla_gradients_synced = True
        if gradient_accumulation_plugin is not None:
            self.plugin = gradient_accumulation_plugin

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @property
    def num_steps(self) -> int:
        return self.plugin.num_steps

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin.adjust_scheduler

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin.sync_with_dataloader

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync_gradients: bool):
        self.sync_gradients = sync_gradients

    def _add_dataloader(self, dataloader):
        if not self.initialized:  # revived after a test-hygiene reset
            GradientState.__init__(self)
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader):
        if not self.initialized:  # reset happened while a loader was live
            return
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    @staticmethod
    def _reset_state():
        GradientState._shared_state.clear()

    def __repr__(self):
        return (
            f"Sync Gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation plugin: {self.plugin}\n"
        )


def is_initialized() -> bool:
    return AcceleratorState._shared_state != {}


def ambient_mesh() -> Optional[jax.sharding.Mesh]:
    """The live :class:`AcceleratorState`'s mesh — what trace-time code
    (ring collective matmuls, the per-shard Mosaic kernel wrap) partitions
    over — or ``None`` when no Accelerator has been built."""
    if not is_initialized():
        return None
    try:
        return AcceleratorState().mesh
    except Exception:  # pragma: no cover - half-built state
        return None


def free_mesh_axes():
    """``(mesh, rest, free)`` of the mesh that trace-time code partitions
    over: the context's when traced inside a ``shard_map`` (jax rejects any
    other there), else the Accelerator's.  ``rest`` is the set of axes not
    yet manual, ``free`` maps those of them wider than one device to their
    size.  No mesh: ``(None, set(), {})``."""
    ctx = jax.sharding.get_abstract_mesh()
    mesh = ambient_mesh() if ctx.empty else ctx
    if mesh is None:
        return None, set(), {}
    rest = set(mesh.axis_names) - set(mesh.manual_axes)
    free = {a: mesh.shape[a] for a in mesh.axis_names if a in rest and mesh.shape[a] > 1}
    return mesh, rest, free
