"""Launch environment construction — config crosses the process boundary
exclusively as ``ACCELERATE_*`` / ``PARALLELISM_CONFIG_*`` / ``FSDP_*`` env
vars, the reference's transport contract (utils/launch.py:99-423; SURVEY §3.1
"Config crosses the boundary only as env vars").

TPU-native differences from the reference:
- no torchrun/elastic layer — workers are plain processes; the collective
  runtime comes up inside the worker via ``jax.distributed.initialize``
  (state.py), keyed off ``ACCELERATE_COORDINATOR_ADDRESS`` /
  ``ACCELERATE_NUM_PROCESSES`` / ``ACCELERATE_PROCESS_ID``;
- TPU pod topology is auto-derived from the TPU metadata env
  (``TPU_WORKER_ID`` / ``TPU_WORKER_HOSTNAMES``) when present, mirroring
  reference ``prepare_tpu`` (utils/launch.py:586).
"""

from __future__ import annotations

import os
import sys
from typing import Optional

from .environment import get_free_port


def _script_cmd(args) -> list[str]:
    """Build the worker command line (reference
    ``prepare_simple_launcher_cmd_env`` utils/launch.py:99-150)."""
    cmd = []
    if not getattr(args, "no_python", False):
        cmd.append(sys.executable)
        if getattr(args, "module", False):
            cmd.append("-m")
    cmd.append(args.training_script)
    cmd.extend(args.training_script_args or [])
    return cmd


def config_env(config) -> dict[str, str]:
    """The framework env transport derived from ``config`` ALONE — no ambient
    environ mixed in (cloud manifests must not inherit the operator shell's
    ACCELERATE_* residue)."""
    env = {str(k): str(v) for k, v in (config.env or {}).items()}
    env["ACCELERATE_MIXED_PRECISION"] = str(config.mixed_precision)
    env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] = str(config.gradient_accumulation_steps)
    if config.use_cpu:
        env["ACCELERATE_USE_CPU"] = "true"
    if config.debug:
        env["ACCELERATE_DEBUG_MODE"] = "true"
    if config.use_fsdp:
        env["ACCELERATE_USE_FSDP"] = "true"
        env["FSDP_SHARDING_STRATEGY"] = config.fsdp_sharding_strategy
        env["FSDP_OFFLOAD_PARAMS"] = str(config.fsdp_offload_params).lower()
        env["FSDP_ACTIVATION_CHECKPOINTING"] = str(config.fsdp_activation_checkpointing).lower()
    # Parallelism axes — PARALLELISM_CONFIG_* transport
    # (reference parallelism_config.py:274-289 / utils/launch.py:397).
    from ..parallelism_config import AXIS_SIZE_FIELDS

    for field in AXIS_SIZE_FIELDS:
        env[f"PARALLELISM_CONFIG_{field.upper()}"] = str(getattr(config, field))
    return env


def _base_env(args, config) -> dict[str, str]:
    """Env vars common to every launch mode.  ``config`` is a
    :class:`~accelerate_tpu.commands.config.LaunchConfig` already merged with
    CLI flags (flag > file > default)."""
    env = os.environ.copy()
    # An uninstalled source checkout must stay importable in workers: the
    # child runs the user script by path (sys.path[0] = script dir), so the
    # package root rides PYTHONPATH (reference installs; we may not be).
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_root, env.get("PYTHONPATH")) if p
    )
    env.update(config_env(config))
    if config.use_cpu:
        # a chip belongs to one process: CPU workers must never reach for it
        # (the launching process, or a sibling, may hold it), not even before
        # their PartialState switches the platform
        env["JAX_PLATFORMS"] = "cpu"
    return env


def prepare_simple_launcher_cmd_env(args, config) -> tuple[list[str], dict[str, str]]:
    """Single-process launch (reference utils/launch.py:99)."""
    return _script_cmd(args), _base_env(args, config)


def prepare_multiprocess_env(args, config, process_id: int) -> dict[str, str]:
    """Env for worker ``process_id`` of a multi-process launch.

    The worker's ``PartialState`` reads the three ``ACCELERATE_*`` coordinator
    vars and calls ``jax.distributed.initialize`` (state.py:47) — the analog of
    torchrun's ``RANK``/``WORLD_SIZE``/``MASTER_ADDR`` contract
    (reference utils/launch.py:198 ``prepare_multi_gpu_env``).
    """
    env = _base_env(args, config)
    ip = config.main_process_ip or "127.0.0.1"
    port = config.main_process_port or get_free_port()
    config.main_process_port = port  # pin so every worker agrees
    env["ACCELERATE_COORDINATOR_ADDRESS"] = f"{ip}:{port}"
    env["ACCELERATE_NUM_PROCESSES"] = str(config.num_processes)
    env["ACCELERATE_PROCESS_ID"] = str(process_id)
    return env


def discover_slice_topology() -> Optional[dict[str, int]]:
    """Slice-level topology from the multi-slice runtime metadata env, if
    present: ``{"num_slices": N, "slice_id": i}``.

    On Cloud TPU multislice the MegaScale runtime exports
    ``MEGASCALE_NUM_SLICES`` / ``MEGASCALE_SLICE_ID`` on every host; a
    single-slice pod (or a laptop) has neither and returns ``None``.  The
    launcher uses this to auto-fill ``ParallelismConfig.dcn_size`` — the
    explicit cross-slice mesh axis the hierarchical gradient-sync path keys
    off — when the operator left it unspecified."""
    num = os.environ.get("MEGASCALE_NUM_SLICES")
    if num is None:
        return None
    try:
        num_slices = int(num)
    except ValueError:
        return None
    if num_slices < 2:
        return None
    slice_id = os.environ.get("MEGASCALE_SLICE_ID")
    return {
        "num_slices": num_slices,
        "slice_id": int(slice_id) if slice_id is not None else 0,
    }


def topology_summary(config) -> str:
    """One-line slice×host topology description for launch-time logging."""
    hosts = config.num_processes
    slices = getattr(config, "dcn_size", 1) or 1
    if slices > 1:
        return (
            f"{slices} slices x {max(hosts // slices, 1)} hosts/slice "
            f"({hosts} processes; dcn axis size {slices})"
        )
    return f"1 slice x {hosts} host{'s' if hosts != 1 else ''}"


def prepare_tpu_pod_env(args, config) -> Optional[dict[str, str]]:
    """Auto-derive multi-host topology from TPU pod metadata env, if present
    (reference ``prepare_tpu`` utils/launch.py:586 — but env-derived rather
    than gcloud-SSH-orchestrated; on Cloud TPU each host's runtime already
    exports its identity)."""
    worker_id = os.environ.get("TPU_WORKER_ID") or os.environ.get("CLOUD_TPU_TASK_ID")
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if worker_id is None or not hostnames:
        return None
    hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
    if len(hosts) < 2:
        # a one-host TPU VM exports the same variables: nothing to
        # coordinate, and a worker handed a coordinator address must call
        # jax.distributed.initialize before its first jax call
        return None
    config.num_processes = len(hosts)
    config.machine_rank = int(worker_id)
    config.main_process_ip = hosts[0]
    config.main_process_port = config.main_process_port or 8476  # TPU runtime default port range
    # Multi-slice metadata fills the dcn axis the operator left unspecified:
    # the worker's ParallelismConfig.from_env then builds the mesh with the
    # explicit cross-slice outer axis (flag > file > metadata precedence —
    # an explicit dcn_size is never overwritten).
    slices = discover_slice_topology()
    if slices is not None and getattr(config, "dcn_size", 1) == 1:
        config.dcn_size = slices["num_slices"]
    env = _base_env(args, config)
    env["ACCELERATE_COORDINATOR_ADDRESS"] = f"{config.main_process_ip}:{config.main_process_port}"
    env["ACCELERATE_NUM_PROCESSES"] = str(config.num_processes)
    env["ACCELERATE_PROCESS_ID"] = str(config.machine_rank)
    return env


def apply_cpu_device_flags(env: dict[str, str], num_cpu_devices: Optional[int]) -> None:
    """Append the virtual-device XLA flag for CPU fake-mesh workers."""
    if num_cpu_devices:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={num_cpu_devices}".strip()


class PrepareForLaunch:
    """Picklable callable handed to ``multiprocessing`` start methods
    (reference utils/launch.py:776) — sets per-process env then calls the
    user function."""

    def __init__(self, launcher, env: dict[str, str], process_id: int):
        self.launcher = launcher
        self.env = env
        self.process_id = process_id

    def __call__(self, *args):
        os.environ.update(self.env)
        os.environ["ACCELERATE_PROCESS_ID"] = str(self.process_id)
        os.environ["FORK_LAUNCHED"] = "1"
        self.launcher(*args)
        # Synchronized teardown: without a barrier, the first worker to exit
        # tears the coordination service down while peers still heartbeat,
        # turning a clean run into a fatal "Socket closed" on the laggard.
        try:
            import jax

            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                multihost_utils.sync_global_devices("accelerate_tpu.worker_exit")
                jax.distributed.shutdown()
        except Exception:  # teardown must never mask the user function's success
            pass
