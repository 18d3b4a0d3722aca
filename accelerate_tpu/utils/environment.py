"""Environment-variable parsing and patching helpers.

TPU-native re-design of the reference's ``utils/environment.py`` (see
/root/reference/src/accelerate/utils/environment.py:31-130 for ``str_to_bool``,
``parse_flag_from_env``, ``parse_choice_from_env`` and :341-411 for
``clear_environment`` / ``patch_environment``).  Config crosses the process
boundary exclusively through ``ACCELERATE_*`` environment variables, exactly
like the reference launcher (reference utils/launch.py:198-423).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from functools import lru_cache
from typing import Any


def str_to_bool(value: str) -> int:
    """Convert a string into a 1/0 truth value.

    Accepts y/yes/t/true/on/1 and n/no/f/false/off/0 (case-insensitive).
    Mirrors reference environment.py:31-43.
    """
    value = value.lower()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0"):
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def get_int_from_env(env_keys, default: int) -> int:
    """Return the first positive int found among ``env_keys``."""
    for key in env_keys:
        val = int(os.environ.get(key, -1))
        if val >= 0:
            return val
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def are_libraries_initialized(*library_names: str) -> list[str]:
    """Return the sub-list of ``library_names`` already imported."""
    import sys

    return [lib for lib in library_names if lib in sys.modules]


@contextmanager
def clear_environment():
    """Temporarily clear ``os.environ``, restoring it on exit.

    Mirrors reference environment.py:341-374 (restores the *same* mapping
    object so references held elsewhere stay valid).
    """
    backup = os.environ.copy()
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(backup)


@contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set environment variables (keys upper-cased).

    Mirrors reference environment.py:376-410.
    """
    existing: dict[str, str] = {}
    missing: set[str] = set()
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        else:
            missing.add(key)
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in missing:
                os.environ.pop(key, None)
            else:
                os.environ[key] = existing[key]


def purge_accelerate_environment(func):
    """Decorator that strips ``ACCELERATE_*`` env vars around a callable.

    Mirrors reference environment.py:412-470 (test hygiene).
    """
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        backup = {k: v for k, v in os.environ.items() if k.startswith("ACCELERATE_")}
        for k in backup:
            del os.environ[k]
        try:
            return func(*args, **kwargs)
        finally:
            for k in list(os.environ):
                if k.startswith("ACCELERATE_"):
                    del os.environ[k]
            os.environ.update(backup)

    return wrapper


@lru_cache
def get_tpu_env_metadata() -> dict[str, str]:
    """Collect TPU topology hints from the environment (GCE metadata style)."""
    keys = (
        "TPU_WORKER_ID",
        "TPU_WORKER_HOSTNAMES",
        "TPU_ACCELERATOR_TYPE",
        "TPU_CHIPS_PER_HOST_BOUNDS",
        "TPU_HOST_BOUNDS",
        "MEGASCALE_COORDINATOR_ADDRESS",
        "MEGASCALE_NUM_SLICES",
        "MEGASCALE_SLICE_ID",
    )
    return {k: os.environ[k] for k in keys if k in os.environ}


def get_free_port() -> int:
    """Pick an unused localhost TCP port (reference utils/other.py:478)."""
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def check_port_in_use(port: int, host: str = "localhost") -> bool:
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        try:
            s.bind((host, port))
            return False
        except OSError:
            return True


@lru_cache
def set_cpu_affinity(local_process_index: int, total_local_processes: int | None = None,
                     verbose: bool | None = None) -> None:
    """Partition the host's CPU cores across co-located ranks (reference
    ``set_numa_affinity`` utils/environment.py:323 + thread pinning
    state.py:266-281 — minus the GPU-NUMA lookup, which has no TPU analog).

    Why this is NOT on the default launch path: a Cloud TPU host runs ONE
    training process that owns the whole (single-socket) VM, and the TPU
    runtime manages its own thread pools — there is no contended NUMA
    boundary to pin across, so pinning can only take cores away.  The two
    real uses are (a) the local CPU-gang rehearsal mode, where N spawned
    ranks otherwise thrash each other's caches, and (b) multi-socket custom
    hosts feeding host-side dataloader workers / the C++ staging ring, where
    the caller knows the topology.  Both opt in explicitly (or via
    ``ACCELERATE_CPU_AFFINITY=1``, the reference's env knob).

    Cached per process index; no-op on platforms without
    ``os.sched_setaffinity`` (macOS).
    """
    if not hasattr(os, "sched_setaffinity"):
        return
    cores = sorted(os.sched_getaffinity(0))
    n = max(total_local_processes or get_int_from_env(["ACCELERATE_NUM_PROCESSES"], 1), 1)
    idx = local_process_index % n
    # striped assignment: cores[idx::n] distributes any remainder (no
    # stranded tail cores) and keeps ranks disjoint; with more ranks than
    # cores the overflow ranks degrade to one (shared) core each instead of
    # grabbing the whole mask back
    mine = cores[idx::n] if idx < len(cores) else []
    if not mine:
        mine = [cores[idx % len(cores)]]
    os.sched_setaffinity(0, mine)
    if verbose or (verbose is None and parse_flag_from_env("ACCELERATE_DEBUG_MODE")):
        from ..logging import get_logger

        get_logger(__name__).info(
            "Pinned process %d to %d/%d cpu cores: %s",
            local_process_index, len(mine), len(cores), mine,
        )
