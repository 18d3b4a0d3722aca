"""Arithmetic of the readers of the engine's host ledger (the flat keys that
``accelerate_tpu/telemetry/host_ledger.py`` keeps in ``engine.metrics``:
``host_s.<kind>.<phase>``, ``ticks.<kind>``, ``tick_wall_s.<kind>``,
``outside_s_sum``, ``gc_pause_s_sum``, ``stall_excess_s_sum``, ``warmup_*``).

Counters of the WHOLE run, ramp and drain included: the engine knows no
window.  On a run whose engine keeps no ledger (the parent of the PR that
brought it, run under these files) every function returns None and the metric
is left out of the line; nothing here imports the program."""

from __future__ import annotations

KINDS = ("decode", "prefill", "verify", "idle")


def _metrics(run) -> dict:
    return run.get("engine_metrics") or {}


def tick_host_ms(run, kind: str = "decode"):
    """The host's own part of a tick of ``kind``: every phase of ``step()``
    but ``host_sync`` (the wait for the device), a tick, in ms."""
    m = _metrics(run)
    ticks = m.get(f"ticks.{kind}")
    if not ticks:
        return None
    prefix, waited = f"host_s.{kind}.", f"host_s.{kind}.host_sync"
    return sum(v for k, v in m.items() if k.startswith(prefix) and k != waited) / ticks * 1e3


def busy_s(run):
    """Seconds the engine had work: its ticks, and the caller's time between
    two of them (``outside``)."""
    m = _metrics(run)
    if "outside_s_sum" not in m:
        return None
    return sum(m.get(f"tick_wall_s.{k}", 0.0) for k in KINDS) + m["outside_s_sum"]


def ms_per_busy_s(run, key: str):
    """``engine.metrics[key]`` (seconds) in ms a second the engine had work."""
    busy = busy_s(run)
    if not busy or key not in _metrics(run):
        return None
    return _metrics(run)[key] * 1e3 / busy


def seconds(run, *keys):
    """The sum of ``keys`` of ``engine.metrics``, or None where one is not kept."""
    m = _metrics(run)
    return sum(m[k] for k in keys) if all(k in m for k in keys) else None
