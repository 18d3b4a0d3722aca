"""tick_host_ms: ``sum(host_s.decode.<phase>, every phase but host_sync) / ticks.decode`` of ``engine.metrics``: the host's own part of a
decode tick on the engine's clock — ``control``, ``schedule``, ``plan``, ``stage``, ``dispatch``, ``commit`` (and ``trace`` where a tracer is armed), not the
wait for the device and not the caller's time between ticks (``outside``).  Over the WHOLE run, ramp and drain included."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.tick_host_ms(run)
