"""tick_host_ms.assist: ``tick_host_ms`` in the Qwen3-Next cell (128 slots: the widest batch the scheduler plans and commits a tick): every phase of a
decode tick but ``host_sync``, a tick, on the engine's clock (``host_s.decode.*`` / ``ticks.decode`` of ``engine.metrics``), over the WHOLE run."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.tick_host_ms(run)
