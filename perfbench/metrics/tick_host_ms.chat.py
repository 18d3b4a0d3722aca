"""tick_host_ms.chat: ``tick_host_ms`` in the chat cell, where it moves the inter-token gap: every phase of a decode tick but ``host_sync``, a tick,
on the engine's clock (``host_s.decode.*`` / ``ticks.decode`` of ``engine.metrics``), over the WHOLE run."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return host_ledger.tick_host_ms(run)
