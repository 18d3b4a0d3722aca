"""tick_stall_ms_per_s.chat: ``tick_stall_ms_per_s`` in the chat cell, where a slow tick lands on every live request's inter-token gap: ``stall_excess_s_sum``
over the engine's busy seconds, in ms a second.  0.0 in a clean run."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "stall_excess_s_sum")
