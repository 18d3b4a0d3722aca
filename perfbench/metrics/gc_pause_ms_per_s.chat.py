"""gc_pause_ms_per_s.chat: ``gc_pause_ms_per_s`` in the chat cell: the collector's pauses over the engine's busy seconds, in ms a second."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "gc_pause_s_sum")
