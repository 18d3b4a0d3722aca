"""tick_stall_ms_per_s: ``stall_excess_s_sum`` of ``engine.metrics`` over the engine's busy seconds (``tick_wall_s.*`` + ``outside_s_sum``): what
the ticks (and the gaps between ticks) that lay far over their class's running median took beyond that median, in ms a second.  0.0 in a clean run."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "stall_excess_s_sum")
