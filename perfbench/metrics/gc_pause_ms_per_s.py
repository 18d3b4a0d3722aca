"""gc_pause_ms_per_s: ``gc_pause_s_sum`` of ``engine.metrics`` (every pause of Python's collector since the engine was built, from the one
``gc.callbacks`` hook of the process) over the engine's busy seconds, in ms a second."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "gc_pause_s_sum")
