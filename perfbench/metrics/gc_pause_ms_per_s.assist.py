"""gc_pause_ms_per_s.assist: ``gc_pause_ms_per_s`` in the Qwen3-Next cell: ``gc_pause_s_sum`` of ``engine.metrics`` (every pause of Python's
collector since the engine was built) over the engine's busy seconds, in ms a second: the stall log names ``gc`` for most of a stalled tick here."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "gc_pause_s_sum")
