"""tick_stall_ms_per_s.assist: ``tick_stall_ms_per_s`` in the Qwen3-Next cell: ``stall_excess_s_sum`` of ``engine.metrics`` over the engine's busy
seconds - what the ticks that lay far over their class's running median took beyond it, in ms a second (a run holds 1-7 stalls of ~130 ms)."""

from perfbench import host_ledger

layer = "serving engine"
unit = "ms/s"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return host_ledger.ms_per_busy_s(run, "stall_excess_s_sum")
