"""pool_copy_ms_per_tick.chat: device time of ``copy`` class ops per run of a decode or prefill program
(the whole-pool relayout around ``models/llama.paged_write_kv``)."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return readers.class_ms_per_program_run(run, "copy")
