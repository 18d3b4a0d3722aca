"""paged_write_kv_device_ms.chat: device self-time of the ops whose ``tf_op`` holds the ``paged_write_kv``
scope (``models/llama.py``), per run of a decode or prefill program.  The scatter alone: the relayout
copies the compiler puts around it carry no name and stay in ``pool_copy_ms_per_tick.*``
(``program_trace.report`` links them by their operands)."""

from perfbench import program_trace

layer = "model step"
unit = "ms"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return program_trace.scoped_ms_per_run(run, ("paged_write_kv",), ("decode", "prefill"))
