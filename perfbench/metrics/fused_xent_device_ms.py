"""fused_xent_device_ms: device self-time of the ops under the ``fused_xent`` scope (``ops/fused_xent.py``: the
chunk loop forward and, under ``transpose(...)``, backward), per run of the step program on the first
chip; collectives the compiler put inside the scope count."""

from perfbench import program_trace

layer = "train step"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return program_trace.scoped_ms_per_run(run, ("fused_xent",))
