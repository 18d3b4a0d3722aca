"""optimizer_device_ms: device self-time of the ops under the ``optimizer_update`` scope (the prepared step's
``tx.update`` + ``apply_updates``, lion-sr's rounding included), per run of the step program on the first chip."""

from perfbench import program_trace

layer = "train step"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return program_trace.scoped_ms_per_run(run, ("optimizer_update",))
