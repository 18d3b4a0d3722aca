"""train_step_device_ms: device duration of the step program, median over the traced runs."""

from perfbench import readers

layer = "train step"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.main_program_median_ms(run)
