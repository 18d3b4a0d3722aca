"""decode_tick_device_ms.assist: device duration of the decode program (128 slots, one token each), median."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.program_median_ms(run, "decode")
