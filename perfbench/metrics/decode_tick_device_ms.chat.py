"""decode_tick_device_ms.chat: device duration of the decode program, median."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return readers.program_median_ms(run, "decode")
