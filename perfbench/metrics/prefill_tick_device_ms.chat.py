"""prefill_tick_device_ms.chat: device duration of the prefill program at the larger bucket, median."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return readers.prefill_large_bucket_ms(run)
