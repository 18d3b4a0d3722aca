"""prefill_tick_device_ms.batch: device duration of the prefill program at the larger bucket, median."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.prefill_large_bucket_ms(run)
