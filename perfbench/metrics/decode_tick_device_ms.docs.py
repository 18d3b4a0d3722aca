"""decode_tick_device_ms.docs: device duration of the decode program (48 slots, one token each: the absorbed walk over
the latent pages, 1.5 rows a held expert), median."""

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.program_median_ms(run, "decode")
