"""serve_device_idle_share.assist: 1 - busy/window from the device timeline of the traced window (8 of 48 layers: the host's
share of a tick is about 1.5 times a 12-layer stage's)."""

from perfbench import readers

layer = "device"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.idle_share_pct(run)
