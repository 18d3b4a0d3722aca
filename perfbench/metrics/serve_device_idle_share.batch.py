"""serve_device_idle_share.batch: 1 - busy/window from the device timeline of the traced window."""

from perfbench import readers

layer = "device"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.idle_share_pct(run)
