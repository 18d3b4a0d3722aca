"""serve_device_idle_share.chat: 1 - busy/window from the device timeline of the traced window."""

from perfbench import readers

layer = "device"
unit = "%"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return readers.idle_share_pct(run)
