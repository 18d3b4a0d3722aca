"""train_device_idle_share: 1 - busy/window from the device timeline of the traced window."""

from perfbench import readers

layer = "device"
unit = "%"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return readers.idle_share_pct(run)
