"""window_attend_device_ms.reason: device self-time under the ``window_attend`` scope (the six window layers: one query a slot against
its ring's 128 rows), per run of the DECODE program (64 slots; a prefill tick is in the traced window of some runs only)."""

from perfbench import scopes

layer = "window and global attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("window_attend",), ("decode",))
