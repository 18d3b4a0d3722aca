"""moe_shared_device_ms.assist: device self-time under the ``moe_shared`` scope (the shared expert's SwiGLU over every live token and
its sigmoid gate), per run of the DECODE program (128 slots; a prefill tick is in the traced window of some runs only)."""

from perfbench import scopes

layer = "experts"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("moe_shared",), ("decode",))
