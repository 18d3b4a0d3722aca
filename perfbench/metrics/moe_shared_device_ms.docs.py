"""moe_shared_device_ms.docs: device self-time under the ``moe_shared`` scope (the shared expert's SwiGLU over every live token),
per run of the DECODE program (48 slots, the 7 sparse layers summed)."""

from perfbench import scopes

layer = "experts"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("moe_shared",), ("decode",))
