"""moe_route_device_ms.assist: device self-time under the ``moe_route`` scope (softmax over 512, the top-10, the gates, the
sort by held expert), per run of the DECODE program (128 slots; a prefill tick is in the traced window of some runs only)."""

from perfbench import scopes

layer = "experts"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("moe_route",), ("decode",))
