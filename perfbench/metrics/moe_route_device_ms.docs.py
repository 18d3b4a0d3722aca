"""moe_route_device_ms.docs: device self-time under the ``moe_route`` scope (sigmoid over 256, the biased top-8, the gates, the
sort by held expert), per run of the DECODE program (48 slots, the 7 sparse layers summed)."""

from perfbench import scopes

layer = "experts"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("moe_route",), ("decode",))
