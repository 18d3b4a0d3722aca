"""linear_project_device_ms.assist: device self-time under the ``linear_project`` scope (the six Gated DeltaNet layers' fused
in-projections, the 4-tap conv over the slot's window, the L2 norms, beta and g), per run of the DECODE program (128 slots; a
prefill tick is in the traced window of some runs only)."""

from perfbench import scopes

layer = "linear and gated attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("linear_project",), ("decode",))
