"""linear_attend_device_ms.assist: device self-time under the ``linear_attend`` scope (the six Gated DeltaNet layers' one-token
recurrence: the ``gated_delta_step`` kernel, each slot's state read, corrected and written in place, and the relayout of its
operands), per run of the DECODE program (128 slots)."""

from perfbench import scopes

layer = "linear and gated attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("linear_attend",), ("decode",))
