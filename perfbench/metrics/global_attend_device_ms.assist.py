"""global_attend_device_ms.assist: device self-time under the ``global_attend`` scope (the two full-attention layers' causal walk
over each slot's own pages: the ``paged_walk_decode`` kernel at head_dim 256), per run of the DECODE program (128 slots; a prefill
tick is in the traced window of some runs only)."""

from perfbench import scopes

layer = "linear and gated attention"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("global_attend",), ("decode",))
