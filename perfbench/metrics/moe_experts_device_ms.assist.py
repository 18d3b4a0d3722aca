"""moe_experts_device_ms.assist: device self-time of the held experts' block, per run of the DECODE program (128 slots; a prefill tick
is in the traced window of some runs only): the ops under the ``moe_experts`` scope (``parallel/expert_parallel.grouped_ffn``: the
blocks of held rows, the gathers, the gated add) plus the grouped matmuls, which XLA names ``ragged-dot-*`` and gives no scope."""

from perfbench import scopes

layer = "experts"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("moe_experts",), ("decode",), hlo=("ragged-dot",))
