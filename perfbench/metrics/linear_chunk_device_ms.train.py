"""linear_chunk_device_ms.train: device self-time of the ops under the ``linear_chunk`` scope (``ops/gated_delta.gated_delta_chunk``:
the chunked gated delta rule of every linear-attention layer - forward, the forward made again where a block is recomputed, and
the backward pass, which runs under the same word), per run of the step program on the first chip."""

from perfbench import scopes

layer = "linear and gated attention"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return scopes.scoped_ms_per_run(run, ("linear_chunk",), ("pinned_step_fn",))
