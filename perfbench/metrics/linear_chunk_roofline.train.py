"""linear_chunk_roofline.train: least time of the gated delta rule forward + backward over a step's tokens in every linear-attention
layer (``rooflines/linear_chunk_train.py``: q, k, v, g, beta, o and their gradients cross HBM once in float32, the recurrence's own 21
``Dk x Dv`` operations a token and head; memory-bound; what ANY implementation must do, no block size) over the device time of the
``linear_chunk`` scope in the step program.  The plain-XLA chunked form reads a few percent: the yardstick of a chunk kernel."""

from perfbench import scopes
from perfbench.rooflines import linear_chunk_train

layer = "kernels"
unit = "%"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    peaks = run.get("peaks")
    spent = scopes.scoped_s_per_run(run, ("linear_chunk",), ("pinned_step_fn",))
    if not peaks or not spent:
        return None
    cfg = run["cfg"]
    linear = cfg["layer_types"][:run["layers"]].count("linear_attention")
    return linear_chunk_train.least_seconds(
        peaks, linear, run["tokens_per_step"] // run["chips"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]) / spent * 100.0
