"""train_mfu.hybrid: train_tokens_per_s x required FLOPs per token over chips x bf16 peak, the FLOPs counted by layer KIND
(``rooflines/train_step_hybrid.py``: 6 x each kind's matmul parameters and the head, causal attention in the full layers
only, the gated delta rule's own recurrence forward + backward in the linear layers; no recompute counted).  An end-to-end
utilization, not a kernel's roofline share; ``train_mfu`` counts a Llama-shaped decoder and is not read on this cell."""

from perfbench.rooflines import train_step_hybrid

layer = "train step"
unit = "%"
moves = "train_tokens_per_s"
source = "host_clock"


def read(run):
    if not run.get("peaks") or "layer_types" not in run.get("cfg", {}):
        return None
    per_token = train_step_hybrid.flops_per_token(run["cfg"], run["layers"], run["seq"])
    return run["end_to_end"]["train_tokens_per_s"] * per_token / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"]) * 100.0
