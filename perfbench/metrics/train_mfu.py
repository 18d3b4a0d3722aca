"""train_mfu: train_tokens_per_s x required FLOPs per token over chips x bf16 peak
(``rooflines/train_step.py``; no recompute counted).  An end-to-end utilization, not a
kernel's roofline share."""

from perfbench.rooflines import train_step

layer = "train step"
unit = "%"
moves = "train_tokens_per_s"
source = "host_clock"


def read(run):
    if not run.get("peaks"):
        return None
    per_token = train_step.flops_per_token(run["cfg"], run["layers"], run["seq"])
    return run["end_to_end"]["train_tokens_per_s"] * per_token / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"]) * 100.0
