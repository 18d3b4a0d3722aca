"""flash_bwd_roofline.hybrid: the four matmuls the gradient requires in every full-attention layer over peak (``rooflines/flash_bwd.py``
as it is: the kernel runs five, so 80% is the reading's ceiling), over the device time of the ``flash_bwd_dkv`` kernel a step.  Counted
by layer KIND, and by the kernel's NAME: ``flash_bwd_roofline`` multiplies one call by ``run["layers"]`` and takes every kernel call
under ``transpose(`` for a backward one, ``remat``'s second forward too."""

from perfbench import scopes
from perfbench.rooflines import flash_bwd as roofline

KERNEL = "flash_bwd_dkv"
layer = "kernels"
unit = "%"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    peaks, cfg = run.get("peaks"), run.get("cfg", {})
    spent = scopes.scoped_s_per_run(run, (KERNEL,), ("pinned_step_fn",))
    if not peaks or not spent or "layer_types" not in cfg:
        return None
    shard = run["flash_shard"]
    full = cfg["layer_types"][:run["layers"]].count("full_attention")
    least = roofline.least_seconds(peaks, shard["batch"], shard["heads"], shard["kv_heads"], run["seq"],
                                   cfg["hidden_size"] // cfg["num_attention_heads"])
    return least * full / spent * 100.0
