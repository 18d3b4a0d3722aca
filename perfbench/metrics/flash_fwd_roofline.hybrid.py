"""flash_fwd_roofline.hybrid: causal FLOPs of ONE forward in every full-attention layer over peak (``rooflines/flash_fwd.py`` as it is),
over the device time of the ``flash_fwd`` kernel a step.  Counted by layer KIND, and by the kernel's NAME: ``flash_fwd_roofline``
multiplies one call by ``run["layers"]`` (every layer an attention layer) and splits calls by ``transpose(`` in their scope, under
which ``remat``'s second forward sits too.  That second forward is device time and no required work, so a step that recomputes its
blocks reads half of the kernel's own share: what the recompute costs is part of the reading."""

from perfbench import scopes
from perfbench.rooflines import flash_fwd as roofline

KERNEL = "flash_fwd"
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
