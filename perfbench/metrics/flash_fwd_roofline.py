"""flash_fwd_roofline: causal FLOPs over peak, over the device time of the forward flash kernel.
Compute-bound.  Per chip: the kernel sees its shard (batch over dp, heads over tp)."""

from perfbench import readers
from perfbench.rooflines import flash_fwd

layer = "kernels"
unit = "%"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not peaks:
        return None
    calls = readers.flash_kernel_seconds(run, "fwd")
    if not calls:
        return None
    cfg, shard = run["cfg"], run["flash_shard"]
    least = flash_fwd.least_seconds(peaks, shard["batch"], shard["heads"], shard["kv_heads"],
                                 run["seq"], cfg["head_dim"])
    return least * run["layers"] * readers.main_program_runs(run) / sum(calls) * 100.0
