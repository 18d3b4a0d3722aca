"""moe_experts_roofline.assist: decode ticks.  Least time (the weights of the HELD experts hit, ``moe_experts_hit_sum /
moe_ticks`` a layer, plus the rows routed here, ``moe_rows_held / moe_ticks``, read and written, over the HBM
bandwidth: ~2.5 rows an expert are memory-bound; ``rooflines/moe_experts.py``) times the layer-ticks of a decode
tick, over the expert block's device time in the decode program (as ``moe_experts_device_ms.assist`` counts it)."""

from perfbench import scopes
from perfbench.rooflines import moe_experts

layer = "kernels"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    peaks = run.get("peaks")
    spent = scopes.scoped_s_per_run(run, ("moe_experts",), ("decode",), hlo=("ragged-dot",))
    hit = scopes.counter_mean(run, "moe_experts_hit_sum", "moe_ticks")
    rows = scopes.counter_mean(run, "moe_rows_held", "moe_ticks")
    # (the parent's engine counts decode_steps but none of this family's counters)
    sparse = scopes.counter_mean(run, "moe_ticks", "decode_steps") if hit is not None else None
    if not peaks or not spent or hit is None or rows is None or sparse is None:
        return None
    cfg = run["cfg"]
    least = moe_experts.least_seconds(peaks, hit, rows, cfg["hidden_size"],
                                      cfg["moe_intermediate_size"]) * sparse
    return least / spent * 100.0
