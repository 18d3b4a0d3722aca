"""global_attend_roofline.assist: decode ticks.  Least time (K and V of every key VISIBLE to the live queries of the
full-attention layers, ``global_visible_sum / decode_steps`` a tick, plus q and o per slot and layer, over the HBM
bandwidth: one query a slot is memory-bound; ``rooflines/paged_attend.py``) over the device time of the
``global_attend`` scope in the decode program."""

from perfbench import scopes
from perfbench.rooflines import paged_attend

layer = "kernels"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    peaks = run.get("peaks")
    spent = scopes.scoped_s_per_run(run, ("global_attend",), ("decode",))
    counted = "global_visible_sum" in (run.get("engine_metrics") or {})     # not the parent's engine
    visible = scopes.counter_mean(run, "global_visible_sum", "decode_steps") if counted else None
    if not peaks or not spent or visible is None:
        return None
    cfg = run["cfg"]
    queries = run["num_slots"] * (run["layers"] // cfg["full_attention_interval"])
    return paged_attend.least_seconds(peaks, visible, queries, cfg["num_attention_heads"],
                                      cfg["num_key_value_heads"], cfg["head_dim"]) / spent * 100.0
