"""latent_attend_roofline.docs: decode ticks.  Least time (the latent row of every key VISIBLE to the live queries read once for
all held heads, ``latent_visible_sum / decode_steps`` a tick over the 8 layers, plus q and u per slot and layer, over the HBM
bandwidth: 7.6 FLOP a byte is memory-bound; ``rooflines/latent_attend.py``) over the device time of the ``latent_attend`` scope
in the decode program."""

from perfbench import scopes
from perfbench.rooflines import latent_attend

layer = "kernels"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    peaks = run.get("peaks")
    spent = scopes.scoped_s_per_run(run, ("latent_attend",), ("decode",))
    counted = "latent_visible_sum" in (run.get("engine_metrics") or {})     # not the parent's engine
    visible = scopes.counter_mean(run, "latent_visible_sum", "decode_steps") if counted else None
    if not peaks or not spent or visible is None:
        return None
    cfg = run["cfg"]
    queries = run["num_slots"] * run["layers"]
    return latent_attend.least_seconds(peaks, visible, queries, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                                       cfg["qk_rope_head_dim"]) / spent * 100.0
