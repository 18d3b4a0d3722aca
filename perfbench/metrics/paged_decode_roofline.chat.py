"""paged_decode_roofline.chat: least time (live K/V page bytes + q + o over the HBM bandwidth:
memory-bound) over the decode kernel's device time, over the traced decode ticks."""

from perfbench import readers
from perfbench.rooflines import paged_decode

layer = "kernels"
unit = "%"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    peaks, calls = run.get("peaks"), readers.kernel_time_s(run, "decode")
    ticks = [t for t in run.get("ticks", []) if t["traced"] and t["kind"] == "decode"]
    if not peaks or not calls or not ticks:
        return None
    cfg, eng = run["cfg"], run["engine_spec"]
    least = sum(paged_decode.least_seconds(
        peaks, t["kv_pages"], eng["page_size"], cfg["num_key_value_heads"],
        cfg["num_attention_heads"], cfg["head_dim"], t["active"]) for t in ticks) * run["layers"]
    return least / sum(calls) * 100.0
