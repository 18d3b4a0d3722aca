"""linear_attend_roofline.assist: decode ticks.  Least time (every LIVE slot-layer's recurrent state read and written once in
float32, ``linear_steps / decode_steps`` a tick, plus the step's q, k, v, g, beta and o rows, over the HBM bandwidth: 1 FLOP a
byte is memory-bound; ``rooflines/linear_attend.py``) over the device time of the ``linear_attend`` scope in the decode program."""

from perfbench import scopes
from perfbench.rooflines import linear_attend

layer = "kernels"
unit = "%"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    peaks = run.get("peaks")
    spent = scopes.scoped_s_per_run(run, ("linear_attend",), ("decode",))
    counted = "linear_steps" in (run.get("engine_metrics") or {})       # not the parent's engine
    updated = scopes.counter_mean(run, "linear_steps", "decode_steps") if counted else None
    if not peaks or not spent or not updated:
        return None
    cfg = run["cfg"]
    return linear_attend.least_seconds(peaks, updated, cfg["linear_num_value_heads"],
                                       cfg["linear_key_head_dim"],
                                       cfg["linear_value_head_dim"]) / spent * 100.0
