"""ttft_p90_ms: 90th percentile of the sample ttft_mean_ms.chat is the mean of.  Recorded, not
judged: with ~200 requests a window its run-to-run spread is the tick phase's (PERF.md 2)."""

from perfbench import window as W

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "host_clock"


def read(run):
    values = [v for u, v in run.get("ttft_ms", {}).items() if run["due"][u] < run["host_window_s"]]
    return W.percentile(values, 90) if values else None
