"""ttft_mean_ms.chat: mean over the requests due inside the window of first-token time minus due time
(a request with no first token 5 s after the window stays in at the wait it had).  Recorded, not
judged: its run-to-run spread differs threefold between machines, more than a bound's window
holds (PERF.md 2); until then it stands beside tpot_p90_ms, which the same prefill ticks move."""

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "host_clock"


def read(run):
    values = [v for u, v in run.get("ttft_ms", {}).items() if run["due"][u] < run["host_window_s"]]
    return sum(values) / len(values) if values else None
