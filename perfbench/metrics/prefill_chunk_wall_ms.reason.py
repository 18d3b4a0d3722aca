"""prefill_chunk_wall_ms.reason: what a 2048-token prefill chunk takes, on the host's clock: from the start of a prefill tick at
the larger bucket to the end of the decode tick behind it (a prefill tick fetches nothing and returns in ~4 ms; the decode tick
behind it waits for both programs), minus the median decode tick; median over the window's such pairs before the profiler starts.
Not ``prefill_tick_device_ms``: a prefill tick comes once in ~13 ticks here (2.7 a second) and the traced window is a fraction of
a second (``traffic/serve_reason.json`` says why), so a device-trace reading of the prefill program would be on the line of some
traced runs and not of others."""

import statistics

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "host_clock"


def read(run):
    ticks = readers.host_ticks(run)
    chunks = [t["bucket"] for t in ticks if t["kind"] == "prefill" and t["bucket"]]
    decode = [t["end"] - t["start"] for t in ticks if t["kind"] == "decode"]
    if not chunks or not decode:
        return None
    pairs = [b["end"] - a["start"] for a, b in zip(ticks, ticks[1:])
             if a["kind"] == "prefill" and a["bucket"] == max(chunks) and b["kind"] == "decode"]
    return (statistics.median(pairs) - statistics.median(decode)) * 1e3 if pairs else None
