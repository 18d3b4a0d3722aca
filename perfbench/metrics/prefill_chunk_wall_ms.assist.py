"""prefill_chunk_wall_ms.assist: what a 2048-token prefill chunk takes, on the host's clock: from the start of a prefill tick at
the larger bucket to the end of the decode tick behind it (a prefill tick fetches nothing and returns at once; the decode tick
behind it waits for both programs), minus the median decode tick that has a decode tick before it; median over the window's such
pairs before the profiler starts.  Not a device-trace reading of the prefill program: a request's ~2.3 chunks alternate with decode
ticks and then a second or more passes with decode ticks alone, so the traced tail (``traffic/serve_assist.json``) holds a whole
2,048-bucket prefill run in some runs and not in others - which is also why the ``linear_chunk`` scope has no benchmark reader."""

import statistics

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "host_clock"


def read(run):
    ticks = readers.host_ticks(run)
    chunks = [t["bucket"] for t in ticks if t["kind"] == "prefill" and t["bucket"]]
    pairs = list(zip(ticks, ticks[1:]))
    decode = [b["end"] - b["start"] for a, b in pairs if a["kind"] == "decode" and b["kind"] == "decode"]
    if not chunks or not decode:
        return None
    behind = [b["end"] - a["start"] for a, b in pairs
              if a["kind"] == "prefill" and a["bucket"] == max(chunks) and b["kind"] == "decode"]
    return (statistics.median(behind) - statistics.median(decode)) * 1e3 if behind else None
