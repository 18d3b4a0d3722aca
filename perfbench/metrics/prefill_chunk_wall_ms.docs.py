"""prefill_chunk_wall_ms.docs: what a 2048-token prefill chunk (the expanded walk over the latent pages) takes, on the host's clock:
from the start of a prefill tick at the larger bucket to the end of the decode tick behind it (a prefill tick fetches nothing and
returns in ~3 ms; the decode tick behind it waits for both programs), minus the median decode tick that has NO prefill tick before
it (here ~70% of the decode ticks follow a prefill tick, so the median of all of them would hold a chunk already); medians over the
window's ticks before the profiler starts.  Not ``prefill_tick_device_ms``: admissions come in bursts (a request's ~4.5 chunks
alternate with decode ticks, then up to 1.7 s pass with decode ticks alone), so a traced tail of a fraction of a second holds a
prefill program in ~9 of 10 runs and a device-trace reading would be on the line of some traced runs and not of others
(``traffic/serve_docs.json``, ``trace_seconds_why``)."""

import statistics

from perfbench import readers

layer = "model step"
unit = "ms"
moves = "serve_tokens_per_s"
source = "host_clock"


def read(run):
    ticks = readers.host_ticks(run)
    chunks = [t["bucket"] for t in ticks if t["kind"] == "prefill" and t["bucket"]]
    wall = lambda a, b: b["end"] - a["start"]
    pairs = [(a, b) for a, b in zip(ticks, ticks[1:]) if b["kind"] == "decode"]
    alone = [wall(b, b) for a, b in pairs if a["kind"] == "decode"]
    if not chunks or not alone:
        return None
    behind = [wall(a, b) for a, b in pairs if a["kind"] == "prefill" and a["bucket"] == max(chunks)]
    return (statistics.median(behind) - statistics.median(alone)) * 1e3 if behind else None
