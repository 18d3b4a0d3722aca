"""tick_sync_exposed_ms.chat: median over the traced ticks that fetch a token of the end of the tick's
``host_sync`` span minus the end of its last program on the device: how long after the device
finished the host knew."""

from perfbench import program_trace

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "device_trace"


def read(run):
    return program_trace.tick_median_ms(run, "sync")
