"""queue_wait_mean_ms.chat: ``queue_wait_s_sum / queue_wait_n`` of ``engine.metrics``: ``add_request`` to the
dispatch of the request's first prefill chunk, on the engine's clock, once per request.  Over the WHOLE
run, ramp and drain included: the engine's counters know no window."""

from perfbench import program_trace

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return program_trace.engine_mean_ms(run, "queue_wait")
