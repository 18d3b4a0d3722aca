"""engine_ttft_mean_ms.chat: ``ttft_s_sum / ttft_n`` of ``engine.metrics``: ``add_request`` to the first token on
the host, stamped inside the engine, over the WHOLE run (ramp and drain included).  Beside
``ttft_mean_ms.chat`` (due time to the return of ``engine.step()``, window only) the difference is what the
benchmark's one-thread loop adds between a request's due time and its ``add_request``."""

from perfbench import program_trace

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return program_trace.engine_mean_ms(run, "ttft")
