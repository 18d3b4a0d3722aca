"""tick_launch_exposed_ms.batch: median over the traced ticks of the device's idle time between the start of the tick's
``stage:*`` span (the engine's own annotation in the profiler's trace) and the start of its
program on the device; what the previous program still covers is not counted."""

from perfbench import program_trace

layer = "serving engine"
unit = "ms"
moves = "serve_tokens_per_s"
source = "device_trace"


def read(run):
    return program_trace.tick_median_ms(run, "launch")
