"""serve_tick_wall_ms.assist: host clock around ``engine.step()``, median over the window's ticks (most are decode ticks, which wait for
the tick's tokens; 8 of 48 layers: the host's part of a tick is about 1.5 times a 12-layer stage's)."""

from perfbench import readers

layer = "serving engine"
unit = "ms"
moves = "serve_tokens_per_s"
source = "host_clock"


def read(run):
    return readers.tick_wall_ms(run)
