"""serve_tick_wall_ms.batch: host clock around ``engine.step()``, which blocks on the tick's tokens;
median over the window's ticks.  Its excess over the program's device time is the host's part."""

from perfbench import readers

layer = "serving engine"
unit = "ms"
moves = "serve_tokens_per_s"
source = "host_clock"


def read(run):
    return readers.tick_wall_ms(run)
