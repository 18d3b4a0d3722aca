"""serve_tick_wall_ms.chat: host clock around ``engine.step()``, which blocks on the tick's tokens;
median over the window's ticks.  Its excess over the program's device time is the host's part."""

from perfbench import readers

layer = "serving engine"
unit = "ms"
moves = "tpot_p90_ms"
source = "host_clock"


def read(run):
    return readers.tick_wall_ms(run)
