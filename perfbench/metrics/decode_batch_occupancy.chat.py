"""decode_batch_occupancy.chat: active slots over num_slots, mean over the window's decode ticks (a count)."""

from perfbench import readers

layer = "serving engine"
unit = "%"
moves = "tpot_p90_ms"
source = "program_counter"


def read(run):
    return readers.occupancy_pct(run)
