"""decode_batch_occupancy.assist: active slots over num_slots (128), mean over the window's decode ticks (a count)."""

from perfbench import readers

layer = "serving engine"
unit = "%"
moves = "serve_tokens_per_s"
source = "program_counter"


def read(run):
    return readers.occupancy_pct(run)
