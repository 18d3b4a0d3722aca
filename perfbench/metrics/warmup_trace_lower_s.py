"""warmup_trace_lower_s: ``warmup_trace_s + warmup_lower_s`` of ``engine.metrics``: the seconds of ``engine.warmup()`` that jax spent tracing the
programs and lowering them to StableHLO — paid on every start, whatever the compile cache holds."""

from perfbench import host_ledger

layer = "compile cache"
unit = "s"
moves = "setup_s"
source = "program_counter"


def read(run):
    return host_ledger.seconds(run, "warmup_trace_s", "warmup_lower_s")
