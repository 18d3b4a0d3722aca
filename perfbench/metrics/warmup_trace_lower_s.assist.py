"""warmup_trace_lower_s.assist: ``warmup_trace_lower_s`` in the Qwen3-Next cell: ``warmup_trace_s + warmup_lower_s`` of ``engine.metrics``, the seconds
of ``engine.warmup()`` that jax spent tracing the three programs (a decode step, two prefill buckets) and lowering them - paid on every start."""

from perfbench import host_ledger

layer = "compile cache"
unit = "s"
moves = "setup_s"
source = "program_counter"


def read(run):
    return host_ledger.seconds(run, "warmup_trace_s", "warmup_lower_s")
