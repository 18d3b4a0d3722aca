"""warmup_cache_load_s.assist: ``warmup_cache_load_s`` in the Qwen3-Next cell: ``warmup_backend_s + warmup_cache_load_s`` of ``engine.metrics``, the
seconds of ``engine.warmup()`` in the backend - reading the persistent compile cache on a warm start, compiling (and writing it) on a cold one."""

from perfbench import host_ledger

layer = "compile cache"
unit = "s"
moves = "setup_s"
source = "program_counter"


def read(run):
    return host_ledger.seconds(run, "warmup_backend_s", "warmup_cache_load_s")
