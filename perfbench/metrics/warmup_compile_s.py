"""warmup_compile_s: host clock around the cell's warm-up calls (building the step or the engine's
programs: compiling, or loading from the persistent cache)."""



layer = "compile cache"
unit = "s"
moves = "setup_s"
source = "host_clock"


def read(run):
    return run.get("warmup_compile_s")
