"""flash_kernels_device_ms: device time of the kernels named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` (``ops/flash_attention.py``), per run of the step program on the first chip; found by
name, so inside ``shard_map`` too."""

from perfbench import program_trace

layer = "kernels"
unit = "ms"
moves = "train_tokens_per_s"
source = "device_trace"


def read(run):
    return program_trace.scoped_ms_per_run(run, program_trace.FLASH_KERNELS)
