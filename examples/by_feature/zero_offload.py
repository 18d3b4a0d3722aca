"""ZeRO-offload training (reference capability: DeepSpeed
``offload_optimizer_device``/``offload_param_device``, dataclasses.py:1172;
examples/deepspeed config zoo).

``FullyShardedDataParallelPlugin(cpu_offload=True)`` pins the Adam moments
and fp32 master params to host memory; the optimizer update runs as XLA
host compute.  On a 16GB v5e this is what lets 32k+ token contexts and
Llama-2-7B train on one chip (see docs/offload.md; measured before PR 1 on
another toolchain — on today's code offload is not measured on the chip:
no benchmark cell offloads, ROADMAP.md A11).
"""

import argparse

import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.test_utils.training import (
    make_regression_loader,
    regression_init_params,
    regression_loss_fn,
)
from accelerate_tpu.utils.dataclasses import FullyShardedDataParallelPlugin


def main(args):
    acc = Accelerator(
        mixed_precision="bf16",
        fsdp_plugin=FullyShardedDataParallelPlugin(
            cpu_offload=True,
            # offload_params=False would keep fp32 masters in HBM and
            # offload only the optimizer state (DeepSpeed stage-2-offload)
            offload_params=not args.optimizer_only,
        ),
    )
    dl = acc.prepare(make_regression_loader(batch_size=16))
    state = acc.create_train_state(regression_init_params(), acc.prepare(optax.adamw(0.05)))
    step = acc.prepare_train_step(regression_loss_fn, max_grad_norm=1.0)

    for epoch in range(3):
        for batch in dl:
            state, metrics = step(state, batch)
        acc.print(f"epoch {epoch}: loss {float(metrics['loss']):.5f}")

    # anything outside the prepared step wants device copies of the masters
    eval_params = acc.device_params(state.params)
    acc.print(f"a={float(eval_params['a']):.3f} b={float(eval_params['b']):.3f} (targets 2, 3)")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--optimizer_only", action="store_true",
                        help="offload only optimizer state, keep fp32 masters in HBM")
    main(parser.parse_args())
