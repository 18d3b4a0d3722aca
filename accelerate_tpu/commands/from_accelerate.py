"""``accelerate-tpu from-accelerate`` — import a HuggingFace Accelerate
config YAML into an accelerate-tpu launch config.

Migration-path analog of the reference's own config converter CLI
(``accelerate to-fsdp2``, reference commands/to_fsdp2.py): reads the
reference's ``default_config.yaml`` format (reference
commands/config/config_args.py; fixtures tests/test_configs/*.yaml) and
emits an equivalent :class:`~accelerate_tpu.commands.config.LaunchConfig`,
reporting every key it dropped and why — GPU-only concerns (gpu_ids, NCCL
rendezvous, DeepSpeed engine internals) have no TPU counterpart, while
strategy-level intent (FSDP/ZeRO sharding, mixed precision, the N-D
parallelism axes) carries over.

Mapping notes:
- ``distributed_type: FSDP``, and DeepSpeed ``zero_stage >= 2``, both become
  ``use_fsdp`` (GSPMD parameter/grad/opt-state sharding — SURVEY §2.4 P2-P4:
  ZeRO ≅ FSDP under GSPMD).  ZeRO stage 2 maps to ``SHARD_GRAD_OP``.
- ``parallelism_config_*`` keys (reference cluster.py:500-546) map 1:1 onto
  the mesh axes.
- DeepSpeed/FSDP cpu-offload flags fold into ``fsdp_offload_params``.
- ``mixed_precision: fp8`` is refused by name (``utils/constants.FP8_REFUSED``).
"""

from __future__ import annotations

import argparse
from pathlib import Path

import yaml

from ..utils.constants import FP8_REFUSED
from .config import LaunchConfig, default_config_path

# Reference keys that are deliberately dropped, with the reason shown to the
# user.  Anything not mapped and not listed here earns an "unknown key"
# warning so silent drift in the reference format is visible.
_DROPPED = {
    "compute_environment": "TPU build has one execution model (local or multi-host pod)",
    "main_training_function": "notebook-launcher detail; not needed by accelerate-tpu launch",
    "rdzv_backend": "torchrun rendezvous; JAX coordination uses coordinator ip:port",
    "same_network": "torchrun rendezvous detail",
    "gpu_ids": "GPU-only; TPU topology comes from the runtime",
    "downcast_bf16": "torch_xla flag; bf16 policy is mixed_precision on TPU",
    "enable_cpu_affinity": "NUMA pinning is host-runtime managed on TPU VMs",
    "tpu_env": "legacy torch_xla pod launcher detail",
    "tpu_use_cluster": "legacy torch_xla pod launcher detail",
    "tpu_use_sudo": "legacy torch_xla pod launcher detail",
    "tpu_name": "gcloud admin detail (see `accelerate-tpu tpu-config`)",
    "tpu_zone": "gcloud admin detail (see `accelerate-tpu tpu-config`)",
    "commands": "gcloud admin detail",
    "command_file": "gcloud admin detail",
    "mpirun_config": "MPI launcher is GPU/CPU-cluster specific",
    "megatron_lm_config": "Megatron 3D parallelism maps onto the GSPMD mesh axes instead",
    "dynamo_config": "torch.compile config; XLA compiles the whole step on TPU",
    "ipex_config": "Intel extension; not applicable",
    "mpirun_hostfile": "MPI launcher detail",
    "sagemaker_config": "SageMaker launcher not supported",
    "additional_args": "SageMaker launcher detail",
}

_FSDP_STRATEGY_MAP = {
    # reference fsdp_sharding_strategy values (dataclasses.py FullyShardedDataParallelPlugin)
    "FULL_SHARD": "FULL_SHARD",
    "SHARD_GRAD_OP": "SHARD_GRAD_OP",
    "NO_SHARD": "NO_SHARD",
    "HYBRID_SHARD": "HYBRID_SHARD",
    "HYBRID_SHARD_ZERO2": "HYBRID_SHARD",
    "1": "FULL_SHARD", "2": "SHARD_GRAD_OP", "3": "NO_SHARD", "4": "HYBRID_SHARD",
}


def convert(raw: dict) -> tuple[LaunchConfig, list[str]]:
    """Convert a parsed reference config dict -> (LaunchConfig, notes)."""
    notes: list[str] = []
    cfg = LaunchConfig()
    handled = set()

    def take(key, default=None):
        handled.add(key)
        return raw.get(key, default)

    cfg.num_processes = int(take("num_processes", 1) or 1)
    cfg.num_machines = int(take("num_machines", 1) or 1)
    rank = take("machine_rank")
    cfg.machine_rank = int(rank) if rank is not None and cfg.num_machines > 1 else None
    ip = take("main_process_ip")
    cfg.main_process_ip = str(ip) if ip else None
    port = take("main_process_port")
    cfg.main_process_port = int(port) if port else None
    cfg.use_cpu = bool(take("use_cpu", False))
    cfg.debug = bool(take("debug", False))

    mp = str(take("mixed_precision", "no") or "no").lower()
    if mp == "fp16":
        notes.append("mixed_precision fp16 -> bf16 (TPU-native; fp16 loss-scaling unneeded)")
        mp = "bf16"
    if mp == "fp8":
        raise ValueError(f"mixed_precision: fp8 — {FP8_REFUSED}")
    cfg.mixed_precision = mp

    dist = str(take("distributed_type", "NO") or "NO").upper()
    if dist == "FSDP":
        cfg.use_fsdp = True
    elif dist == "DEEPSPEED":
        pass  # zero_stage decides below
    elif dist in ("MULTI_GPU", "MULTI_CPU", "MULTI_XPU", "MULTI_MLU", "MULTI_NPU",
                  "MULTI_MUSA", "MULTI_SDAA", "MULTI_HPU", "XLA", "TPU", "NO"):
        notes.append(f"distributed_type {dist} -> data parallelism over the dp mesh axis")
    else:
        notes.append(f"distributed_type {dist!r} not recognized; defaulting to data parallel")

    fsdp = take("fsdp_config") or {}
    fsdp_handled = set()
    if fsdp:
        strategy = str(fsdp.get("fsdp_sharding_strategy", "FULL_SHARD"))
        cfg.fsdp_sharding_strategy = _FSDP_STRATEGY_MAP.get(strategy, "FULL_SHARD")
        cfg.fsdp_offload_params = bool(fsdp.get("fsdp_offload_params", False))
        cfg.fsdp_activation_checkpointing = bool(
            fsdp.get("fsdp_activation_checkpointing", False)
        )
        fsdp_handled |= {"fsdp_sharding_strategy", "fsdp_offload_params",
                         "fsdp_activation_checkpointing"}
        for k in ("fsdp_auto_wrap_policy", "fsdp_transformer_layer_cls_to_wrap"):
            fsdp_handled.add(k)
            if fsdp.get(k):
                notes.append(
                    f"{k}={fsdp[k]!r} dropped: GSPMD shards every weight by "
                    "NamedSharding; no wrap policy needed"
                )
        # remaining fsdp_* knobs are torch-FSDP execution details (prefetch,
        # sync_module_states, state_dict_type, use_orig_params, ...)
        for k in sorted(set(fsdp) - fsdp_handled):
            notes.append(f"dropped fsdp_config.{k}: torch-FSDP execution detail "
                         "with no GSPMD analog")

    ds = take("deepspeed_config") or {}
    if ds:
        if ds.get("deepspeed_config_file"):
            raise ValueError(
                "this config delegates to a DeepSpeed JSON file "
                f"({ds['deepspeed_config_file']}), which from-accelerate does not "
                "read — converting without it would silently mis-state the ZeRO "
                "stage and offload settings.  Inline zero_stage / offload_* keys "
                "into the accelerate YAML and re-run."
            )
        stage = int(ds.get("zero_stage", 2))
        if stage >= 2:
            cfg.use_fsdp = True
            cfg.fsdp_sharding_strategy = "FULL_SHARD" if stage == 3 else "SHARD_GRAD_OP"
        if str(ds.get("offload_optimizer_device", "none")) != "none" or \
                str(ds.get("offload_param_device", "none")) != "none":
            cfg.fsdp_offload_params = True
        if ds.get("gradient_accumulation_steps"):
            cfg.gradient_accumulation_steps = int(ds["gradient_accumulation_steps"])
        notes.append(f"deepspeed zero_stage {stage} -> GSPMD sharding "
                     f"({cfg.fsdp_sharding_strategy})")
        ds_handled = {"deepspeed_config_file", "zero_stage", "offload_optimizer_device",
                      "offload_param_device", "gradient_accumulation_steps",
                      "gradient_clipping", "zero3_init_flag", "zero3_save_16bit_model"}
        for k in sorted(set(ds) - ds_handled):
            notes.append(f"dropped deepspeed_config.{k}: DeepSpeed engine detail "
                         "with no TPU analog")

    pc = take("parallelism_config") or {}
    prefix = "parallelism_config_"
    axis_map = {"dp_replicate_size": "dp_replicate_size", "dp_shard_size": "dp_shard_size",
                "tp_size": "tp_size", "cp_size": "cp_size", "sp_size": "sp_size"}
    pc_handled = set()
    for ref_key, our_key in axis_map.items():
        for key in (prefix + ref_key, ref_key):
            if key in pc:
                setattr(cfg, our_key, int(pc[key]))
                pc_handled.add(key)
                break
    for k in sorted(set(pc) - pc_handled):
        notes.append(f"dropped parallelism_config.{k}: backend/strategy detail "
                     "(TPU CP/SP strategies are chosen at the attention layer)")

    gas = take("gradient_accumulation_steps")
    if gas:
        cfg.gradient_accumulation_steps = int(gas)

    for key in list(raw):
        if key in handled:
            continue
        if key in _DROPPED:
            notes.append(f"dropped {key}: {_DROPPED[key]}")
        else:
            notes.append(f"unknown key {key!r} ignored")
    return cfg, notes


def from_accelerate_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = "Convert a HuggingFace Accelerate config YAML to accelerate-tpu format."
    if subparsers is not None:
        parser = subparsers.add_parser(
            "from-accelerate", description=description, help=description
        )
    else:
        parser = argparse.ArgumentParser("accelerate-tpu from-accelerate", description=description)
    parser.add_argument("config_file", help="Path to the reference accelerate YAML config.")
    parser.add_argument(
        "--output", default=None,
        help=f"Where to write the converted config (default {default_config_path()})",
    )
    if subparsers is not None:
        parser.set_defaults(func=from_accelerate_command)
    return parser


def from_accelerate_command(args):
    with open(args.config_file) as f:
        raw = yaml.safe_load(f) or {}
    cfg, notes = convert(raw)
    path = cfg.save(Path(args.output) if args.output else default_config_path())
    for note in notes:
        print(f"  - {note}")
    print(f"converted config saved at {path}")


def main():
    args = from_accelerate_command_parser().parse_args()
    from_accelerate_command(args)


if __name__ == "__main__":
    main()
