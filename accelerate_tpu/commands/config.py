"""``accelerate-tpu config`` — launch configuration store + questionnaire.

TPU-native re-design of reference ``commands/config/`` (cluster.py:924-line
interactive flow, config_args.py YAML dataclass).  One flat dataclass replaces
the reference's cluster/sagemaker split: on TPU there is exactly one execution
model (one process per host over an ICI/DCN mesh), so the questionnaire is a
short, linear flow instead of a 900-line decision tree.

Config precedence (reference contract, commands/launch.py:1196): CLI flag >
YAML config file > built-in default.  The file location honors
``ACCELERATE_CONFIG_FILE`` and defaults to
``~/.cache/accelerate_tpu/default_config.yaml``.
"""

from __future__ import annotations

import argparse
import os
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Optional

import yaml

from ..utils.constants import MIXED_PRECISION_CHOICES

DEFAULT_CONFIG_DIR = Path(
    os.environ.get("ACCELERATE_TPU_CACHE", Path.home() / ".cache" / "accelerate_tpu")
)
DEFAULT_CONFIG_FILE = DEFAULT_CONFIG_DIR / "default_config.yaml"

# Fields the launcher transports to workers as env vars (utils/launch.py).
CONFIG_VERSION = 1


@dataclass
class LaunchConfig:
    """The persisted launch configuration (reference config_args.py:40
    ``BaseConfig``/``ClusterConfig``)."""

    config_version: int = CONFIG_VERSION
    # -- process topology (one process per host on TPU) --------------------
    num_processes: int = 1
    # num_machines decides local-spawn vs multi-host (reference ClusterConfig
    # num_machines); machine_rank stays None until a host identifies itself —
    # a silent default of 0 would make every host rank 0.
    num_machines: int = 1
    machine_rank: Optional[int] = None
    main_process_ip: Optional[str] = None
    main_process_port: Optional[int] = None
    # -- execution ---------------------------------------------------------
    use_cpu: bool = False
    mixed_precision: str = "no"  # no | bf16 | fp16
    gradient_accumulation_steps: int = 1
    debug: bool = False
    # gang restarts after a worker crash (torchrun-elasticity analog for the
    # local spawner; crashed state is recovered via checkpoint-resume)
    max_restarts: int = 0
    # -- parallelism axes (PARALLELISM_CONFIG_* transport) -----------------
    # dcn: cross-slice data parallelism (the explicit DCN outer mesh axis);
    # auto-filled from slice metadata (MEGASCALE_NUM_SLICES) when left at 1
    dcn_size: int = 1
    dp_replicate_size: int = 1
    dp_shard_size: int = -1  # -1: infer remainder at runtime
    cp_size: int = 1
    sp_size: int = 1
    tp_size: int = 1
    ep_size: int = 1
    pp_size: int = 1
    # -- FSDP/ZeRO sharding knobs (FSDP_* transport) -----------------------
    use_fsdp: bool = False
    fsdp_sharding_strategy: str = "FULL_SHARD"
    fsdp_offload_params: bool = False
    fsdp_activation_checkpointing: bool = False
    # -- managed-cloud defaults for `cloud-launch` (the reference's
    # SageMakerConfig questionnaire analog: commands/config/sagemaker.py —
    # stored once, every cloud submission reuses them) -------------------
    cloud_backend: Optional[str] = None  # "gke" | "queued-resources"
    cloud_tpu_type: Optional[str] = None
    cloud_image: Optional[str] = None
    cloud_tpu_topology: Optional[str] = None
    cloud_zone: Optional[str] = None
    cloud_project: Optional[str] = None
    cloud_chips_per_host: Optional[int] = None
    # -- free-form env passthrough ----------------------------------------
    env: dict = field(default_factory=dict)

    def save(self, path: os.PathLike | str = DEFAULT_CONFIG_FILE) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            yaml.safe_dump(asdict(self), f, sort_keys=False)
        return path

    @classmethod
    def load(cls, path: Optional[os.PathLike | str] = None) -> "LaunchConfig":
        path = Path(path or default_config_path())
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
        known = {f_.name for f_ in cls.__dataclass_fields__.values()}  # type: ignore[attr-defined]
        unknown = {k: v for k, v in raw.items() if k not in known}
        cfg = cls(**{k: v for k, v in raw.items() if k in known})
        # Forward-compat: stash unknown keys into env passthrough untouched.
        if unknown:
            cfg.env.update({k: str(v) for k, v in unknown.items()})
        # Migration guard: configs written before num_machines existed used a
        # stored main_process_ip to mean "multi-host".  Loading one under the
        # new semantics would silently spawn locally with duplicate ranks —
        # make the user re-state their topology instead.
        if raw.get("main_process_ip") and "num_machines" not in raw:
            raise ValueError(
                f"{path} predates the num_machines field: it stores a "
                "main_process_ip but no host count.  Re-run `accelerate-tpu "
                "config` (or add `num_machines: N` to the file) to state "
                "whether this is a multi-host job."
            )
        return cfg


def default_config_path() -> Path:
    return Path(os.environ.get("ACCELERATE_CONFIG_FILE", DEFAULT_CONFIG_FILE))


def load_config_or_default(path: Optional[str] = None) -> LaunchConfig:
    """Load the YAML config if present, else built-in defaults."""
    target = Path(path) if path else default_config_path()
    if target.is_file():
        return LaunchConfig.load(target)
    return LaunchConfig()


# ---------------------------------------------------------------------------
# Interactive questionnaire (reference commands/config/cluster.py)
# ---------------------------------------------------------------------------


def _ask(prompt: str, default, cast=str):
    raw = input(f"{prompt} [{default}]: ").strip()
    if not raw:
        return default
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "y")
    return cast(raw)


def _ask_choice(prompt: str, choices: tuple, default):
    """Choice question: arrow-key bullet menu on a TTY (reference
    ``commands/menu/`` `_ask_options` UI), validated numbered prompt
    otherwise (pipes/CI)."""
    from .menu import select

    return select(prompt, choices, default)


def _ask_pos_int(prompt: str, default: int) -> int:
    while True:
        try:
            val = _ask(prompt, default, int)
        except ValueError:
            print("  -> enter an integer")
            continue
        if val >= 1:
            return val
        print("  -> must be >= 1")


def interactive_config() -> LaunchConfig:
    """Validated questionnaire covering every field the launcher transports
    (reference commands/config/cluster.py questionnaire; the vendor-engine
    branches collapse into the mesh-axis questions)."""
    cfg = LaunchConfig()
    print("accelerate-tpu configuration (enter to accept defaults)")
    cfg.num_processes = _ask_pos_int("How many processes (= TPU hosts)?", 1)
    if cfg.num_processes > 1:
        cfg.num_machines = _ask_pos_int(
            "How many machines (1 = spawn all processes on this host)?", 1
        )
        if cfg.num_machines > 1:
            cfg.main_process_ip = _ask("Coordinator (process-0) IP?", "127.0.0.1")
            cfg.main_process_port = _ask_pos_int("Coordinator port?", 29500)
            cfg.dcn_size = _ask_pos_int(
                "How many slices (cross-slice DCN data-parallel axis; 1 = "
                "one slice / auto-discover)?", 1
            )
    cfg.use_cpu = _ask("Force CPU (debug runs without an accelerator)?", False, bool)
    cfg.debug = _ask("Enable debug mode (collective shape verification)?", False, bool)
    cfg.mixed_precision = _ask_choice(
        "Mixed precision", tuple(MIXED_PRECISION_CHOICES), "bf16"
    )
    cfg.gradient_accumulation_steps = _ask_pos_int("Gradient accumulation steps?", 1)

    # -- model-parallel mesh axes, validated as ParallelismConfig would ----
    cfg.tp_size = _ask_pos_int("Tensor-parallel size?", 1)
    while True:
        cfg.cp_size = _ask_pos_int("Context-parallel size (ring attention)?", 1)
        cfg.sp_size = _ask_pos_int("Sequence-parallel size (Ulysses)?", 1)
        if cfg.cp_size > 1 and cfg.sp_size > 1:
            print("  -> cp and sp are alternative long-context mechanisms; "
                  "pick one (cp: ring attention, sp: Ulysses)")
            continue
        break
    cfg.ep_size = _ask_pos_int("Expert-parallel size (MoE)?", 1)
    cfg.pp_size = _ask_pos_int("Pipeline-parallel size?", 1)
    cfg.dp_replicate_size = _ask_pos_int(
        "Data-parallel replicate size (HSDP outer/DCN axis)?", 1
    )
    # device count per host is unknown at config time, so divisibility is
    # re-validated by ParallelismConfig at launch; surface the product here
    model_axes = (cfg.tp_size * cfg.cp_size * cfg.sp_size * cfg.ep_size
                  * cfg.pp_size * cfg.dp_replicate_size * cfg.dcn_size)
    print(f"  (model-axis product: {model_axes}; dp_shard fills the remainder)")

    cfg.use_fsdp = _ask("Shard parameters/optimizer state (FSDP/ZeRO)?", True, bool)
    if cfg.use_fsdp:
        cfg.fsdp_sharding_strategy = _ask_choice(
            "Sharding strategy",
            ("FULL_SHARD", "SHARD_GRAD_OP", "HYBRID_SHARD", "NO_SHARD"),
            "FULL_SHARD",
        )
        cfg.fsdp_offload_params = _ask(
            "ZeRO-offload (optimizer state + fp32 masters in host memory)?",
            False, bool,
        )
        cfg.fsdp_activation_checkpointing = _ask(
            "Activation checkpointing (remat)?", False, bool
        )
    cfg.dp_shard_size = -1 if cfg.use_fsdp else 1
    print(
        "Mesh: dcn=%d x dp_replicate=%d x dp_shard=%s x pp=%d x cp=%d x sp=%d x tp=%d x ep=%d"
        % (cfg.dcn_size, cfg.dp_replicate_size,
           "auto" if cfg.dp_shard_size == -1 else cfg.dp_shard_size,
           cfg.pp_size, cfg.cp_size, cfg.sp_size, cfg.tp_size, cfg.ep_size)
    )

    # managed-cloud defaults (the reference SageMaker questionnaire analog):
    # stored once, `cloud-launch` reuses them so submission is one command
    if _ask("Configure managed-cloud defaults for `cloud-launch`?", False, bool):
        cfg.cloud_backend = _ask_choice(
            "Cloud backend", ("gke", "queued-resources"), "gke"
        )
        cfg.cloud_tpu_type = _ask(
            "TPU type (GKE accelerator / queued-resource accelerator-type)?",
            "tpu-v5-lite-podslice" if cfg.cloud_backend == "gke" else "v5litepod-8",
        )
        if cfg.cloud_backend == "gke":
            cfg.cloud_image = _ask("Container image?", "python:3.11")
            cfg.cloud_tpu_topology = _ask("Slice topology label (e.g. 2x4)?", "2x4")
            cfg.cloud_chips_per_host = _ask_pos_int("Chips per host?", 4)
        else:
            cfg.cloud_zone = _ask("GCP zone?", "us-west4-a")
            cfg.cloud_project = _ask("GCP project (empty = gcloud default)?", "") or None
    return cfg


# ---------------------------------------------------------------------------
# argparse wiring
# ---------------------------------------------------------------------------


def config_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = "Create a launch config file for accelerate-tpu."
    if subparsers is not None:
        parser = subparsers.add_parser("config", description=description, help=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu config", description=description)
    parser.add_argument(
        "--config_file", default=None,
        help=f"Where to save the config (default {DEFAULT_CONFIG_FILE})",
    )
    parser.add_argument(
        "--default", action="store_true",
        help="Write the non-interactive default config (single host, bf16, FSDP).",
    )
    if subparsers is not None:
        parser.set_defaults(func=config_command)
    return parser


def config_command(args):
    if args.default:
        cfg = LaunchConfig(mixed_precision="bf16", use_fsdp=True, dp_shard_size=-1)
    else:
        cfg = interactive_config()
    path = cfg.save(args.config_file or default_config_path())
    print(f"accelerate-tpu config saved at {path}")


def main():
    args = config_command_parser().parse_args()
    config_command(args)


if __name__ == "__main__":
    main()
