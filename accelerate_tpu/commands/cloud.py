"""``accelerate-tpu cloud-launch`` — managed-cloud job submission.

The reference ships a SageMaker launcher (reference commands/launch.py:1176:
config → HuggingFace-estimator args → ``fit()``, credentials/region from
``SageMakerConfig``, script args converted to hyperparameters).  The
TPU-native analog of "hand this training to a managed cloud service" is a
**GKE JobSet** (the recommended way to run multi-host TPU jobs on Kubernetes)
or a **Cloud TPU queued resource**; this command renders either from the same
merged :class:`LaunchConfig` the local launcher uses — the
``ACCELERATE_*``/``PARALLELISM_CONFIG_*`` env transport is the one contract,
so a job that runs under ``accelerate-tpu launch`` runs unchanged in the
rendered manifest.

Like the reference (which raises unless ``sagemaker`` is installed),
``--submit`` hands the manifest to ``kubectl``/``gcloud`` only when the tool
is present; the default prints/writes the manifest for review.
"""

from __future__ import annotations

import argparse
import shlex
import shutil
import subprocess
import sys
from typing import Optional

from .config import LaunchConfig, load_config_or_default
from .launch import _merge_args_into_config, _validate
from ..utils.constants import MIXED_PRECISION_CHOICES
from ..utils.launch import config_env

# Accelerator counts per host for common TPU types (public Cloud TPU docs):
# v5e hosts expose 1/4/8 chips depending on slice; we default to 4 and let
# --chips_per_host override.
_DEFAULT_CHIPS_PER_HOST = 4


def _transport_env(args, config: LaunchConfig) -> dict[str, str]:
    """The framework env contract from the config ALONE — the operator
    shell's ambient ACCELERATE_* residue must not leak into manifests."""
    return dict(sorted(config_env(config).items()))


def _worker_command(args) -> list[str]:
    cmd = ["python", args.training_script]
    cmd.extend(args.training_script_args or [])
    return cmd


def render_jobset_yaml(
    args,
    config: LaunchConfig,
    *,
    tpu_type: str,
    image: str,
    name: str = "accelerate-tpu-job",
    chips_per_host: int = _DEFAULT_CHIPS_PER_HOST,
    tpu_topology: str = "2x4",
) -> str:
    """A GKE JobSet manifest: one replicated Job, ``num_machines``
    completions in Indexed mode (the JOB_COMPLETION_INDEX is the machine
    rank), TPU nodeSelectors, and the env transport inlined.  Worker-crash
    recovery maps to JobSet's ``failurePolicy.maxRestarts`` — it recreates
    ALL child jobs, matching the local launcher's whole-gang restart
    semantics (jax.distributed cannot survive losing a member)."""
    env = _transport_env(args, config)
    env_yaml = "\n".join(
        f"                - name: {k}\n                  value: {v!r}" for k, v in env.items()
    )
    # rank/coordinator come from the JobSet runtime, not the render
    runtime_env = (
        "                - name: ACCELERATE_NUM_PROCESSES\n"
        f"                  value: '{config.num_machines}'\n"
        "                - name: ACCELERATE_PROCESS_ID\n"
        "                  valueFrom:\n"
        "                    fieldRef:\n"
        "                      fieldPath: metadata.annotations['batch.kubernetes.io/job-completion-index']\n"
        "                - name: ACCELERATE_COORDINATOR_ADDRESS\n"
        f"                  value: '{name}-workers-0-0.{name}:8476'"
    )
    cmd = ", ".join(repr(c) for c in _worker_command(args))
    return f"""apiVersion: jobset.x-k8s.io/v1alpha2
kind: JobSet
metadata:
  name: {name}
spec:
  failurePolicy:
    maxRestarts: {getattr(config, "max_restarts", 0)}
  replicatedJobs:
    - name: workers
      replicas: 1
      template:
        spec:
          parallelism: {config.num_machines}
          completions: {config.num_machines}
          completionMode: Indexed
          backoffLimit: 0
          template:
            spec:
              restartPolicy: Never
              nodeSelector:
                cloud.google.com/gke-tpu-accelerator: {tpu_type}
                cloud.google.com/gke-tpu-topology: {tpu_topology}
              containers:
              - name: worker
                image: {image}
                command: [{cmd}]
                env:
{env_yaml}
{runtime_env}
                resources:
                  limits:
                    google.com/tpu: {chips_per_host}
"""


def render_queued_resource_command(
    args,
    config: LaunchConfig,
    *,
    tpu_type: str,
    name: str = "accelerate-tpu-job",
    zone: Optional[str] = None,
    project: Optional[str] = None,
) -> list[str]:
    """The ``gcloud`` line creating a Cloud TPU queued resource whose startup
    script exports the env transport and execs the training script on every
    host (Cloud TPU runs the same command on each worker — exactly the
    multi-host contract of ``accelerate-tpu launch``)."""
    env = _transport_env(args, config)
    exports = "; ".join(f"export {k}={shlex.quote(v)}" for k, v in env.items())
    script = f"{exports}; {shlex.join(_worker_command(args))}"
    cmd = [
        "gcloud", "compute", "tpus", "queued-resources", "create", name,
        f"--accelerator-type={tpu_type}",
        "--runtime-version=tpu-ubuntu2204-base",
        f"--node-id={name}-node",
    ]
    if zone:
        cmd.append(f"--zone={zone}")
    if project:
        cmd.append(f"--project={project}")
    # gcloud splits --metadata on commas; the ^|^ alternate-delimiter prefix
    # keeps a script containing commas (e.g. --betas 0.9,0.95) intact
    cmd.append(f"--metadata=^|^startup-script={script}")
    return cmd


def cloud_launch_command(args) -> None:
    config = _merge_args_into_config(args, load_config_or_default(args.config_file))
    if config.num_machines < 1:
        config.num_machines = 1
    if config.num_processes < config.num_machines:
        config.num_processes = config.num_machines  # one process per TPU host
    _validate(config)
    if not args.training_script.endswith(".py"):
        # same constraint as the reference's SageMaker path (launch.py:670)
        raise ValueError("cloud-launch needs a python training script file")

    # flag > stored questionnaire answer (cloud_* in the config file, the
    # SageMakerConfig analog) > hard default
    backend = args.backend or getattr(config, "cloud_backend", None) or "gke"
    tpu_type = args.tpu_type or getattr(config, "cloud_tpu_type", None) or (
        "tpu-v5-lite-podslice" if backend == "gke" else "v5litepod-8"
    )
    if backend == "gke":
        manifest = render_jobset_yaml(
            args, config, tpu_type=tpu_type,
            image=args.image or getattr(config, "cloud_image", None) or "python:3.11",
            name=args.name,
            chips_per_host=args.chips_per_host
            or getattr(config, "cloud_chips_per_host", None) or _DEFAULT_CHIPS_PER_HOST,
            tpu_topology=args.tpu_topology
            or getattr(config, "cloud_tpu_topology", None) or "2x4",
        )
        if args.output:
            with open(args.output, "w") as f:
                f.write(manifest)
            print(f"JobSet manifest written to {args.output}")
        else:
            print(manifest)
        if args.submit:
            submit_cmd = ["kubectl", "apply", "-f", args.output or "-"]
            if args.dry_run:
                # client-side validation only: kubectl parses the manifest
                # and prints what WOULD be created, nothing reaches the
                # cluster — the CI-safe path the submit test asserts
                submit_cmd.append("--dry-run=client")
            if args.dry_run and shutil.which("kubectl") is None:
                print(f"DRY RUN (kubectl not on PATH): {shlex.join(submit_cmd)}")
                return
            if shutil.which("kubectl") is None:
                raise ImportError(
                    "--submit needs kubectl on PATH (or drop --submit and "
                    "apply the printed manifest yourself)"
                )
            subprocess.run(submit_cmd,
                           input=None if args.output else manifest,
                           text=True, check=True)
    else:  # queued-resources
        cmd = render_queued_resource_command(
            args, config, tpu_type=tpu_type, name=args.name,
            zone=args.zone or getattr(config, "cloud_zone", None),
            project=args.project or getattr(config, "cloud_project", None),
        )
        print(shlex.join(cmd))
        if args.submit:
            if args.dry_run:
                # gcloud has no universal --dry-run: the contract is "print
                # the exact submission line, touch nothing"
                print(f"DRY RUN: {shlex.join(cmd)}")
                return
            if shutil.which("gcloud") is None:
                raise ImportError("--submit needs gcloud on PATH")
            subprocess.run(cmd, check=True)


def cloud_command_parser(subparsers=None) -> argparse.ArgumentParser:
    description = "Render (or submit) a managed-cloud TPU training job (GKE JobSet / queued resource)."
    if subparsers is not None:
        parser = subparsers.add_parser("cloud-launch", description=description, help=description)
    else:
        parser = argparse.ArgumentParser("accelerate-tpu cloud-launch", description=description)
    parser.add_argument("--config_file", default=None)
    parser.add_argument("--backend", choices=["gke", "queued-resources"], default=None,
                        help="default: the config file's cloud_backend, else gke")
    parser.add_argument("--tpu_type", dest="tpu_type", default=None,
                        help="GKE accelerator type / queued-resource accelerator-type "
                             "(default: config cloud_tpu_type).")
    parser.add_argument("--image", default=None,
                        help="Container image with your training environment (gke; "
                             "default: config cloud_image).")
    parser.add_argument("--name", default="accelerate-tpu-job")
    parser.add_argument("--chips_per_host", type=int, default=None)
    parser.add_argument("--tpu_topology", default=None,
                        help="GKE slice topology label (e.g. 2x4, 4x4, 4x8) — must match "
                             "the node pool; see `gcloud container node-pools describe`.")
    parser.add_argument("--zone", default=None)
    parser.add_argument("--project", default=None)
    parser.add_argument("--num_machines", type=int, default=None)
    parser.add_argument("--num_processes", type=int, default=None)
    parser.add_argument("--mixed_precision", default=None, choices=MIXED_PRECISION_CHOICES)
    parser.add_argument("--gradient_accumulation_steps", type=int, default=None)
    parser.add_argument("--output", "-o", default=None, help="Write the manifest here instead of stdout.")
    parser.add_argument("--submit", action="store_true",
                        help="Apply via kubectl / gcloud (must be on PATH).")
    parser.add_argument("--dry-run", dest="dry_run", action="store_true",
                        help="With --submit: validate client-side (kubectl "
                             "--dry-run=client) or print the exact gcloud "
                             "line without executing it.")
    parser.add_argument("training_script")
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)

    # attrs _merge_args_into_config reads unconditionally but that make no
    # sense as cloud flags
    parser.set_defaults(cpu=False, debug=False)
    if subparsers is not None:
        parser.set_defaults(func=cloud_launch_command)
    return parser
