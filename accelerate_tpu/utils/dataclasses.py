"""Enums, plugins and kwargs handlers — the declarative config surface.

TPU-native re-design of the reference's ``utils/dataclasses.py`` (3,200+ LoC of
plugins/enums, reference utils/dataclasses.py).  The big behavioral difference:
on GSPMD every parallelism strategy is a *sharding configuration of one
mechanism*, so the DeepSpeed/Megatron/FSDP plugin zoo collapses into
``ShardingPlugin``-style dataclasses that produce :class:`jax.sharding`
annotations instead of wrapping engines.

Every plugin reads ``ACCELERATE_*`` environment defaults in ``__post_init__``,
matching the reference's env-as-config-transport contract
(reference utils/dataclasses.py:1217-1260, parallelism_config.py:274-289).
"""

from __future__ import annotations

import copy
import dataclasses
import enum
import functools
import os
import warnings
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Iterable, Optional

from .environment import parse_flag_from_env


class EnumWithContains(enum.EnumMeta):
    """Metaclass so ``"bf16" in MixedPrecisionType`` works (reference :585)."""

    def __contains__(cls, item):
        try:
            cls(item)
        except ValueError:
            return False
        return True


class BaseEnum(str, enum.Enum, metaclass=EnumWithContains):
    def __str__(self):
        return self.value

    @classmethod
    def list(cls):
        return list(map(str, cls))


class DistributedType(BaseEnum):
    """Topology of the current run (reference dataclasses.py:613-645).

    The reference enumerates one value per engine (DDP/FSDP/DeepSpeed/...);
    here strategies are sharding configs, so the enum only describes the
    *process/device topology*.
    """

    NO = "NO"                    # single device
    MULTI_DEVICE = "MULTI_DEVICE"  # one process, many local devices (single host)
    MULTI_HOST = "MULTI_HOST"    # jax.distributed world, one process per host


class MixedPrecisionType(BaseEnum):
    """reference dataclasses.py:647 — 'no'|'fp16'|'bf16' (the reference's 'fp8' is
    refused by name: ``state.py``)."""

    NO = "no"
    FP16 = "fp16"
    BF16 = "bf16"


class ShardingStrategy(BaseEnum):
    """How parameters/optimizer state are laid out across ``dp_shard``.

    Capability-parity with reference FSDP ``sharding_strategy``
    (dataclasses.py:1566) and DeepSpeed ``zero_stage`` (:1164):
    NO_SHARD≅DDP/stage-0, SHARD_GRAD_OP≅ZeRO-2, FULL_SHARD≅ZeRO-3/FSDP,
    HYBRID_SHARD≅HSDP (shard intra-slice over ICI, replicate over DCN).
    """

    NO_SHARD = "NO_SHARD"
    SHARD_GRAD_OP = "SHARD_GRAD_OP"
    FULL_SHARD = "FULL_SHARD"
    HYBRID_SHARD = "HYBRID_SHARD"


class RNGType(BaseEnum):
    """Which RNG streams to synchronize/checkpoint (reference :600)."""

    JAX = "jax"
    NUMPY = "numpy"
    PYTHON = "python"
    TORCH = "torch"
    GENERATOR = "generator"


class CheckpointFormat(BaseEnum):
    """FULL = merged single-host arrays; SHARDED = per-shard OCDBT/tensorstore
    (capability parity with reference ``StateDictType`` full/sharded,
    dataclasses.py:1601)."""

    FULL = "FULL_STATE"
    SHARDED = "SHARDED_STATE"


class LoggerType(BaseEnum):
    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    COMETML = "comet_ml"
    MLFLOW = "mlflow"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    SWANLAB = "swanlab"
    TRACKIO = "trackio"


# ---------------------------------------------------------------------------
# Kwargs handlers (reference dataclasses.py:68-560)
# ---------------------------------------------------------------------------


@dataclass
class KwargsHandler:
    """Base for objects that tweak a subsystem's kwargs (reference :68)."""

    def to_dict(self):
        return copy.deepcopy(self.__dict__)

    def to_kwargs(self):
        """Only the fields that differ from the default instance."""
        default_dict = self.__class__().to_dict()
        this_dict = self.to_dict()
        return {k: v for k, v in this_dict.items() if default_dict[k] != v}


@dataclass
class AutocastKwargs(KwargsHandler):
    """Compute-dtype policy knobs (reference AutocastKwargs :113)."""

    enabled: bool = True
    cache_enabled: bool = True  # kept for API parity; XLA handles caching


@dataclass
class GradSyncKwargs(KwargsHandler):
    """Analog of ``DistributedDataParallelKwargs`` (reference :155).

    On GSPMD the all-reduce is compiler-inserted; the surviving knobs control
    *how* gradients cross ``dp``: reduction dtype compression (the DDP comm
    hook analog, reference DDPCommunicationHookType :134) and bucketing hints.
    """

    comm_dtype: Optional[str] = None  # None | "bf16" | "fp16" — grads cast before psum
    # mean (DDP semantics) vs sum across dp: GSPMD's implicit reduction
    # yields the global-mean grad, so False rescales the tree by the dp
    # world size before clip/update (honored in both the dense and the
    # powersgd train-step paths)
    average_grads: bool = True
    # None: grads carry master (fp32) width through clip/update (torch-DDP
    # semantics).  "bf16": differentiate wrt the compute-width param copy so
    # the whole grad tree stays bf16 — halves grad HBM; the per-leaf optimizer
    # math still promotes against its fp32 state (MaxText-style).  Requires
    # mixed_precision="bf16" (fp16 needs fp32 unscaling, see prepare_train_step).
    grad_dtype: Optional[str] = None
    # "powersgd": error-feedback low-rank compression of the dp-axis grad
    # reduction (reference DDPCommunicationHookType.POWER_SGD analog; engine:
    # parallel/powersgd.py).  ``rank`` is the factor rank — wire bytes per
    # eligible [n, m] leaf drop from n*m to rank*(n+m) (the P psum moves
    # n*rank floats, the Q psum m*rank — matching wire_bytes_report).
    compression: Optional[str] = None
    rank: int = 4
    # Hierarchical ICI->DCN reduction (parallel/hierarchical.py) for meshes
    # with a non-trivial `dcn` (cross-slice) axis: reduce-scatter inside the
    # slice over ICI, all-reduce only the sharded slab over DCN, all-gather
    # back — replacing the flat joint-axis psum whose DCN hop would carry
    # ici_size redundant full-gradient copies.  None = auto (engage when the
    # mesh has dcn > 1 and the config is compatible: pure data parallelism
    # with replicated params, like `compression`); False = never (flat psum
    # even across slices); True = require (raise on incompatible configs
    # instead of falling back).
    hierarchical: Optional[bool] = None
    # "powersgd": compress the hierarchical path's cross-slice (DCN) hop —
    # each device's slab crosses as its rank-`rank` factors with per-device
    # error feedback.  Requires the hierarchical path (dcn axis present and
    # not disabled); ICI legs stay uncompressed (they are ~7x cheaper per
    # byte, and the EF residual would have to survive two codecs).
    dcn_compression: Optional[str] = None


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """Coordinator init knobs (reference InitProcessGroupKwargs :273)."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    timeout: timedelta = field(default_factory=lambda: timedelta(seconds=1800))
    initialization_timeout: Optional[int] = None


@dataclass
class ProfileKwargs(KwargsHandler):
    """Declarative profiler config → a step-scheduled ``jax.profiler`` trace
    (reference ProfileKwargs :484 builds torch.profiler.profile; engine:
    ``utils/profiler.py``).

    ``wait``/``warmup``/``active`` define the per-cycle step schedule —
    each cycle traces exactly steps ``[wait+warmup, wait+warmup+active)``
    as counted by ``profiler.step()`` calls; ``repeat`` bounds the number
    of cycles (0 = cycle until the block ends, each cycle under
    ``cycle_<i>/``).  When **no schedule is given** (all of
    ``wait``/``warmup``/``repeat`` at 0 and ``active`` left at ``None``)
    the whole ``with`` block is ONE continuous trace window even if
    ``profiler.step()`` is called — the reference's no-schedule
    ``torch.profiler`` behavior — instead of a start/stop pair per step.
    ``profile_memory`` reports device memory deltas over
    the active window in ``profiler.summary['memory']``; ``with_flops``
    accumulates :meth:`TPUProfiler.flops_estimate` results into
    ``summary['flops']``.  ``on_trace_ready(trace_dir)`` fires at the end
    of every cycle.
    """

    wait: int = 0
    warmup: int = 0
    # None = "no schedule declared" (continuous window); an explicit int
    # turns on the per-cycle schedule
    active: Optional[int] = None
    repeat: int = 0
    output_trace_dir: Optional[str] = None
    with_flops: bool = False
    profile_memory: bool = False
    create_perfetto_link: bool = False
    on_trace_ready: Optional[Callable] = None

    def __post_init__(self):
        if self.active is not None and self.active < 1:
            raise ValueError(
                f"ProfileKwargs.active must be >= 1 when set (got {self.active}); "
                "leave it at None for a single continuous trace window"
            )

    def has_schedule(self) -> bool:
        return bool(self.wait or self.warmup or self.repeat or self.active is not None)


@dataclass
class SeedWorkersKwargs(KwargsHandler):
    """Dataloader worker seeding (DataLoaderConfiguration companion)."""

    base_seed: int = 0


# ---------------------------------------------------------------------------
# Plugins (the strategy config surface)
# ---------------------------------------------------------------------------


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """reference dataclasses.py:85-111 — plus TPU-native microbatch mode.

    ``in_step`` folds the accumulation loop into the jitted train step as a
    ``lax.scan`` over microbatches (TPU idiom: one compilation, compiler
    overlaps); ``across_steps`` keeps the reference's python-loop semantics
    (grad buffer carried in TrainState between step calls).
    """

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False
    mode: str = "in_step"  # "in_step" | "across_steps"

    def __post_init__(self):
        if self.mode not in ("in_step", "across_steps"):
            raise ValueError(f"invalid gradient accumulation mode {self.mode!r}")
        if self.num_steps < 1:
            raise ValueError("gradient_accumulation num_steps must be >= 1")


@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """FSDP/ZeRO-as-sharding-config (reference FSDP plugin dataclasses.py:1566,
    DeepSpeedPlugin :1113).

    Under GSPMD the whole plugin compiles down to: which mesh axes shard the
    parameter/optimizer pytrees, above what size, and what happens after
    forward.  ``state_dict_type`` controls checkpoint materialization.
    """

    sharding_strategy: Optional[ShardingStrategy] = None  # default: env or FULL_SHARD
    reshard_after_forward: bool = True      # ZeRO-3 vs ZeRO-2 behavior
    min_weight_size: int = 2**12            # auto-wrap-policy analog: don't shard tiny params
    state_dict_type: CheckpointFormat = CheckpointFormat.SHARDED
    cpu_offload: Optional[bool] = None      # ZeRO-offload: optimizer state in pinned host
                                            # memory, update as XLA host compute
    offload_params: Optional[bool] = None   # also keep the fp32 master params host-side
                                            # (default: follows cpu_offload, matching FSDP
                                            # CPUOffload(offload_params=True) semantics)
    host_update_chunk_gib: Optional[float] = None
                                            # split the host-compute optimizer update into
                                            # per-leaf-group regions of at most this many
                                            # GiB of fp32 params each, bounding the host's
                                            # transient working set (upcasts + moment temps)
                                            # — what lets adamw run at 7B on one chip.
                                            # Requires a per-leaf-independent optimizer
                                            # chain (adamw/lion/sgd/...; NOT
                                            # clip_by_global_norm inside tx — use the
                                            # train step's max_grad_norm instead).
                                            # None = one monolithic region.
    host_update_pipeline: Optional[bool] = None
                                            # 3-stage software pipeline over the chunked
                                            # host update (ops/streaming.py): chunk k+1's
                                            # grads stage D2H and chunk k-1's outputs
                                            # write back while chunk k's host region runs
                                            # (only the update regions ride the
                                            # serialization token chain).  Bitwise-
                                            # identical to the serial schedule — same
                                            # chunk boundaries, same SR hash streams
                                            # (tests/test_offload.py).  Default True; env
                                            # ACCELERATE_HOST_UPDATE_PIPELINE=false
                                            # restores the fully serialized A/B baseline.
                                            # Only consulted when host_update_chunk_gib
                                            # is set.
    int8_state_block_size: Optional[int] = None
                                            # per-block fp32-scale granularity for the
                                            # -sr8 int8 optimizer-state recipes
                                            # (ops/int8_state.py; smaller blocks = finer
                                            # scales = lower quant noise, more scale
                                            # bytes: 8/block B/param/moment of extra host
                                            # traffic).  Config transport only — the
                                            # recipes are built through
                                            # optimizer.make_optimizer(name,
                                            # block_size=...) or
                                            # Accelerator.prepare_optimizer("<name>"),
                                            # which reads this knob.  Default 128 (one
                                            # TPU lane width); env
                                            # ACCELERATE_INT8_STATE_BLOCK.
    collective_matmul: Optional[str] = None
                                            # ring collective-matmul for the TP/SP hot
                                            # path (ops/collective_matmul.py): "off"
                                            # leaves the monolithic GSPMD all-gather/
                                            # reduce-scatter, "on"/"ring" decomposes
                                            # them into ppermute ring schedules that
                                            # hide ICI hops under the partial matmuls,
                                            # "bidir" halves ring depth with opposing
                                            # half-rings.  Trace-time: the Accelerator
                                            # installs it as the ambient mode at
                                            # construction.  Default "off"; env
                                            # ACCELERATE_COLLECTIVE_MATMUL.
    activation_checkpointing: Optional[bool] = None  # jax.checkpoint on remat-policy blocks
    remat_policy: str = "nothing_saveable"  # name of a jax.checkpoint policy
    use_orig_params: bool = True            # API parity; always true under GSPMD

    def __post_init__(self):
        # Env vars supply *defaults* only — an explicit argument always wins
        # (reference plugin __post_init__ contract, dataclasses.py:1217-1260).
        env = os.environ
        if self.sharding_strategy is None:
            self.sharding_strategy = ShardingStrategy(env.get("FSDP_SHARDING_STRATEGY", "FULL_SHARD"))
        elif isinstance(self.sharding_strategy, str):
            self.sharding_strategy = ShardingStrategy(self.sharding_strategy)
        if isinstance(self.state_dict_type, str):
            self.state_dict_type = CheckpointFormat(self.state_dict_type)
        if self.cpu_offload is None:
            self.cpu_offload = parse_flag_from_env("FSDP_OFFLOAD_PARAMS")
        if self.offload_params is None:
            self.offload_params = self.cpu_offload
        if self.host_update_pipeline is None:
            self.host_update_pipeline = parse_flag_from_env(
                "ACCELERATE_HOST_UPDATE_PIPELINE", default=True
            )
        if self.int8_state_block_size is None:
            self.int8_state_block_size = int(env.get("ACCELERATE_INT8_STATE_BLOCK", 128))
        if self.int8_state_block_size < 1:
            raise ValueError(
                f"int8_state_block_size must be >= 1, got {self.int8_state_block_size}"
            )
        if self.collective_matmul is None:
            self.collective_matmul = env.get("ACCELERATE_COLLECTIVE_MATMUL", "off")
        # normalize through the engine's canonical table (raises on junk)
        from ..ops.collective_matmul import normalize_mode

        self.collective_matmul = normalize_mode(self.collective_matmul)
        if self.activation_checkpointing is None:
            self.activation_checkpointing = parse_flag_from_env("FSDP_ACTIVATION_CHECKPOINTING")


@dataclass
class ResiliencePlugin(KwargsHandler):
    """Preemption-safe training knobs (engine: ``accelerate_tpu/resilience/``;
    CheckFreq/Varuna discipline — see docs/resilience.md).

    ``ACCELERATE_RESILIENCE=1`` arms the whole layer by default (NaN guard +
    preemption handling); individual ``ACCELERATE_NAN_GUARD`` /
    ``ACCELERATE_PREEMPTION`` flags override per-feature.  Checkpoint
    verification and bounded I/O retry are on regardless — they cost nothing
    on the hot path and are what the corruption-fallback contract rests on.
    """

    nan_guard: Optional[bool] = None        # lax-select skip-step on non-finite
                                            # loss/grad-norm inside the jitted
                                            # step; counters persist in
                                            # TrainState.guard_state.  Default:
                                            # env ACCELERATE_NAN_GUARD, else
                                            # ACCELERATE_RESILIENCE.
    max_consecutive_nan_skips: int = 3      # abort (NanGuardAbort) after this
                                            # many consecutive skipped steps;
                                            # 0 disables the abort only — the
                                            # armed guard always fetches its
                                            # skip scalar per step so goodput/
                                            # bench counters stay truthful.
    handle_preemption: Optional[bool] = None  # install the SIGTERM-at-step-
                                            # boundary handler at Accelerator
                                            # construction.  Default: env
                                            # ACCELERATE_PREEMPTION, else
                                            # ACCELERATE_RESILIENCE.
    preemption_signals: tuple = ("SIGTERM",)
    preemption_check_every: int = 1         # multi-process: agree the any-rank
                                            # stop via a tiny host-blocking
                                            # all-gather every N steps.  1 =
                                            # stop at the very next boundary;
                                            # raise it on long runs to keep
                                            # the step pipeline async (the
                                            # stop then lands within N steps
                                            # of the notice — budget against
                                            # the preemption grace window).
    emergency_checkpoint: bool = True       # write a checkpoint at the stop
                                            # boundary before exiting
    resume_exit_code: int = 75              # EX_TEMPFAIL: "re-run me" — what
                                            # supervisors key restarts on
    verify_checkpoints: bool = True         # manifest (sizes+crc32) on save,
                                            # verify + valid-fallback on load
    io_retries: int = 3                     # bounded retry budget for
                                            # checkpoint I/O + host transfers
    io_backoff_s: float = 0.05              # first backoff; doubles per retry
    peer_snapshot_every: int = 0            # >0: CheckFreq-style host snapshot
                                            # of the TrainState every N steps,
                                            # replicated to the buddy rank's
                                            # host RAM (resilience/peer_ckpt) —
                                            # the fast rung of the recovery
                                            # ladder.  0 disables.
    peer_snapshot_keep: int = 2             # newest waves kept per side
                                            # (local + buddy copies)

    def __post_init__(self):
        armed = parse_flag_from_env("ACCELERATE_RESILIENCE")
        if self.nan_guard is None:
            self.nan_guard = parse_flag_from_env("ACCELERATE_NAN_GUARD", default=armed)
        if self.handle_preemption is None:
            self.handle_preemption = parse_flag_from_env(
                "ACCELERATE_PREEMPTION", default=armed
            )
        if isinstance(self.preemption_signals, str):
            self.preemption_signals = (self.preemption_signals,)
        else:
            self.preemption_signals = tuple(self.preemption_signals)
        if self.max_consecutive_nan_skips < 0:
            raise ValueError(
                "max_consecutive_nan_skips must be >= 0 (0 disables the "
                f"abort), got {self.max_consecutive_nan_skips}"
            )
        if self.io_retries < 0:
            raise ValueError(f"io_retries must be >= 0, got {self.io_retries}")
        if self.peer_snapshot_every < 0:
            raise ValueError(
                "peer_snapshot_every must be >= 0 (0 disables peer "
                f"snapshots), got {self.peer_snapshot_every}"
            )
        if self.peer_snapshot_keep < 1:
            raise ValueError(
                f"peer_snapshot_keep must be >= 1, got {self.peer_snapshot_keep}"
            )


@dataclass
class ServingPlugin(KwargsHandler):
    """Serving-core knobs (engine: ``accelerate_tpu/serving/`` — paged KV
    cache + continuous batching; see docs/serving.md).

    Geometry defaults target the CPU test mesh; production configs size the
    pool off the predicted KV-HBM ladder
    (``serving.paged_cache.kv_pool_accounting``).  Every knob reads an
    ``ACCELERATE_SERVE_*`` environment default in ``__post_init__`` (explicit
    arguments always win — the reference plugin contract).
    """

    num_slots: Optional[int] = None          # concurrent decode lanes
                                             # (env ACCELERATE_SERVE_SLOTS, default 8)
    page_size: Optional[int] = None          # tokens per KV page
                                             # (env ACCELERATE_SERVE_PAGE_SIZE, default 16)
    pages_per_slot: Optional[int] = None     # block-table width = per-sequence KV
                                             # ceiling in pages (env
                                             # ACCELERATE_SERVE_PAGES_PER_SLOT, default 8)
    num_pages: Optional[int] = None          # pool size; default provisions ~half the
                                             # worst case (num_slots * pages_per_slot
                                             # // 2) — continuous batching's bet that
                                             # sequences rarely all peak together
                                             # (env ACCELERATE_SERVE_PAGES)
    prefill_chunk: Optional[int] = None      # max prompt tokens prefilled per engine
                                             # tick (chunked prefill; env
                                             # ACCELERATE_SERVE_PREFILL_CHUNK, default 64)
    prefill_buckets: Optional[tuple] = None  # pad-to-bucket widths for the jitted
                                             # prefill step — one compile per bucket,
                                             # never a recompile mid-traffic.  Default:
                                             # powers of two from 16 up to prefill_chunk.
    decode_kernel: str = ""                  # "auto" (paged Pallas kernel on TPU,
                                             # native gather elsewhere) | "native" |
                                             # "flash" (env ACCELERATE_SERVE_KERNEL)
    speculate: str = ""                      # speculative multi-token decode:
                                             # "off" | "ngram" (prompt-lookup
                                             # self-drafting) | "draft" (small
                                             # draft model — pass draft_model/
                                             # draft_params to the engine).
                                             # env ACCELERATE_SERVE_SPECULATE
                                             # ("on"/"1" mean "ngram"), default off
    speculate_k: Optional[int] = None        # draft tokens proposed per verify
                                             # pass (env
                                             # ACCELERATE_SERVE_SPECULATE_K,
                                             # default 4)
    speculate_buckets: Optional[tuple] = None  # verify-program width ladder (the
                                             # program compiles once per bucket
                                             # at width bucket+1, never
                                             # mid-traffic).  Default:
                                             # (speculate_k,)
    speculate_draft_window: Optional[int] = None  # draft-model context window
                                             # (the fixed-shape windowed forward
                                             # the "draft" provider re-runs per
                                             # draft token; env
                                             # ACCELERATE_SERVE_SPECULATE_DRAFT,
                                             # default 32)
    prefix_cache: str = ""                   # content-addressed COW prefix
                                             # reuse (serving/prefix_cache.py):
                                             # "off" | "on" — full prompt-prefix
                                             # pages hash-match against shared
                                             # refcounted physical pages, chunked
                                             # prefill starts at the hit
                                             # boundary.  env
                                             # ACCELERATE_SERVE_PREFIX_CACHE
                                             # ("1"/"on" mean on), default off
    max_queue: Optional[int] = None          # bounded waiting line: beyond this
                                             # depth the deterministic shed
                                             # policy drops requests (0 =
                                             # unbounded; env
                                             # ACCELERATE_SERVE_MAX_QUEUE)
    kv_shed_watermark: Optional[float] = None  # predicted KV pressure (used +
                                             # queued prompt demand, as a pool
                                             # fraction) beyond which queued
                                             # requests shed (0.0 = off; env
                                             # ACCELERATE_SERVE_KV_WATERMARK)
    default_deadline_ticks: Optional[int] = None  # deadline (engine ticks from
                                             # arrival) stamped on requests that
                                             # carry none (0 = no deadline; env
                                             # ACCELERATE_SERVE_DEADLINE)
    ladder_reserve_frac: Optional[float] = None  # free-page reserve admission
                                             # must keep once the degradation
                                             # ladder tightens (fraction of the
                                             # pool; env
                                             # ACCELERATE_SERVE_LADDER_RESERVE,
                                             # default 0.125)
    kv_dtype: str = ""                       # KV page storage dtype: "bf16"
                                             # (model dtype, dense pages) |
                                             # "int8" | "fp8" — quantized pages
                                             # store 1-byte codes + per-(kv-
                                             # head, page) scales, ~1.9-2x the
                                             # token capacity per HBM byte
                                             # (serving/paged_cache.py
                                             # kv_pool_accounting ladder).  env
                                             # ACCELERATE_SERVE_KV_DTYPE,
                                             # default bf16

    def __post_init__(self):
        env = os.environ
        if self.num_slots is None:
            self.num_slots = int(env.get("ACCELERATE_SERVE_SLOTS", 8))
        if self.page_size is None:
            self.page_size = int(env.get("ACCELERATE_SERVE_PAGE_SIZE", 16))
        if self.pages_per_slot is None:
            self.pages_per_slot = int(env.get("ACCELERATE_SERVE_PAGES_PER_SLOT", 8))
        if self.num_pages is None:
            env_pages = env.get("ACCELERATE_SERVE_PAGES")
            self.num_pages = (int(env_pages) if env_pages
                              else max(self.pages_per_slot,
                                       self.num_slots * self.pages_per_slot // 2))
        if self.prefill_chunk is None:
            self.prefill_chunk = int(env.get("ACCELERATE_SERVE_PREFILL_CHUNK", 64))
        if not self.decode_kernel:
            self.decode_kernel = env.get("ACCELERATE_SERVE_KERNEL", "auto")
        if self.decode_kernel not in ("auto", "native", "flash"):
            raise ValueError(
                f"decode_kernel must be 'auto', 'native' or 'flash', got "
                f"{self.decode_kernel!r}"
            )
        if isinstance(self.speculate, bool):
            # the generate_paged(speculate=True) convention works here too
            self.speculate = "ngram" if self.speculate else "off"
        if not self.speculate:
            self.speculate = env.get("ACCELERATE_SERVE_SPECULATE", "off")
        self.speculate = {"1": "ngram", "on": "ngram", "0": "off",
                          "": "off"}.get(self.speculate.lower(),
                                         self.speculate.lower())
        if self.speculate not in ("off", "ngram", "draft"):
            raise ValueError(
                f"speculate must be 'off', 'ngram' or 'draft' (or 'on'/'1' "
                f"for ngram), got {self.speculate!r}"
            )
        if self.speculate_k is None:
            self.speculate_k = int(env.get("ACCELERATE_SERVE_SPECULATE_K", 4))
        if self.speculate_draft_window is None:
            self.speculate_draft_window = int(
                env.get("ACCELERATE_SERVE_SPECULATE_DRAFT", 32)
            )
        if self.speculate != "off" and self.speculate_k < 1:
            raise ValueError(
                f"speculate_k must be >= 1 with speculation on, got "
                f"{self.speculate_k}"
            )
        if self.speculate_buckets is None:
            self.speculate_buckets = (self.speculate_k,)
        else:
            self.speculate_buckets = tuple(
                sorted(int(b) for b in self.speculate_buckets)
            )
            if not self.speculate_buckets or \
                    self.speculate_buckets[-1] < self.speculate_k:
                raise ValueError(
                    f"speculate_buckets {self.speculate_buckets} must include "
                    f"a bucket >= speculate_k={self.speculate_k}"
                )
            if self.speculate_buckets[0] < 1:
                raise ValueError("speculate_buckets entries must be >= 1")
        if isinstance(self.prefix_cache, bool):
            self.prefix_cache = "on" if self.prefix_cache else "off"
        if not self.prefix_cache:
            self.prefix_cache = os.environ.get(
                "ACCELERATE_SERVE_PREFIX_CACHE", "off"
            )
        self.prefix_cache = {"1": "on", "true": "on", "0": "off",
                             "false": "off", "": "off"}.get(
            self.prefix_cache.lower(), self.prefix_cache.lower()
        )
        if self.prefix_cache not in ("off", "on"):
            raise ValueError(
                f"prefix_cache must be 'off' or 'on' (or '1'/'true' for on), "
                f"got {self.prefix_cache!r}"
            )
        if self.max_queue is None:
            self.max_queue = int(env.get("ACCELERATE_SERVE_MAX_QUEUE", 0))
        if self.kv_shed_watermark is None:
            self.kv_shed_watermark = float(
                env.get("ACCELERATE_SERVE_KV_WATERMARK", 0.0)
            )
        if self.default_deadline_ticks is None:
            self.default_deadline_ticks = int(env.get("ACCELERATE_SERVE_DEADLINE", 0))
        if self.ladder_reserve_frac is None:
            self.ladder_reserve_frac = float(
                env.get("ACCELERATE_SERVE_LADDER_RESERVE", 0.125)
            )
        if self.max_queue < 0:
            raise ValueError(f"max_queue must be >= 0 (0 = unbounded), got {self.max_queue}")
        if not 0.0 <= self.kv_shed_watermark <= 1.0:
            raise ValueError(
                f"kv_shed_watermark must be in [0, 1] (0 = off), got "
                f"{self.kv_shed_watermark}"
            )
        if self.default_deadline_ticks < 0:
            raise ValueError(
                f"default_deadline_ticks must be >= 0 (0 = none), got "
                f"{self.default_deadline_ticks}"
            )
        if not 0.0 < self.ladder_reserve_frac < 1.0:
            raise ValueError(
                f"ladder_reserve_frac must be in (0, 1), got "
                f"{self.ladder_reserve_frac}"
            )
        for name in ("num_slots", "page_size", "pages_per_slot", "num_pages",
                     "prefill_chunk"):
            if getattr(self, name) < 1:
                raise ValueError(f"ServingPlugin.{name} must be >= 1, got {getattr(self, name)}")
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} must cover at least one sequence "
                f"(pages_per_slot={self.pages_per_slot})"
            )
        if not self.kv_dtype:
            self.kv_dtype = env.get("ACCELERATE_SERVE_KV_DTYPE", "bf16")
        self.kv_dtype = self.kv_dtype.lower()
        if self.kv_dtype not in ("bf16", "int8", "fp8"):
            raise ValueError(
                f"kv_dtype must be 'bf16', 'int8' or 'fp8', got "
                f"{self.kv_dtype!r}"
            )
        if self.prefill_buckets is None:
            buckets, b = [], 16
            while b < self.prefill_chunk:
                buckets.append(b)
                b *= 2
            buckets.append(self.prefill_chunk)
            self.prefill_buckets = tuple(buckets)
        else:
            self.prefill_buckets = tuple(sorted(int(b) for b in self.prefill_buckets))
            if not self.prefill_buckets or self.prefill_buckets[-1] < self.prefill_chunk:
                raise ValueError(
                    f"prefill_buckets {self.prefill_buckets} must include a bucket "
                    f">= prefill_chunk={self.prefill_chunk}"
                )


@dataclass
class LoraPlugin(KwargsHandler):
    """Multi-tenant batched-LoRA knobs (engine:
    ``accelerate_tpu/serving/adapters.py`` + ``ops/lora.py`` — see the
    multi-tenant section of docs/serving.md).

    One base model serves/fine-tunes thousands of LoRA adapters: a
    fixed-size **device pool** holds the hot adapters as stacked A/B
    factors (slot 0 reserved for the null adapter = base model), cold
    adapters hot-swap in from host memmaps, and every batch row routes
    through its adapter by id in ONE gathered einsum (S-LoRA/BGMV
    discipline — no recompile per tenant mix).  Every knob reads an
    ``ACCELERATE_LORA_*`` environment default in ``__post_init__``
    (explicit arguments win — the plugin contract).
    """

    rank: Optional[int] = None               # LoRA rank r
                                             # (env ACCELERATE_LORA_RANK, default 8)
    alpha: Optional[float] = None            # scaling numerator; alpha/rank is folded
                                             # into stored B factors at adapter
                                             # creation (env ACCELERATE_LORA_ALPHA,
                                             # default 16.0)
    pool_slots: Optional[int] = None         # device-resident adapters (excl. the
                                             # null slot) — the hot-swap LRU pool
                                             # size (env ACCELERATE_LORA_POOL_SLOTS,
                                             # default 4)
    targets: Optional[tuple] = None          # module names that carry adapters
                                             # (env ACCELERATE_LORA_TARGETS,
                                             # comma-separated; default q_proj,v_proj)
    kernel: str = ""                         # "auto" (Pallas BGMV gather-matmul on
                                             # TPU T=1 decode, gathered einsum
                                             # elsewhere) | "native" | "bgmv"
                                             # (env ACCELERATE_LORA_KERNEL)
    max_bypass_age: Optional[int] = None     # admission fairness bound: how many
                                             # engine ticks a head-of-line request
                                             # blocked on an adapter swap tolerates
                                             # younger zero-swap requests admitting
                                             # past it before admission holds the
                                             # line (env ACCELERATE_LORA_BYPASS_AGE,
                                             # default 16; 0 = strict FIFO)
    optimizer: str = ""                      # make_optimizer recipe for per-adapter
                                             # fine-tuning state — the int8-SR
                                             # recipes keep per-tenant state tiny
                                             # (env ACCELERATE_LORA_OPTIMIZER,
                                             # default lion-sr8)

    def __post_init__(self):
        env = os.environ
        if self.rank is None:
            self.rank = int(env.get("ACCELERATE_LORA_RANK", 8))
        if self.alpha is None:
            self.alpha = float(env.get("ACCELERATE_LORA_ALPHA", 16.0))
        if self.pool_slots is None:
            self.pool_slots = int(env.get("ACCELERATE_LORA_POOL_SLOTS", 4))
        if self.targets is None:
            raw = env.get("ACCELERATE_LORA_TARGETS", "q_proj,v_proj")
            self.targets = tuple(t.strip() for t in raw.split(",") if t.strip())
        elif isinstance(self.targets, str):
            self.targets = tuple(t.strip() for t in self.targets.split(",") if t.strip())
        else:
            self.targets = tuple(self.targets)
        if not self.kernel:
            self.kernel = env.get("ACCELERATE_LORA_KERNEL", "auto")
        from ..ops.lora import normalize_lora_kernel

        self.kernel = normalize_lora_kernel(self.kernel)
        if self.max_bypass_age is None:
            self.max_bypass_age = int(env.get("ACCELERATE_LORA_BYPASS_AGE", 16))
        if not self.optimizer:
            self.optimizer = env.get("ACCELERATE_LORA_OPTIMIZER", "lion-sr8")
        if self.rank < 1:
            raise ValueError(f"LoraPlugin.rank must be >= 1, got {self.rank}")
        if self.alpha <= 0:
            raise ValueError(f"LoraPlugin.alpha must be > 0, got {self.alpha}")
        if self.pool_slots < 1:
            raise ValueError(
                f"LoraPlugin.pool_slots must be >= 1, got {self.pool_slots}"
            )
        if self.max_bypass_age < 0:
            raise ValueError(
                f"LoraPlugin.max_bypass_age must be >= 0, got {self.max_bypass_age}"
            )
        if not self.targets:
            raise ValueError("LoraPlugin.targets must name at least one module")


@dataclass
class PreflightConfig(KwargsHandler):
    """Deploy-preflight knobs (``commands/preflight.py`` — AOT-compile every
    production program and audit the executables; see the "Deploy
    preflight" section of docs/static_analysis.md).

    Every knob reads an ``ACCELERATE_PREFLIGHT_*`` environment default in
    ``__post_init__`` (explicit arguments win — the plugin contract).
    """

    hbm_gb: Optional[float] = None           # HBM budget for GL302; None = use the
                                             # backend's measured bytes_limit (CPU
                                             # reports none -> GL302 skipped)
                                             # (env ACCELERATE_PREFLIGHT_HBM_GB)
    donation_slack_bytes: int = -1           # non-aliased donated bytes tolerated
                                             # before GL301 (scalar counters XLA
                                             # reasonably declines; default 1024,
                                             # env ACCELERATE_PREFLIGHT_DONATION_SLACK)
    fail_on: str = ""                        # lowest severity that fails the run
                                             # ("error" | "warning" | "info"; env
                                             # ACCELERATE_PREFLIGHT_FAIL_ON, default
                                             # error — GL301/GL302 are errors)
    optimizer: str = ""                      # optimizer recipe for the train-step
                                             # program (env
                                             # ACCELERATE_PREFLIGHT_OPTIMIZER,
                                             # default lion)

    def __post_init__(self):
        env = os.environ
        if self.hbm_gb is None:
            raw = env.get("ACCELERATE_PREFLIGHT_HBM_GB")
            self.hbm_gb = float(raw) if raw else None
        if self.donation_slack_bytes < 0:
            self.donation_slack_bytes = int(
                env.get("ACCELERATE_PREFLIGHT_DONATION_SLACK", 1024)
            )
        if not self.fail_on:
            self.fail_on = env.get("ACCELERATE_PREFLIGHT_FAIL_ON", "error")
        if self.fail_on not in ("error", "warning", "info"):
            raise ValueError(
                f"PreflightConfig.fail_on must be 'error', 'warning' or "
                f"'info', got {self.fail_on!r}"
            )
        if not self.optimizer:
            self.optimizer = env.get("ACCELERATE_PREFLIGHT_OPTIMIZER", "lion")
        if self.hbm_gb is not None and self.hbm_gb <= 0:
            raise ValueError(f"PreflightConfig.hbm_gb must be > 0, got {self.hbm_gb}")


@dataclass
class TelemetryPlugin(KwargsHandler):
    """Unified-telemetry knobs (engine: ``accelerate_tpu/telemetry/`` —
    twin registry, request trace spans, training timeline, SLO monitors;
    see docs/observability.md).

    Telemetry is host-side only: on or off, serving tokens and training
    loss are bitwise identical and no new program compiles (pinned by
    tests and the multichip dryrun ``_telemetry_leg``); the measured
    recording cost is reported as ``telemetry_overhead_frac``.  Every knob
    reads an ``ACCELERATE_TELEMETRY*`` environment default in
    ``__post_init__`` (explicit arguments win — the plugin contract).
    """

    enabled: Optional[bool] = None           # master switch: arms timeline +
                                             # request tracing defaults (env
                                             # ACCELERATE_TELEMETRY, default off)
    trace_requests: Optional[bool] = None    # per-request lifecycle spans on the
                                             # serving engine (env
                                             # ACCELERATE_TELEMETRY_TRACE_REQUESTS,
                                             # else `enabled`)
    timeline: Optional[bool] = None          # training step timeline on the
                                             # Accelerator (env
                                             # ACCELERATE_TELEMETRY_TIMELINE,
                                             # else `enabled`)
    ring_capacity: Optional[int] = None      # span ring-buffer size per recorder
                                             # (bounded memory; env
                                             # ACCELERATE_TELEMETRY_RING, default 4096)
    slo: Optional[dict] = None               # SLOMonitor thresholds, e.g.
                                             # {"ttft_s": {"p99_warn": 0.5,
                                             #  "p99_trip": 2.0}} — None: no monitor
    export_dir: Optional[str] = None         # where end-of-run Chrome traces land
                                             # (env ACCELERATE_TELEMETRY_DIR;
                                             # None: export only on request)

    def __post_init__(self):
        env = os.environ
        if self.enabled is None:
            self.enabled = parse_flag_from_env("ACCELERATE_TELEMETRY")
        if self.trace_requests is None:
            self.trace_requests = parse_flag_from_env(
                "ACCELERATE_TELEMETRY_TRACE_REQUESTS", default=self.enabled
            )
        if self.timeline is None:
            self.timeline = parse_flag_from_env(
                "ACCELERATE_TELEMETRY_TIMELINE", default=self.enabled
            )
        if self.ring_capacity is None:
            self.ring_capacity = int(env.get("ACCELERATE_TELEMETRY_RING", 4096))
        if self.ring_capacity < 1:
            raise ValueError(
                f"TelemetryPlugin.ring_capacity must be >= 1, got "
                f"{self.ring_capacity}"
            )
        if self.export_dir is None:
            self.export_dir = env.get("ACCELERATE_TELEMETRY_DIR") or None
        if self.slo is not None and not isinstance(self.slo, dict):
            raise ValueError(
                f"TelemetryPlugin.slo must be a thresholds dict, got "
                f"{type(self.slo).__name__}"
            )


@dataclass
class TensorParallelConfig(KwargsHandler):
    """reference TorchTensorParallelConfig dataclasses.py:2264.

    ``plan`` names a sharding-rule table (models ship defaults); GSPMD makes TP
    pure annotation — no module rewrite (reference had to DTensor-ify params,
    accelerator.py:1594-1616).
    """

    tp_size: int = 1
    plan: str = "auto"
    async_matmul: bool = True  # allow XLA latency-hiding collective matmuls


@dataclass
class ContextParallelConfig(KwargsHandler):
    """reference TorchContextParallelConfig dataclasses.py:2186-2210.

    rotate_method: 'allgather' gathers all KV once; 'alltoall' (ring) streams
    KV blocks with ppermute — the ring-attention path.
    """

    cp_size: int = 1
    rotate_method: str = "allgather"  # "allgather" | "alltoall"
    load_balance: bool = True          # zigzag sequence ordering for causal masks

    def __post_init__(self):
        if self.rotate_method not in ("allgather", "alltoall"):
            raise ValueError(f"invalid cp rotate method {self.rotate_method!r}")


@dataclass
class SequenceParallelConfig(KwargsHandler):
    """Ulysses/ALST head-parallel attention (reference
    DeepSpeedSequenceParallelConfig dataclasses.py:2214-2260): two all_to_alls
    swap sharding between sequence dim and head dim around attention."""

    sp_size: int = 1
    seq_length: Optional[int] = None
    attn_implementation: str = "native"


@dataclass
class ExpertParallelConfig(KwargsHandler):
    """MoE expert sharding over an ``ep`` mesh axis (capability parity with the
    reference's DeepSpeed MoE leaf-module marking accelerator.py:2258-2259)."""

    ep_size: int = 1
    capacity_factor: float = 1.25
    drop_tokens: bool = True


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """reference dataclasses.py DataLoaderConfiguration (split_batches,
    dispatch_batches, even_batches, use_seedable_sampler...)."""

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = False
    data_seed: Optional[int] = None
    non_blocking: bool = True
    use_stateful_dataloader: bool = False
    prefetch_size: int = 2


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/project dir config (reference ProjectConfiguration
    dataclasses.py — automatic_checkpoint_naming + total_limit GC used by
    accelerator.save_state :3587-3613)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: Optional[str] = None):
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        self.set_directories(self.project_dir)


# ---------------------------------------------------------------------------
# Exceptions
# ---------------------------------------------------------------------------


class DistributedOperationException(Exception):
    """Raised by debug-mode collective shape verification
    (reference operations.py:364-398)."""


ALL_KWARGS_HANDLERS = (
    AutocastKwargs,
    GradSyncKwargs,
    InitProcessGroupKwargs,
    ProfileKwargs,
)
