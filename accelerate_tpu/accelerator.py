"""The Accelerator façade — single user-facing object (L5).

TPU-native re-design of reference ``accelerator.py`` (4,324 LoC).  The
capability surface survives — ``prepare`` lifts (model, optimizer, dataloader,
scheduler), gradient accumulation, clipping, ``gather_for_metrics``,
``save_state``/``load_state``, process control — but the architecture follows
SURVEY §7's design stance: **one mesh + NamedSharding specs + a single
jit-compiled train step**.  FSDP/HSDP/TP/CP/SP/ZeRO are sharding
configurations of that one mechanism, not separate code paths like the
reference's ``_prepare_{fsdp2,tp,cp,deepspeed,megatron}`` dispatch
(reference accelerator.py:1530-1559).

The training hot loop (reference call stack §3.4) becomes::

    state = accelerator.create_train_state(params, tx, apply_fn=model.apply)
    step = accelerator.prepare_train_step(loss_fn)   # jitted, sharded
    for batch in train_dl:                           # global jax.Arrays
        state, metrics = step(state, batch)          # grads/update/collectives
                                                     # all compiler-scheduled

``accelerator.backward(loss)`` cannot exist under a functional autodiff; the
method raises with migration guidance (the contract shift SURVEY §7 'hard
parts' predicts).  Gradient accumulation folds into the step as a
``lax.scan`` over microbatches (``in_step`` mode, TPU idiom) or is carried in
the train state across calls (``across_steps`` mode preserving the
``with accelerator.accumulate():`` loop shape, reference :1254).
"""

from __future__ import annotations

import contextlib
import inspect
import math
import os
import time
from pathlib import Path
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental.compute_on import compute_on
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .ops import operations as ops
from .ops.precision import DynamicLossScale, Policy, all_finite, get_policy
from .optimizer import AcceleratedOptimizer
from .parallel.sharding import (
    device_plan,
    get_tp_rules,
    host_offload_supported,
    host_plan,
    make_opt_state_sharding_plan,
    make_sharding_plan,
    shard_params,
)
from .parallelism_config import ParallelismConfig
from .resilience import faults as _faults
from .resilience import guard as _guard
from .resilience import peer_ckpt as _peer_ckpt
from .resilience.goodput import GoodputTracker
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .utils.dataclasses import (
    AutocastKwargs,
    ContextParallelConfig,
    DataLoaderConfiguration,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradSyncKwargs,
    InitProcessGroupKwargs,
    KwargsHandler,
    MixedPrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
    ResiliencePlugin,
    SequenceParallelConfig,
    TelemetryPlugin,
    TensorParallelConfig,
)
from .logging import get_logger
from .utils.environment import parse_flag_from_env

logger = get_logger(__name__)

try:
    import flax.struct

    _HAS_FLAX = True
except ImportError:  # pragma: no cover
    _HAS_FLAX = False


if _HAS_FLAX:

    @flax.struct.dataclass
    class TrainState:
        """The train-state pytree the framework owns (SURVEY §7 hard part #2:
        owning this kills the reference's optimizer-param remapping dance).

        All array fields are sharded ``jax.Array``s; ``apply_fn``/``tx`` are
        static (not traced)."""

        step: jax.Array
        params: Any
        opt_state: Any
        rng: jax.Array
        loss_scale: Optional[DynamicLossScale] = None
        grad_accum: Any = None
        accum_step: Optional[jax.Array] = None
        # gradient-compression carry (PowerSGD warm-start Qs + per-rank
        # error buffers); None unless GradSyncKwargs.compression is set
        comm_state: Any = None
        # NaN-guard skip counters ({nan_skips, consecutive_nan_skips} int32
        # scalars, resilience/guard.py) — carried in the state so they
        # survive checkpoint/resume; None unless ResiliencePlugin.nan_guard
        guard_state: Any = None
        apply_fn: Callable = flax.struct.field(pytree_node=False, default=None)
        tx: Any = flax.struct.field(pytree_node=False, default=None)
        # .replace(**kwargs) is provided by flax.struct.dataclass


def _tree_zeros_like(tree, dtype=jnp.float32):
    return jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, dtype), tree)


# -- chunked host-update helpers ---------------------------------------------
# The ZeRO-offload optimizer update runs as XLA host compute.  A monolithic
# region materializes the whole tree's transients at once (fp32 grad upcasts +
# moment temps — at 7B with adamw that working set crashes the TPU worker
# host).  The chunking/slicing/merging machinery lives in ops/streaming.py
# (shared with the layer-streamed decode path); the names are re-bound here
# because the train step below and its tests grew up around them.  Per-leaf
# optimizers (adamw/lion/sgd/…) are bit-exact under the split.

from .ops.streaming import (  # noqa: E402  (grouped with the helper block)
    chunk_groups as _host_update_groups,
    merge_congruent as _merge_congruent,
    slice_congruent as _slice_congruent,
    stage_put as _stage_put,
)


def _host_constant_hoist(fn, host_sharding, *example_args):
    """Make ``fn`` safe to call inside a ``compute_on("device_host")`` region
    by hoisting its jaxpr constants into explicit arguments pinned to host
    memory.

    Some optimizer updates materialize constant *arrays* at trace time
    (adafactor's ``jnp.where`` fills / factored-moment eps broadcasts);
    under host-compute lowering those constants default to device space and
    the elementwise ops that consume them fail as mixed-memory-space
    (ROADMAP r2 "adafactor under host offload").  Two mechanisms combine —
    ``jax.closure_convert`` alone is not enough, it hoists only closed-over
    *tracers*:

    1. jaxpr consts: concrete arrays captured at trace time.
    2. literal-born arrays: ``jnp.where(c, x, 0.0)`` broadcasts its scalar
       inside the traced computation, and that broadcast output has no
       host-space operand to inherit from (measured on-chip:
       ``select_n ... f32<host>[512] vs f32[512]``).  Partial evaluation
       with every input unknown splits the jaxpr into a const-only known
       part (the broadcasts) and an unknown part consuming them as
       residual *arguments* — which we pin to ``host_sharding``.

    The traced fn is inlined (``disable_jit``) so nested ``jit[_where]``
    calls expose their literals to the split.  Per-leaf optimizers without
    constant arrays (adamw/lion/sgd) hoist nothing and pass through
    untouched.

    The split leans on non-public JAX machinery (``partial_eval``,
    ``eval_jaxpr`` replay of recorded eqn contexts), tested against jax
    0.9.x; if a JAX upgrade breaks it we fall back to the unhoisted ``fn``
    with a loud warning rather than crashing every host-offload config —
    const-free optimizers keep working, const-bearing ones (adafactor) will
    fail at lowering with the mixed-memory-space error this hoist exists to
    prevent."""
    try:
        return _host_constant_hoist_unsafe(fn, host_sharding, *example_args)
    except Exception as e:  # pragma: no cover - only fires on JAX API drift
        logger.warning_once(
            "Constant hoisting for host-compute optimizer updates is unavailable "
            f"on jax {jax.__version__} ({type(e).__name__}: {e}). Optimizers that "
            "materialize constant arrays at trace time (e.g. adafactor) are "
            "unsupported with cpu_offload on this JAX version; adamw/lion/sgd "
            "are unaffected."
        )
        return fn


def _host_constant_hoist_unsafe(fn, host_sharding, *example_args):
    from jax._src.interpreters import partial_eval as pe

    flat, in_tree = jax.tree_util.tree_flatten(example_args)
    # trace on space-free avals: the example operands carry <host> memory
    # spaces, and the very mixed-space select_n error this hoist prevents
    # would otherwise fire during this trace
    flat = [
        jax.ShapeDtypeStruct(np.shape(x), getattr(x, "dtype", np.result_type(x)))
        for x in flat
    ]

    def flat_fn(*flat_args):
        return fn(*jax.tree_util.tree_unflatten(in_tree, flat_args))

    # trace under the SAME compute context the replay runs in: eval_jaxpr
    # re-enters each eqn's recorded context manager, and a no-context eqn
    # replayed inside compute_on("device_host") raises the compute_on
    # nesting NotImplementedError
    with jax.disable_jit(), compute_on("device_host"):
        closed, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(*flat)
    known, unknown, _, res_avals = pe.partial_eval_jaxpr_nounits(
        closed, [True] * len(closed.jaxpr.invars), instantiate=True
    )
    if not res_avals and not any(hasattr(c, "dtype") for c in unknown.consts):
        return fn
    out_tree = jax.tree_util.tree_structure(out_shape)

    def pin(v):
        return jax.device_put(v, host_sharding) if hasattr(v, "dtype") else v

    # the const-only subcomputation runs once at wrap time (outside the host
    # region); its residuals enter the region as host-pinned arguments
    residuals = [pin(r) for r in jax.core.eval_jaxpr(known.jaxpr, known.consts)]
    consts = [pin(c) for c in unknown.consts]

    def call(*args):
        outs = jax.core.eval_jaxpr(
            unknown.jaxpr, consts, *residuals, *jax.tree_util.tree_leaves(args)
        )
        return jax.tree_util.tree_unflatten(out_tree, outs)

    return call


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves))) if leaves else jnp.float32(0.0)


class Accelerator:
    """reference Accelerator (accelerator.py:184) — same construction surface,
    GSPMD internals."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        parallelism_config: Optional[ParallelismConfig] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        tp_config: Optional[TensorParallelConfig] = None,
        cp_config: Optional[ContextParallelConfig] = None,
        sp_config: Optional[SequenceParallelConfig] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        resilience_plugin: Optional[ResiliencePlugin] = None,
        telemetry_plugin: Optional[TelemetryPlugin] = None,
        rng_types: Optional[list] = None,
        log_with: Optional[Union[str, list]] = None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list[KwargsHandler]] = None,
    ):
        if parallelism_config is None and fsdp_plugin is None and parse_flag_from_env("ACCELERATE_USE_FSDP"):
            fsdp_plugin = FullyShardedDataParallelPlugin()

        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # kwargs handlers (reference accelerator.py:427-452)
        self.autocast_handler = AutocastKwargs()
        self.grad_sync_kwargs = GradSyncKwargs()
        self.init_process_group_kwargs: Optional[InitProcessGroupKwargs] = None
        self.profile_kwargs = ProfileKwargs()
        for handler in kwargs_handlers or []:
            if isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, GradSyncKwargs):
                self.grad_sync_kwargs = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_process_group_kwargs = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_kwargs = handler

        state_kwargs = {}
        if self.init_process_group_kwargs is not None:
            state_kwargs["init_process_group_kwargs"] = self.init_process_group_kwargs
        self.state = AcceleratorState(
            mixed_precision=mixed_precision, cpu=cpu, parallelism_config=parallelism_config, **state_kwargs
        )

        if gradient_accumulation_plugin is None:
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=gradient_accumulation_steps)
        elif gradient_accumulation_steps != 1 and gradient_accumulation_plugin.num_steps != gradient_accumulation_steps:
            raise ValueError(
                "Pass gradient_accumulation_steps OR gradient_accumulation_plugin, not conflicting both"
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)

        if parallelism_config is not None:
            # Validate + build the mesh eagerly: a mis-sized config must fail
            # at construction, not at first .mesh access (reference
            # _validate_accelerator parallelism_config.py:355).
            self.state.mesh

        self.fsdp_plugin = fsdp_plugin
        # install the ring collective-matmul mode as the ambient trace-time
        # default (ops/collective_matmul.py); models traced through this
        # accelerator's steps pick it up at compile.  Construction is
        # authoritative either way: a plugin-less Accelerator clears any
        # previous override back to the env default rather than inheriting
        # a stale mode from an earlier instance.
        from .ops.collective_matmul import set_collective_matmul

        set_collective_matmul(
            fsdp_plugin.collective_matmul if fsdp_plugin is not None else None
        )
        self.tp_config = tp_config
        self.cp_config = cp_config
        self.sp_config = sp_config
        self.split_batches = split_batches
        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        self.rng_types = rng_types

        self.policy: Policy = get_policy(self.state.mixed_precision)
        self.flag_tensor = None

        self._dataloaders: list = []
        self._optimizers: list = []
        self._schedulers: list = []
        self._models: list = []
        self._custom_objects: list = []
        self._state_sharding = None
        self._save_model_state_pre_hooks: dict = {}
        self._load_model_state_pre_hooks: dict = {}
        # in-flight async train-state write (save_state(async_save=True));
        # awaited before the next save/GC/load and at end_training/exit.
        # _async_checkpointer is the long-lived orbax AsyncCheckpointer it
        # points at while a write is in flight.
        self._pending_checkpointer = None
        self._async_checkpointer = None
        self.step_count = 0
        self._in_accumulate = False
        # recompile guard: backend-compile events since construction (the
        # process-wide jax.monitoring stream, reported as a delta) — after
        # the first step compiles, a steady-state loop must stay flat
        # (perfbench refuses a window with a compile in it)
        from .analysis.compiled_audit import install_global_compile_counter

        self._compile_counter = install_global_compile_counter()
        self._compile_baseline = self._compile_counter.count

        self.trackers: list = []
        self.log_with = log_with if isinstance(log_with, (list, tuple)) else ([log_with] if log_with else [])

        # resilience layer (docs/resilience.md): knobs default from the
        # ACCELERATE_RESILIENCE env family; the goodput tracker always exists
        # (its report reads zeros when the run is clean)
        self.resilience_plugin = resilience_plugin or ResiliencePlugin()
        self.goodput = GoodputTracker()
        # unified telemetry (docs/observability.md): the training timeline
        # + SLO monitor are host-side only — enabling them is bitwise-
        # invisible to the loss (pinned by tests).  The twin registry is
        # process-global (telemetry/twins.py); timeline/slo exist only when
        # armed so the hot step wrapper pays one attribute check when off.
        self.telemetry_plugin = telemetry_plugin or TelemetryPlugin()
        self.timeline = None
        self.slo_monitor = None
        if self.telemetry_plugin.timeline:
            from .telemetry import TrainTimeline

            self.timeline = TrainTimeline(
                capacity=self.telemetry_plugin.ring_capacity
            )
        if self.telemetry_plugin.slo is not None:
            from .telemetry import SLOMonitor

            self.slo_monitor = SLOMonitor(self.telemetry_plugin.slo)
        self._slo_prev_step_t = None  # inter-step cadence anchor
        # buddy-rank host-RAM snapshotter (resilience/peer_ckpt.py): armed
        # lazily by the prepared step when peer_snapshot_every > 0
        self._peer_snapshotter = None
        self._preemption = None
        if self.resilience_plugin.handle_preemption:
            self.install_preemption_handler()
        if _faults.active_fault_plan() is None:
            # subprocess fault-matrix runs ship their plan as JSON in
            # ACCELERATE_FAULT_PLAN (deterministic; no-op when unset)
            env_plan = _faults.FaultPlan.from_env()
            if env_plan is not None:
                _faults.install_fault_plan(env_plan)

    # ------------------------------------------------------------------
    # Introspection / process control (delegation, reference :234-278)
    # ------------------------------------------------------------------

    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def num_processes(self):
        return self.state.num_processes

    @property
    def process_index(self):
        return self.state.process_index

    @property
    def local_process_index(self):
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self) -> Mesh:
        return self.state.mesh

    @property
    def parallelism_config(self) -> ParallelismConfig:
        self.state.mesh  # ensure default config materialized
        return self.state.parallelism_config

    @property
    def is_main_process(self):
        return self.state.is_main_process

    @property
    def is_local_main_process(self):
        return self.state.is_local_main_process

    @property
    def is_last_process(self):
        return self.state.is_last_process

    @property
    def mixed_precision(self):
        return self.state.mixed_precision

    @property
    def gradient_accumulation_steps(self):
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin.num_steps = value

    @property
    def sync_gradients(self):
        return self.gradient_state.sync_gradients

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def use_distributed(self):
        return self.state.use_distributed

    def on_main_process(self, function=None):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function=None):
        return self.state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index=process_index)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    # ------------------------------------------------------------------
    # prepare (reference :1413 dispatch spine)
    # ------------------------------------------------------------------

    def prepare(self, *args, device_placement: Optional[list] = None):
        """Lift user objects into accelerated equivalents, preserving order
        (reference prepare accelerator.py:1413)."""
        if device_placement is None:
            device_placement = [None] * len(args)
        result = tuple(self._prepare_one(obj, dp) for obj, dp in zip(args, device_placement))
        return result if len(result) > 1 else (result[0] if result else None)

    def _is_dataloader(self, obj) -> bool:
        from .data_loader import _is_torch_loader

        if _is_torch_loader(obj) or isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
            return True
        return False

    def _prepare_one(self, obj, device_placement=None):
        if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
            return obj  # already prepared
        if self._is_dataloader(obj):
            return self.prepare_data_loader(obj, device_placement=device_placement)
        if isinstance(obj, AcceleratedOptimizer):
            return obj
        if isinstance(obj, optax.GradientTransformation):
            return self.prepare_optimizer(obj, device_placement=device_placement)
        if isinstance(obj, AcceleratedScheduler):
            return obj
        if _HAS_FLAX:
            import flax.linen as nn

            if isinstance(obj, nn.Module):
                return self.prepare_model(obj, device_placement=device_placement)
        # schedules: plain callables of step -> lr.  Only auto-wrap callables
        # that are identifiably schedules (optax-built, or explicitly marked
        # with `.is_schedule = True`) — a user's collate_fn or loss_fn is
        # also a 1-arg callable and silently wrapping it as a scheduler is a
        # foot-gun; those pass through with a hint instead.
        if callable(obj) and not hasattr(obj, "shape") and not inspect.isclass(obj):
            sig = None
            try:
                sig = inspect.signature(obj)
            except (TypeError, ValueError):
                pass
            if sig is not None and len(sig.parameters) == 1:
                is_schedule = getattr(obj, "is_schedule", False) or getattr(
                    obj, "__module__", ""
                ).startswith("optax")
                if is_schedule:
                    return self.prepare_scheduler(obj)
                logger.warning(
                    "prepare() received a 1-argument callable %r that is not an optax "
                    "schedule; returning it unchanged. If it is a learning-rate schedule, "
                    "pass it through accelerator.prepare_scheduler() or set "
                    "`fn.is_schedule = True`.", getattr(obj, "__name__", obj),
                )
        return obj

    def prepare_model(self, model, device_placement=None, evaluation_mode: bool = False):
        """Models under JAX are (apply_fn, params); the Module itself carries
        no state — record it and return unchanged (sharding is applied to the
        params in :meth:`create_train_state`).  reference prepare_model
        (:1748) wrapped in DDP/FSDP here; GSPMD needs nothing."""
        self._models.append(model)
        return model

    def prepare_optimizer(self, optimizer, device_placement=None) -> AcceleratedOptimizer:
        """Wrap an optax transform — or build one of the named recipes
        (``optimizer.OPTIMIZER_RECIPES``, e.g. ``"lion-sr8"``) at its
        benchmarked hyperparameters; the -sr8 int8-state recipes take their
        per-block scale granularity from the FSDP plugin's
        ``int8_state_block_size`` knob."""
        if isinstance(optimizer, AcceleratedOptimizer):
            return optimizer
        if isinstance(optimizer, str):
            from .optimizer import make_optimizer

            block = (
                self.fsdp_plugin.int8_state_block_size
                if self.fsdp_plugin is not None and optimizer.endswith("-sr8")
                else None
            )
            optimizer = make_optimizer(optimizer, block_size=block)
        wrapped = AcceleratedOptimizer(optimizer)
        self._optimizers.append(wrapped)
        return wrapped

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        wrapped = AcceleratedScheduler(
            scheduler,
            optimizer=self._optimizers[-1] if self._optimizers else None,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.split_batches,
        )
        self._schedulers.append(wrapped)
        return wrapped

    def _compression_axes(self) -> list:
        """Mesh axes the gradient compression reduces over (the data-parallel
        plane; every other axis must be trivial for DDP-style compression).
        Includes the cross-slice ``dcn`` axis — it is data parallelism too,
        just on the slow network tier."""
        return [a for a in ("dcn", "dp_replicate", "dp_shard") if a in self.mesh.shape]

    def _resolve_hierarchical(self) -> tuple[bool, Optional[str]]:
        """``(engage, incompatibility)`` for the ICI->DCN hierarchical
        gradient-sync path: engage when the mesh has a non-trivial ``dcn``
        axis and the config is DDP-shaped (same constraints as PowerSGD
        compression — replicated params, pure data parallelism).
        ``incompatibility`` names the blocker when the dcn axis exists but
        the path cannot replace the flat psum."""
        gsk = self.grad_sync_kwargs
        if gsk.hierarchical is False:
            return False, None
        if int(self.mesh.shape.get("dcn", 1)) <= 1:
            if gsk.hierarchical:
                return False, "mesh has no dcn axis (ParallelismConfig.dcn_size <= 1)"
            return False, None
        pc = self.parallelism_config
        bad = {k: v for k, v in
               {"tp": pc.tp_size, "pp": pc.pp_size, "cp": pc.cp_size,
                "sp": pc.sp_size, "ep": pc.ep_size}.items() if v > 1}
        from .parallel.sharding import param_fsdp_axes, resolve_sharding_strategy

        strategy = resolve_sharding_strategy(self.fsdp_plugin, pc)
        params_sharded = bool(param_fsdp_axes(self.mesh, pc, strategy))
        offload_opt, _ = self._offload_flags()
        blockers = []
        if bad:
            blockers.append(f"non-dp axes {bad}")
        if params_sharded:
            blockers.append(f"params sharded ({strategy})")
        if offload_opt:
            blockers.append("cpu_offload")
        if self.gradient_state.num_steps > 1:
            blockers.append("gradient accumulation > 1")
        if self.policy.needs_loss_scaling:
            blockers.append("fp16 loss scaling")
        if gsk.comm_dtype or gsk.grad_dtype:
            blockers.append("comm_dtype/grad_dtype")
        if gsk.compression:
            blockers.append("compression='powersgd' (the flat DDP codec owns the step)")
        if blockers:
            return False, "; ".join(blockers)
        return True, None

    def _default_batch_spec(self):
        cfg = self.parallelism_config
        batch_axes = cfg.batch_dim_names or None
        seq_axes = cfg.seq_dim_names or None

        def _spec(x):
            ndim = np.ndim(x)
            if ndim == 0:
                return PartitionSpec()
            entries = [batch_axes]
            if ndim >= 2 and seq_axes:
                entries.append(seq_axes)
            while len(entries) < ndim:
                entries.append(None)
            return PartitionSpec(*entries)

        return _spec

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            return data_loader
        put_on_device = device_placement if device_placement is not None else self.device_placement
        dlc = self.dataloader_config
        prepared = prepare_data_loader(
            data_loader,
            device=self.device,
            split_batches=dlc.split_batches or self.split_batches,
            put_on_device=put_on_device,
            rng_types=self.rng_types,
            dispatch_batches=dlc.dispatch_batches,
            even_batches=dlc.even_batches,
            slice_fn_for_dispatch=slice_fn_for_dispatch,
            use_seedable_sampler=dlc.use_seedable_sampler,
            data_seed=dlc.data_seed,
            non_blocking=dlc.non_blocking,
            use_stateful_dataloader=dlc.use_stateful_dataloader,
            mesh=self.mesh,
            batch_spec=self._default_batch_spec(),
            parallelism_config=self.parallelism_config,
            prefetch_size=dlc.prefetch_size,
            transfer_retry_policy=self._transfer_retry_policy(),
            on_transfer_retry=self.goodput.record_retry,
        )
        if self.timeline is not None:
            # data_wait / h2d_staging phase spans ride the existing loader
            # hook points (data_loader.py) — host-side only
            prepared._timeline = self.timeline
        self._dataloaders.append(prepared)
        return prepared

    # ------------------------------------------------------------------
    # Train state + sharding plan
    # ------------------------------------------------------------------

    def init_params(self, module, rng, *sample_args, **sample_kwargs):
        """Abstract-init + shard: params materialize directly into their
        target shards (never a full replica per host — the big-model path,
        SURVEY §2.7 TPU-native note).  Under ``cpu_offload`` the outputs are
        placed in pinned host memory, but the init *computation* still
        stages the full-precision tree on device — for models whose fp32
        tree exceeds HBM, stream real weights leaf-wise via
        ``load_checkpoint_in_model`` or use
        :func:`~accelerate_tpu.big_modeling.init_params_leafwise`."""
        abstract = jax.eval_shape(partial(module.init, rng), *sample_args, **sample_kwargs)
        plan = self._params_plan(abstract)
        _, offload_params = self._offload_flags()
        if offload_params and host_offload_supported():
            plan = host_plan(plan)
        init_fn = jax.jit(partial(module.init, rng), out_shardings=plan)
        return init_fn(*sample_args, **sample_kwargs)

    def _params_plan(self, params_or_shapes):
        tp_rules = get_tp_rules(self.tp_config.plan) if self.tp_config is not None else (
            get_tp_rules("auto") if self.parallelism_config.tp_size > 1 else []
        )
        return make_sharding_plan(
            params_or_shapes,
            self.mesh,
            parallelism_config=self.parallelism_config,
            fsdp_plugin=self.fsdp_plugin,
            tp_rules=tp_rules,
        )

    def device_params(self, params):
        """Device-memory copies of (possibly host-offloaded) params.

        Under ``cpu_offload`` the fp32 masters live in pinned host memory;
        any consumer outside the prepared train step — eval, generation,
        export — needs HBM copies.  No-op for resident params, so it is
        always safe to call (reference analog: DeepSpeed gathers/unpartitions
        params for inference after ZeRO-offload training)."""
        def _leaf(x):
            s = getattr(x, "sharding", None)
            if isinstance(s, NamedSharding) and s.memory_kind not in (None, "device"):
                return jax.device_put(x, NamedSharding(s.mesh, s.spec))
            return x

        return jax.tree_util.tree_map(_leaf, params)

    def _transfer_retry_policy(self):
        """The ResiliencePlugin's bounded-retry budget as a RetryPolicy (the
        dataloaders' H2D staging shares it with checkpoint I/O)."""
        from .resilience.retry import RetryPolicy

        rp = self.resilience_plugin
        return RetryPolicy(retries=rp.io_retries, backoff_s=rp.io_backoff_s)

    def _offload_flags(self) -> tuple[bool, bool]:
        """(offload optimizer state, offload master params) — the ZeRO-offload
        configuration resolved from the FSDP plugin (reference DeepSpeed
        ``offload_optimizer_device``/``offload_param_device``,
        dataclasses.py:1172-1187)."""
        p = self.fsdp_plugin
        if p is None:
            return False, False
        return bool(p.cpu_offload), bool(p.cpu_offload and p.offload_params)

    def create_train_state(
        self,
        params,
        optimizer: Union[AcceleratedOptimizer, optax.GradientTransformation, str],
        apply_fn: Optional[Callable] = None,
        rng: Optional[jax.Array] = None,
        sharded: bool = True,
    ) -> "TrainState":
        """Build the sharded TrainState (params placed on the plan, optimizer
        state *initialized directly sharded* — the ZeRO property).
        ``optimizer`` may be a recipe name (see :meth:`prepare_optimizer`)."""
        if isinstance(optimizer, (str, optax.GradientTransformation)):
            optimizer = self.prepare_optimizer(optimizer)
        tx = optimizer.tx
        if rng is None:
            from .utils.random import get_rng_key

            # fold_in produces a fresh key array: the train step donates its
            # input state, and donating the shared root key would delete it
            rng = jax.random.fold_in(get_rng_key(), 0)

        offload_opt, offload_params = self._offload_flags()
        if sharded:
            plan = self._params_plan(params)
            # fp32 masters placed straight into pinned host memory under
            # offload — at 7B the fp32 tree must never transit HBM; the
            # train step fetches a compute-width device copy each step
            place_plan = (
                host_plan(plan) if offload_params and host_offload_supported() else plan
            )
            params = shard_params(params, place_plan)
            abstract_opt = jax.eval_shape(tx.init, params)
            opt_plan = make_opt_state_sharding_plan(
                abstract_opt, plan, self.mesh,
                parallelism_config=self.parallelism_config, fsdp_plugin=self.fsdp_plugin,
            )
            if offload_opt and host_offload_supported():
                # ZeRO-offload storage: the m/v moments (and the count
                # scalars — mixing spaces inside one optax update is
                # rejected by the memory-space checker) live in pinned host
                # memory from init on, and the init itself runs as host
                # compute — a device-side init would stage the full fp32
                # moment tree in HBM before writing the host outputs
                # (measured OOM at 7B).
                opt_plan = host_plan(opt_plan)

                def _host_init(p):
                    with compute_on("device_host"):
                        return tx.init(p)

                opt_state = jax.jit(_host_init, out_shardings=opt_plan)(params)
            else:
                opt_state = jax.jit(tx.init, out_shardings=opt_plan)(params)
        else:
            plan = None
            opt_state = tx.init(params)

        loss_scale = DynamicLossScale() if self.policy.needs_loss_scaling else None
        mode = self.gradient_state.plugin.mode
        accum_needed = self.gradient_state.num_steps > 1 and mode == "across_steps"
        if accum_needed and plan is not None:
            # accumulation buffers shard exactly like the params (plain
            # _tree_zeros_like leaves would be uncommitted and later pinned
            # replicated — a full gradient copy per device under FSDP)
            grad_accum = jax.jit(_tree_zeros_like, out_shardings=plan)(params)
        else:
            grad_accum = _tree_zeros_like(params) if accum_needed else None
        comm_state = None
        if self.grad_sync_kwargs.compression == "powersgd":
            from .parallel.powersgd import init_powersgd_state

            axes = self._compression_axes()
            dp_size = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
            qs, errs = init_powersgd_state(params, self.grad_sync_kwargs.rank, dp_size)
            if sharded:
                # Qs replicated; each rank owns its residual slice
                rep = NamedSharding(self.mesh, PartitionSpec())
                err_sh = NamedSharding(self.mesh, PartitionSpec(tuple(axes) or None))
                qs = jax.tree_util.tree_map(lambda q: jax.device_put(q, rep), qs)
                errs = jax.tree_util.tree_map(lambda e: jax.device_put(e, err_sh), errs)
            comm_state = (qs, errs)
        elif (self.grad_sync_kwargs.dcn_compression == "powersgd"
              and self._resolve_hierarchical()[0]):
            # DCN codec state for the hierarchical path: per-leaf slab error
            # buffers (one [rows, cols] residual per dp rank, sharded over
            # the joint dp axes) + replicated warm-start Qs.  Only built
            # when the hierarchical path will actually engage — prepare_
            # train_step raises on incompatible configs before a None
            # comm_state could silently drop the codec.
            from .parallel.hierarchical import init_dcn_powersgd_state

            axes = self._compression_axes()
            ici_axes = [a for a in axes if a != "dcn"]
            ici = int(np.prod([self.mesh.shape[a] for a in ici_axes])) if ici_axes else 1
            dcn = int(self.mesh.shape.get("dcn", 1))
            qs, errs = init_dcn_powersgd_state(
                params, self.grad_sync_kwargs.rank, dcn * ici, ici
            )
            if sharded:
                rep = NamedSharding(self.mesh, PartitionSpec())
                err_sh = NamedSharding(self.mesh, PartitionSpec(tuple(axes)))
                qs = jax.tree_util.tree_map(lambda q: jax.device_put(q, rep), qs)
                errs = jax.tree_util.tree_map(lambda e: jax.device_put(e, err_sh), errs)
            comm_state = (qs, errs)
        state = TrainState(
            step=jnp.int32(0),
            params=params,
            opt_state=opt_state,
            rng=rng,
            loss_scale=loss_scale,
            grad_accum=grad_accum,
            accum_step=jnp.int32(0) if accum_needed else None,
            comm_state=comm_state,
            guard_state=(
                _guard.init_guard_state() if self.resilience_plugin.nan_guard else None
            ),
            apply_fn=apply_fn,
            tx=tx,
        )
        if sharded:
            # Scalar members (step/rng/loss-scale counters) must live on the
            # same device set as the mesh-sharded params, or jit rejects the
            # mixed device sets.  jit-identity (not device_put) so placement
            # works multi-process, where the mesh spans non-addressable
            # devices.  Only genuine scalars/keys — never accidentally
            # replicate a full-size uncommitted array.
            replicated = NamedSharding(self.mesh, PartitionSpec())
            _place = jax.jit(lambda x: x, out_shardings=replicated)

            def _replicate_scalar(x):
                if (
                    isinstance(x, jax.Array)
                    and not isinstance(x.sharding, NamedSharding)
                    and (x.ndim == 0 or jnp.issubdtype(x.dtype, jax.dtypes.prng_key))
                ):
                    return _place(x)
                return x

            state = jax.tree_util.tree_map(_replicate_scalar, state)
        self._state_sharding = jax.tree_util.tree_map(
            lambda x: x.sharding if isinstance(x, jax.Array) else None,
            state,
        )
        return state

    # ------------------------------------------------------------------
    # The jitted train step
    # ------------------------------------------------------------------

    def prepare_train_step(
        self,
        loss_fn: Callable,
        max_grad_norm: Optional[float] = None,
        has_aux: bool = False,
        donate_state: bool = True,
    ) -> Callable:
        """Compile ``loss_fn(params, batch [, rng])`` into the full sharded
        train step (reference hot loop §3.4, collapsed into one jit).

        Returns ``step(state, batch) -> (new_state, metrics)`` where metrics
        holds ``loss``, ``grad_norm`` and (fp16) ``grads_finite``.
        """
        wants_rng = "rng" in inspect.signature(loss_fn).parameters
        accum_steps = self.gradient_state.num_steps
        mode = self.gradient_state.plugin.mode
        policy = self.policy
        comm_dtype = {"bf16": jnp.bfloat16, "fp16": jnp.float16, None: None}[self.grad_sync_kwargs.comm_dtype]
        offload_opt, offload_params = self._offload_flags()
        # NaN/Inf step guard (resilience/guard.py): a where-select skip-step
        # gated on isfinite(loss) & isfinite(global grad-norm) — the same
        # skipped-step mechanism the fp16 loss-scale overflow path uses, so
        # it composes with every offload/chunk branch below.  Counters ride
        # TrainState.guard_state; the Python wrapper enforces the
        # consecutive-skip abort.
        nan_guard = bool(self.resilience_plugin.nan_guard)
        guard_abort_after = (
            self.resilience_plugin.max_consecutive_nan_skips if nan_guard else 0
        )
        if nan_guard and mode == "across_steps" and accum_steps > 1:
            logger.warning(
                "nan_guard with gradient accumulation mode='across_steps' "
                "only protects the boundary update: a non-finite microbatch "
                "still pollutes the carried accumulator before the guard "
                "sees it. Use mode='in_step' (the default) for full coverage."
            )
        # memory-kind placement works on TPU; on the CPU test mesh the
        # storage stays in device memory but the host-compute update region
        # is still exercised, so numerics are pinned by the CPU suite.
        kinds_ok = offload_opt and host_offload_supported()
        chunk_bytes = (
            int(self.fsdp_plugin.host_update_chunk_gib * 2**30)
            if offload_opt
            and self.fsdp_plugin is not None
            and self.fsdp_plugin.host_update_chunk_gib
            else None
        )
        # 3-stage software pipeline over the chunk sequence (ops/streaming.py):
        # stage A (per-chunk D2H grad staging) and stage C (per-chunk output
        # write-back) are issued un-gated by the update token chain, so chunk
        # k+1's grads and chunk k-1's outputs are in transfer flight while
        # chunk k's host region runs.  host_update_pipeline=False restores
        # the fully serialized schedule (the A/B baseline).
        pipeline_offload = bool(
            chunk_bytes is not None
            and self.fsdp_plugin is not None
            and self.fsdp_plugin.host_update_pipeline
        )
        if chunk_bytes is not None:
            # per-group updates cannot be detected as wrong for cross-leaf
            # transforms (clip_by_global_norm's state is empty), so say it
            # loudly once per prepared step
            logger.warning(
                "host_update_chunk_gib=%s splits the optimizer update into "
                "per-leaf-group host regions. The optax chain must be "
                "per-leaf independent (adamw/lion/sgd/...); a cross-leaf "
                "transform like optax.clip_by_global_norm would silently use "
                "per-GROUP statistics — pass max_grad_norm to "
                "prepare_train_step for global clipping instead.",
                self.fsdp_plugin.host_update_chunk_gib,
            )
        if kinds_ok and mode == "across_steps" and accum_steps > 1:
            # across_steps carries the fp32 grad_accum tree in HBM between
            # steps (it feeds a lax.cond, which cannot mix memory spaces), so
            # the 'HBM never holds the fp32 grad tree' offload invariant does
            # not hold in this mode — at 7B that tree alone exceeds a v5e.
            logger.warning(
                "gradient accumulation mode='across_steps' keeps the fp32 "
                "accumulation tree resident in device memory, defeating part "
                "of the cpu_offload memory budget; use mode='in_step' (the "
                "default) for offload configs sized against HBM."
            )

        def _stored_params_shardings():
            ss = self._state_sharding
            return getattr(ss, "params", None) if ss is not None else None

        def fetch_params(params):
            """Device copies of host-resident master params (one H2D fetch per
            step; XLA's latency-hiding scheduler overlaps the per-leaf copies
            with the first layers' compute)."""
            psh = _stored_params_shardings()
            if not (offload_params and kinds_ok) or psh is None:
                return params
            # cast the fp32 masters to the compute dtype *on the host* so
            # only the compute-width copy crosses PCIe and HBM never holds
            # the fp32 tree (at 7B, the fp32 params alone exceed a v5e chip)
            with compute_on("device_host"):
                params = policy.cast_to_compute(params)
            return jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, s) if isinstance(s, NamedSharding) else p,
                params, device_plan(psh),
            )

        # DDP "sum" semantics: the GSPMD-implicit reduction produces the
        # global-mean gradient (grad of the global-mean loss), so
        # average_grads=False rescales the tree by the data-parallel world
        # size — the optimizer then sees the sum across dp ranks.
        _dp_axes = self._compression_axes()
        dp_world = int(np.prod([self.mesh.shape[a] for a in _dp_axes])) if _dp_axes else 1
        grad_scale = 1 if self.grad_sync_kwargs.average_grads else dp_world
        compute_width_grads = self.grad_sync_kwargs.grad_dtype is not None
        if compute_width_grads:
            if self.grad_sync_kwargs.grad_dtype != "bf16" or policy.needs_loss_scaling:
                raise ValueError(
                    "GradSyncKwargs.grad_dtype supports only 'bf16' without loss "
                    "scaling (fp16 grads must be unscaled in fp32); got "
                    f"grad_dtype={self.grad_sync_kwargs.grad_dtype!r} with "
                    f"mixed_precision={self.mixed_precision!r}"
                )

        def compute_grads(params, batch, rng, loss_scale):
            if compute_width_grads:
                # differentiate wrt the compute-width copy: every grad leaf is
                # born bf16 and the fp32 grad tree never exists in HBM — the
                # lever that lets a ~1B resident config keep cheap remat
                params = policy.cast_to_compute(params)

            def scaled_loss(p, mb):
                if not compute_width_grads:
                    p = policy.cast_to_compute(p)
                mb_args = (p, mb, rng) if wants_rng else (p, mb)
                out = loss_fn(*mb_args)
                loss, aux = (out if has_aux else (out, None))
                # the scalar loss always lives in fp32 (torch-AMP keeps
                # reductions fp32); otherwise scaling by 2^16 overflows fp16
                loss = loss.astype(jnp.float32)
                if loss_scale is not None:
                    loss = loss_scale.scale_loss(loss)
                return loss, aux

            (loss, aux), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params, batch)
            if grad_scale != 1:
                grads = jax.tree_util.tree_map(
                    lambda g: g * jnp.asarray(grad_scale, g.dtype), grads
                )
            if comm_dtype is not None:
                grads = jax.tree_util.tree_map(lambda g: g.astype(comm_dtype), grads)
            if compute_width_grads:
                # stay compute-width; per-leaf optimizer math promotes
                # against its fp32 state transiently
                return loss, aux, grads
            if not kinds_ok or policy.needs_loss_scaling:
                # fp16 loss scaling must unscale in fp32 — dividing fp16
                # grads by ~2^16 first would flush small gradients to zero,
                # defeating the point of scaling
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            # otherwise, under real host offload, grads stay in compute width
            # until the host upcasts them inside the update region: HBM never
            # holds the fp32 grad tree and the D2H transfer is half the bytes
            # (the DeepSpeed ZeRO-offload wire format)
            return loss, aux, grads

        def apply_update(state: TrainState, grads, loss):
            loss_scale = state.loss_scale
            if loss_scale is not None:
                grads = loss_scale.unscale(grads)
                loss = loss / loss_scale.scale
                finite = all_finite(grads)
                new_scale = loss_scale.update(finite)
            else:
                finite = jnp.bool_(True)
                new_scale = None
            # the skip-step select engages for fp16 overflow handling OR the
            # NaN guard; under the guard the finiteness predicate also folds
            # in the loss (and, below, the global grad-norm — one NaN/Inf
            # anywhere in the grad tree makes the norm non-finite)
            use_skip = (loss_scale is not None) or nan_guard
            if nan_guard:
                finite = jnp.logical_and(finite, jnp.isfinite(loss))

            # Under real host offload with clipping, the norm + clip move
            # into the host region: a device-side clip keeps every gradient
            # alive until the global norm is ready (an all-grads barrier —
            # at 7B that is the whole 13.5GiB bf16 grad tree resident at
            # once, measured OOM).  Without clipping the device norm is just
            # per-leaf partial sums and each grad streams D2H as backward
            # produces it, so it stays on device.
            gnorm_on_host = offload_opt and kinds_ok and max_grad_norm is not None
            if not gnorm_on_host:
                gnorm = global_norm(grads)
                if nan_guard:
                    finite = jnp.logical_and(finite, jnp.isfinite(gnorm))
                if max_grad_norm is not None:
                    clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                    # clip in each grad's own width: a fp32 scalar would
                    # promote a bf16 tree back to fp32 (the very tree
                    # grad_dtype="bf16" keeps out of HBM)
                    grads = jax.tree_util.tree_map(lambda g: g * clip.astype(g.dtype), grads)

            @jax.named_scope("optimizer_update")
            def run_update(grads, opt_state, params, finite):
                updates, new_opt = state.tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                if use_skip:
                    # fp16 overflow / NaN-guard skip: hold params/opt_state
                    # bitwise (reference skipped-step; resilience/guard.py)
                    new_params = _guard.select_tree(finite, new_params, params)
                    new_opt = _guard.select_tree(finite, new_opt, opt_state)
                return new_params, new_opt

            if offload_opt:
                # ZeRO-offload update: grads stream D2H, the optimizer math
                # runs as XLA host compute against the host-resident
                # moments/masters, and only what compute needs returns to HBM.
                params_master = state.params
                psh = _stored_params_shardings()
                grads_in, finite_in = grads, finite
                ghost = None
                # Stage A granularity: per-chunk D2H staging needs the
                # pipeline AND no host-side global clip (the clip's norm is
                # an all-grads barrier, so the whole tree must be host-side
                # before any chunk can start — bulk staging is then optimal).
                stage_a_per_chunk = pipeline_offload and not gnorm_on_host
                if kinds_ok and psh is not None:
                    ghost = host_plan(psh)
                    # every operand of the host region must sit in host memory
                    # space — jax 0.9 rejects mixed-space elementwise ops.
                    # Under the chunk pipeline each chunk stages its own
                    # grads (stage A below) instead of this bulk move.
                    if not stage_a_per_chunk:
                        grads_in = jax.tree_util.tree_map(jax.device_put, grads, ghost)
                    if not offload_params:
                        params_master = jax.tree_util.tree_map(jax.device_put, state.params, ghost)
                    if use_skip:
                        # graft-lint: disable=GL103 -- the skip predicate must live in host space: every operand of the host-compute update region shares one memory space
                        finite_in = jax.device_put(
                            finite, NamedSharding(self.mesh, PartitionSpec(), memory_kind="pinned_host")
                        )
                host_rep = NamedSharding(
                    self.mesh, PartitionSpec(), memory_kind="pinned_host"
                ) if kinds_ok else None
                if chunk_bytes is not None:
                    # Chunked host update: one compute_on region per leaf
                    # group bounds the host's transient working set (fp32
                    # grad upcasts + moment temps) — the monolithic region's
                    # whole-tree transients crash the worker host at 7B+adamw.
                    treedef = jax.tree_util.tree_structure(params_master)
                    groups = _host_update_groups(params_master, chunk_bytes)
                    if gnorm_on_host:
                        with compute_on("device_host"):
                            gnorm = global_norm(grads_in)
                            clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                            if nan_guard:
                                finite_in = jnp.logical_and(
                                    finite_in, jnp.isfinite(gnorm)
                                )
                    group_outs = []
                    token = None
                    # Probe the FULL tree once: per-group const presence can
                    # vary with the group's leaf shapes (adafactor's factored-
                    # moment constants only exist for >=2-D leaves), but every
                    # group's consts arise from update math the full-tree
                    # trace also contains — so a const-free full trace proves
                    # all groups const-free, and const-free optimizers
                    # (adamw/lion/sgd) skip the per-group probe traces.
                    needs_hoist = (
                        kinds_ok
                        and psh is not None
                        and _host_constant_hoist(
                            run_update, host_rep,
                            params_master, state.opt_state, params_master, finite_in,
                        ) is not run_update
                    )
                    osh = getattr(self._state_sharding, "opt_state", None)
                    for idxs in groups:
                        if stage_a_per_chunk:
                            # Stage A (D2H): this chunk's grads are staged as
                            # their own transfer, OUTSIDE the token chain —
                            # chunk k+1's grads fly while chunk k's host
                            # region runs.  Same values as the bulk move, so
                            # the update stays bitwise-identical.
                            g_grads = _slice_congruent(grads, treedef, idxs)
                            if kinds_ok and ghost is not None:
                                g_grads = _stage_put(
                                    g_grads, _slice_congruent(ghost, treedef, idxs)
                                )
                        else:
                            g_grads = _slice_congruent(grads_in, treedef, idxs)
                        g_params = _slice_congruent(params_master, treedef, idxs)
                        g_opt = _slice_congruent(state.opt_state, treedef, idxs)
                        upd = run_update
                        if needs_hoist:
                            upd = _host_constant_hoist(
                                run_update, host_rep, g_params, g_opt, g_params, finite_in
                            )
                        with compute_on("device_host"):
                            if token is not None:
                                # serialize the regions: without a data
                                # dependency the scheduler may overlap groups,
                                # re-creating the unbounded working set
                                # chunking exists to avoid.  The barrier MUST
                                # live inside the host region — outside it is
                                # a device op, and bouncing every grad leaf
                                # through HBM at 7B re-creates the OOM
                                # (measured 59G) offload exists to avoid.
                                g_grads = tuple(
                                    jax.lax.optimization_barrier((g, token))[0]
                                    for g in g_grads
                                )
                            if kinds_ok:
                                g_grads = tuple(g.astype(jnp.float32) for g in g_grads)
                            if gnorm_on_host:
                                g_grads = tuple(g * clip for g in g_grads)
                            g_new_params, g_new_opt = upd(
                                g_grads, g_opt, g_params, finite_in
                            )
                            # token touches every output so the next group
                            # cannot start until this one's writes finished
                            deps = [
                                leaf.ravel()[0]
                                for leaf in (
                                    list(g_new_params)
                                    + jax.tree_util.tree_leaves(g_new_opt)
                                )
                                if hasattr(leaf, "ravel") and getattr(leaf, "size", 0)
                            ]
                            token = sum(deps) if deps else None
                        if pipeline_offload and psh is not None:
                            # Stage C (write-back): this chunk's outputs
                            # return to their storage spaces immediately and
                            # OFF the token chain (the token was formed from
                            # the pre-placement host values above), so chunk
                            # k-1's write-back flies under chunk k's update.
                            # Deliberately NOT gated on kinds_ok: on the CPU
                            # test mesh the placements are memory-kind-free
                            # no-ops value-wise, but they make the pipelined
                            # trace genuinely different from the serial one —
                            # which is what gives the pipelined-vs-serial
                            # parity tests teeth off-chip.
                            g_new_params = _stage_put(
                                g_new_params, _slice_congruent(psh, treedef, idxs)
                            )
                            if osh is not None:
                                g_new_opt = _stage_put(
                                    g_new_opt, _slice_congruent(osh, treedef, idxs)
                                )
                        group_outs.append((g_new_params, g_new_opt))
                    new_params = _merge_congruent(
                        params_master, [o[0] for o in group_outs], treedef, groups
                    )
                    new_opt = _merge_congruent(
                        state.opt_state, [o[1] for o in group_outs], treedef, groups
                    )
                else:
                    # hoist only when operands were actually moved to host
                    # space (kinds_ok AND psh) — pinned-host consts against
                    # device-resident operands would themselves mix spaces
                    upd = (
                        _host_constant_hoist(
                            run_update, host_rep,
                            params_master, state.opt_state, params_master, finite_in,
                        ) if kinds_ok and psh is not None else run_update
                    )
                    with compute_on("device_host"):
                        if kinds_ok:
                            # grads crossed PCIe at compute width; the host
                            # upcasts before touching the fp32 moments/masters
                            grads_in = jax.tree_util.tree_map(
                                lambda g: g.astype(jnp.float32), grads_in
                            )
                        if gnorm_on_host:
                            gnorm = global_norm(grads_in)
                            if nan_guard:
                                finite_in = jnp.logical_and(
                                    finite_in, jnp.isfinite(gnorm)
                                )
                            if max_grad_norm is not None:
                                clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                                grads_in = jax.tree_util.tree_map(lambda g: g * clip, grads_in)
                        new_params, new_opt = upd(grads_in, state.opt_state, params_master, finite_in)
                if kinds_ok and psh is not None and not (
                    chunk_bytes is not None and pipeline_offload
                ):
                    # pin the host-execute outputs back to their storage
                    # spaces — libtpu's host-compute alias assigner aborts on
                    # unannotated outputs aliased with pinned-host inputs.
                    # (The chunk pipeline already placed each chunk's outputs
                    # in stage C above.)
                    osh = getattr(self._state_sharding, "opt_state", None)
                    if osh is not None:
                        new_opt = jax.tree_util.tree_map(jax.device_put, new_opt, osh)
                    new_params = jax.tree_util.tree_map(jax.device_put, new_params, psh)
                if gnorm_on_host:
                    # the metric scalar returns to device memory space
                    gnorm = jax.device_put(gnorm, NamedSharding(self.mesh, PartitionSpec()))
            else:
                new_params, new_opt = run_update(grads, state.opt_state, state.params, finite)
            metrics = {"loss": loss, "grad_norm": gnorm}
            if loss_scale is not None:
                metrics["grads_finite"] = finite
                metrics["loss_scale"] = new_scale.scale
            new_guard_state = state.guard_state
            if nan_guard:
                if gnorm_on_host:
                    # fold the norm's finiteness into the device-side metric
                    # predicate too (the host-side finite_in already carried
                    # it into the update) — gnorm is back in device space here
                    finite = jnp.logical_and(finite, jnp.isfinite(gnorm))
                if state.guard_state is not None:
                    new_guard_state = _guard.update_guard_counters(
                        state.guard_state, finite
                    )
                    metrics = _guard.guard_metrics(metrics, finite, new_guard_state)
                else:
                    metrics["nan_skipped"] = jnp.logical_not(finite)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                loss_scale=new_scale,
                guard_state=new_guard_state,
            )
            return new_state, metrics

        compression = self.grad_sync_kwargs.compression
        if compression not in (None, "powersgd"):
            raise ValueError(f"unknown GradSyncKwargs.compression {compression!r}; options: 'powersgd'")
        dcn_codec = self.grad_sync_kwargs.dcn_compression
        if dcn_codec not in (None, "powersgd"):
            raise ValueError(
                f"unknown GradSyncKwargs.dcn_compression {dcn_codec!r}; options: 'powersgd'"
            )
        # Hierarchical ICI->DCN reduction (parallel/hierarchical.py): engages
        # when the mesh carries a non-trivial cross-slice `dcn` axis and the
        # config is DDP-shaped — then the shard_map below replaces the flat
        # joint-axis psum with reduce-scatter(ICI) -> slab all-reduce(DCN,
        # optionally PowerSGD-compressed) -> all-gather(ICI).
        hier_engage, hier_why = self._resolve_hierarchical()
        if hier_engage and has_aux:
            hier_engage, hier_why = False, "has_aux"
        dcn_size_mesh = int(self.mesh.shape.get("dcn", 1))
        if self.grad_sync_kwargs.hierarchical and not hier_engage:
            raise ValueError(
                "GradSyncKwargs.hierarchical=True but the ICI->DCN path cannot "
                f"engage: {hier_why}. The hierarchical reduction is the DDP "
                "comm-hook shape: a dcn mesh axis > 1 plus pure data "
                "parallelism with replicated params (sharding_strategy "
                "NO_SHARD or SHARD_GRAD_OP), no cpu_offload, accumulation of "
                "1, no fp16 scaling, no aux outputs, no comm_dtype/grad_dtype."
            )
        if dcn_codec and not hier_engage:
            raise ValueError(
                f"GradSyncKwargs.dcn_compression={dcn_codec!r} rides the "
                f"hierarchical ICI->DCN path, which cannot engage: "
                f"{hier_why or 'mesh has no dcn axis'}"
            )
        if not hier_engage and hier_why and dcn_size_mesh > 1:
            logger.warning(
                "mesh has a dcn axis (size %d) but the hierarchical gradient "
                "sync cannot engage (%s): falling back to the flat joint-axis "
                "reduction, whose cross-slice hop carries ici_size redundant "
                "full-gradient copies over DCN", dcn_size_mesh, hier_why,
            )
        _hier_axes = tuple(self._compression_axes())
        self._dcn_sync = {
            "enabled": bool(hier_engage),
            "dcn_size": dcn_size_mesh,
            "ici_size": int(np.prod([self.mesh.shape[a] for a in _hier_axes
                                     if a != "dcn"])) if _hier_axes else 1,
            "compression": dcn_codec if hier_engage else None,
            "why_not": None if hier_engage else hier_why,
        }
        if compression == "powersgd":
            pc = self.parallelism_config
            bad = {k: v for k, v in
                   {"tp": pc.tp_size, "pp": pc.pp_size, "cp": pc.cp_size,
                    "sp": pc.sp_size, "ep": pc.ep_size}.items() if v > 1}
            width_knobs = self.grad_sync_kwargs.comm_dtype or self.grad_sync_kwargs.grad_dtype
            # DDP-style compression needs replicated params: under
            # FULL_SHARD/HYBRID (ZeRO-3) the shard_map's replicated in_specs
            # would force a full param all-gather every step plus replicated
            # fp32 grad/error trees — inverting the wire-bytes/memory purpose
            # on configs sized for ZeRO.  NO_SHARD/SHARD_GRAD_OP keep params
            # replicated (SHARD_GRAD_OP shards only optimizer state, which
            # never crosses the shard_map).
            from .parallel.sharding import param_fsdp_axes, resolve_sharding_strategy

            strategy = resolve_sharding_strategy(self.fsdp_plugin, pc)
            params_sharded = bool(param_fsdp_axes(self.mesh, pc, strategy))
            if (bad or params_sharded or offload_opt or accum_steps > 1
                    or policy.needs_loss_scaling or has_aux or width_knobs):
                raise ValueError(
                    "compression='powersgd' is the DDP comm-hook analog: pure "
                    "data parallelism with replicated params (sharding_strategy "
                    "NO_SHARD or SHARD_GRAD_OP — FULL_SHARD/HYBRID would "
                    "all-gather every param each step inside the shard_map), "
                    "no cpu_offload, accumulation of 1, no "
                    "fp16 scaling, no aux outputs, and no comm_dtype/"
                    "grad_dtype (the factor psums are fp32 — a width knob "
                    "would be silently ignored). Offending config: "
                    f"{bad or ''}"
                    f"{' params-sharded(' + str(strategy) + ')' if params_sharded else ''}"
                    f"{' offload' if offload_opt else ''}"
                    f"{' accum>1' if accum_steps > 1 else ''}"
                    f"{' fp16' if policy.needs_loss_scaling else ''}"
                    f"{' has_aux' if has_aux else ''}"
                    f"{' comm_dtype/grad_dtype' if width_knobs else ''}"
                )
            from .parallel.powersgd import compress_decompress

            psgd_rank = self.grad_sync_kwargs.rank
            axes = tuple(self._compression_axes())
            err_spec = PartitionSpec(axes)
            from jax import shard_map as _shard_map

            def _psgd_local(params, mb, use_rng, qs, errs):
                def loss_only(p):
                    p = policy.cast_to_compute(p)
                    mb_args = (p, mb, use_rng) if wants_rng else (p, mb)
                    return loss_fn(*mb_args).astype(jnp.float32)

                loss, grads = jax.value_and_grad(loss_only)(params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
                errs_local = jax.tree_util.tree_map(lambda e: e[0], errs)
                g_hat, new_qs, new_errs = compress_decompress(
                    grads, qs, errs_local, axes, psgd_rank
                )
                if grad_scale != 1:
                    # sum semantics: compression runs at mean scale (the EF
                    # residual is self-consistent either way); the optimizer
                    # sees the dp-sum like the dense path
                    g_hat = jax.tree_util.tree_map(
                        lambda g: g * jnp.asarray(grad_scale, g.dtype), g_hat
                    )
                new_errs = jax.tree_util.tree_map(lambda e: e[None], new_errs)
                return jax.lax.pmean(loss, axes), g_hat, new_qs, new_errs

            def step_fn(state: TrainState, batch):
                rng, use_rng = jax.random.split(state.rng)
                qs, errs = state.comm_state
                spec_of = self._default_batch_spec()
                batch_specs = jax.tree_util.tree_map(spec_of, batch)
                fn = _shard_map(
                    _psgd_local, mesh=self.mesh,
                    in_specs=(PartitionSpec(), batch_specs, PartitionSpec(),
                              PartitionSpec(), err_spec),
                    out_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec(), err_spec),
                    check_vma=False,
                )
                loss, g_hat, new_qs, new_errs = fn(state.params, batch, use_rng, qs, errs)
                new_state, metrics = apply_update(
                    state.replace(rng=rng, comm_state=(new_qs, new_errs)), g_hat, loss
                )
                return new_state, metrics

        elif hier_engage:
            from .parallel.hierarchical import hierarchical_sync

            psgd_rank = self.grad_sync_kwargs.rank
            # trivial (size-1) axes are dropped from the collective calls:
            # reducing over them is a no-op, and joint-axis reduce-scatter
            # thunks carrying dead axes proved crash-prone on the CPU backend
            hier_axes = tuple(a for a in _hier_axes
                              if int(self.mesh.shape.get(a, 1)) > 1)
            ici_axes = tuple(a for a in hier_axes if a != "dcn")
            err_spec = PartitionSpec(hier_axes)
            from jax import shard_map as _shard_map

            def _hier_grads(params, mb, use_rng, qs, errs):
                """Per-rank loss/grad + the three-phase reduction.  ``qs``/
                ``errs`` are the DCN PowerSGD state (None trees = dense DCN
                hop); returns world-MEAN grads like the flat pmean."""
                def loss_only(p):
                    p = policy.cast_to_compute(p)
                    mb_args = (p, mb, use_rng) if wants_rng else (p, mb)
                    return loss_fn(*mb_args).astype(jnp.float32)

                loss, grads = jax.value_and_grad(loss_only)(params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
                errs_local = jax.tree_util.tree_map(lambda e: e[0], errs)
                g_hat, new_qs, new_errs = hierarchical_sync(
                    grads, ici_axes, "dcn",
                    qs=qs, errs=errs_local, rank=psgd_rank,
                )
                if grad_scale != 1:
                    # sum semantics: the schedule reduces at mean scale (the
                    # EF residual is self-consistent either way); the
                    # optimizer sees the dp-sum like the dense path
                    g_hat = jax.tree_util.tree_map(
                        lambda g: g * jnp.asarray(grad_scale, g.dtype), g_hat
                    )
                new_errs = jax.tree_util.tree_map(lambda e: e[None], new_errs)
                # loss averaged in the SAME two-stage order as the grads
                # (ICI first, then the dcn hop): a flat joint-axis pmean
                # leaves the reduction order to the backend, and the order
                # differs between a single-process mesh and a launched gang
                # — the one float-associativity leak in the bitwise
                # process-count-parity contract
                loss = jax.lax.pmean(loss, ici_axes) if ici_axes else loss
                return jax.lax.pmean(loss, "dcn"), g_hat, new_qs, new_errs

            if dcn_codec:

                def step_fn(state: TrainState, batch):
                    rng, use_rng = jax.random.split(state.rng)
                    qs, errs = state.comm_state
                    spec_of = self._default_batch_spec()
                    batch_specs = jax.tree_util.tree_map(spec_of, batch)
                    fn = _shard_map(
                        _hier_grads, mesh=self.mesh,
                        in_specs=(PartitionSpec(), batch_specs, PartitionSpec(),
                                  PartitionSpec(), err_spec),
                        out_specs=(PartitionSpec(), PartitionSpec(),
                                   PartitionSpec(), err_spec),
                        check_vma=False,
                    )
                    loss, g_hat, new_qs, new_errs = fn(
                        state.params, batch, use_rng, qs, errs
                    )
                    new_state, metrics = apply_update(
                        state.replace(rng=rng, comm_state=(new_qs, new_errs)),
                        g_hat, loss,
                    )
                    return new_state, metrics

            else:

                def _hier_dense(params, mb, use_rng):
                    loss, g_hat, _, _ = _hier_grads(params, mb, use_rng, None, None)
                    return loss, g_hat

                def step_fn(state: TrainState, batch):
                    rng, use_rng = jax.random.split(state.rng)
                    spec_of = self._default_batch_spec()
                    batch_specs = jax.tree_util.tree_map(spec_of, batch)
                    fn = _shard_map(
                        _hier_dense, mesh=self.mesh,
                        in_specs=(PartitionSpec(), batch_specs, PartitionSpec()),
                        out_specs=(PartitionSpec(), PartitionSpec()),
                        check_vma=False,
                    )
                    loss, g_hat = fn(state.params, batch, use_rng)
                    new_state, metrics = apply_update(state.replace(rng=rng), g_hat, loss)
                    return new_state, metrics

        elif mode == "in_step" and accum_steps > 1:

            def step_fn(state: TrainState, batch):
                rng, use_rng = jax.random.split(state.rng)
                params_c = fetch_params(state.params)

                def microbatch(carry, mb):
                    grads_acc, loss_acc, _prev_aux = carry
                    loss, aux, grads = compute_grads(params_c, mb, use_rng, state.loss_scale)
                    # the carry accumulates in fp32 regardless of the grad
                    # wire dtype: summing accum_steps microbatches in bf16
                    # would lose ~log2(accum_steps) mantissa bits
                    grads_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
                    )
                    # aux rides the carry (overwritten each microbatch) so only
                    # one copy is live — stacking it as scan output would cost
                    # accum_steps× the aux memory.
                    return (grads_acc, loss_acc + loss, aux), None

                def reshape(x):
                    if np.ndim(x) == 0:
                        return x
                    b = x.shape[0]
                    if b % accum_steps != 0:
                        raise ValueError(
                            f"batch dim {b} not divisible by gradient_accumulation_steps {accum_steps}"
                        )
                    # graft-lint: disable=GL305 -- batch shapes are pinned by the dataloader; the accumulation reshape specializes once per fixed batch shape, never mid-traffic
                    return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

                micro = jax.tree_util.tree_map(reshape, batch)
                zeros = _tree_zeros_like(params_c)
                if has_aux:
                    first_mb = jax.tree_util.tree_map(lambda x: x[0] if np.ndim(x) else x, micro)
                    aux0 = jax.eval_shape(
                        lambda p, mb: loss_fn(*((p, mb, use_rng) if wants_rng else (p, mb)))[1],
                        policy.cast_to_compute(params_c), first_mb,
                    )
                    aux0 = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), aux0)
                else:
                    aux0 = None
                (grads, loss_sum, aux), _ = jax.lax.scan(
                    microbatch, (zeros, jnp.float32(0.0), aux0), micro
                )
                grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
                if kinds_ok and not policy.needs_loss_scaling:
                    # one downcast of the accumulated mean before the D2H
                    # stream: the host region upcasts again before touching
                    # the fp32 moments/masters, so this halves the wire bytes
                    # without giving up fp32 accumulation across microbatches
                    grads = policy.cast_to_compute(grads)
                loss = loss_sum / accum_steps
                new_state, metrics = apply_update(state.replace(rng=rng), grads, loss)
                if has_aux:
                    # last microbatch's aux (e.g. final batch-norm stats)
                    metrics["aux"] = aux
                return new_state, metrics

        elif mode == "across_steps" and accum_steps > 1:

            def step_fn(state: TrainState, batch):
                rng, use_rng = jax.random.split(state.rng)
                loss, aux, grads = compute_grads(
                    fetch_params(state.params), batch, use_rng, state.loss_scale)
                grad_accum = jax.tree_util.tree_map(jnp.add, state.grad_accum, grads)
                accum_step = state.accum_step + 1
                is_boundary = accum_step >= accum_steps

                def do_update(operand):
                    st, acc = operand
                    mean_grads = jax.tree_util.tree_map(lambda g: g / accum_steps, acc)
                    new_st, _m = apply_update(st, mean_grads, loss)
                    return new_st.replace(
                        grad_accum=_tree_zeros_like(acc), accum_step=jnp.int32(0)
                    )

                def no_update(operand):
                    st, acc = operand
                    return st.replace(grad_accum=acc, accum_step=accum_step)

                base = state.replace(rng=rng)
                new_state = jax.lax.cond(is_boundary, do_update, no_update, (base, grad_accum))
                metrics = {
                    "loss": loss if state.loss_scale is None else loss / state.loss_scale.scale,
                    "grad_norm": global_norm(grads),
                    "synced": is_boundary,
                }
                if has_aux:
                    metrics["aux"] = aux
                return new_state, metrics

        else:

            def step_fn(state: TrainState, batch):
                rng, use_rng = jax.random.split(state.rng)
                loss, aux, grads = compute_grads(
                    fetch_params(state.params), batch, use_rng, state.loss_scale)
                new_state, metrics = apply_update(state.replace(rng=rng), grads, loss)
                if has_aux:
                    metrics["aux"] = aux
                return new_state, metrics

        # Pin the returned state to the plan's shardings: without this, GSPMD
        # propagation may prefer a compute-time layout and reshard the whole
        # param tree at step entry every step ("involuntary full
        # rematerialization" under cp/sp + FSDP joint-axis sharding).  Input
        # shardings come from the committed arrays; constraining the output
        # pins both ends of the steady-state loop.  self._state_sharding is
        # read at trace time (not prepare time) so prepare/create ordering
        # doesn't matter, and a structure mismatch (state from a different
        # create_train_state) degrades to the unpinned behavior.
        def pinned_step_fn(state, batch):
            new_state, metrics = step_fn(state, batch)
            state_sharding = self._state_sharding
            if state_sharding is not None:

                def _pin(x, s):
                    if not isinstance(s, NamedSharding):
                        return x
                    if s.memory_kind not in (None, "device"):
                        # host-resident members (offloaded opt state/masters)
                        # were already placed by apply_update; device_put is a
                        # no-op there and with_sharding_constraint would strip
                        # the memory kind
                        # graft-lint: disable=GL103 -- re-pins host-resident state members to their offload memory kind; a no-op for buffers apply_update already placed, never a data transfer
                        return jax.device_put(x, s)
                    return jax.lax.with_sharding_constraint(x, s)

                try:
                    new_state = jax.tree_util.tree_map(_pin, new_state, state_sharding)
                except ValueError:
                    pass
            return new_state, metrics

        jitted = jax.jit(pinned_step_fn, donate_argnums=(0,) if donate_state else ())
        # resolved once at prepare time: the flag must not cost the hot
        # training-step wrapper an environ lookup per call when unset
        lint_at_first_call = parse_flag_from_env("ACCELERATE_LINT")

        def wrapped(state, batch):
            if lint_at_first_call and wrapped._lint_report is None:
                # audit at first compile: trace-only (nothing executes, the
                # donated buffers are untouched), findings go through
                # logging.py + any active trackers
                wrapped._lint_report = self.audit_step(wrapped, state, batch)
            # fault-injection hook (resilience/faults.py): a no-op None check
            # unless a deterministic plan is installed
            for ev in _faults.fault_point("step"):
                if ev.kind == "preempt":
                    # a REAL signal through the installed handler — the same
                    # delivery path a cloud preemption notice takes
                    import signal as _signal

                    handler = self.install_preemption_handler()
                    os.kill(os.getpid(), handler.signals[0] if handler.signals
                            else _signal.SIGTERM)
                elif ev.kind == "nan_grad":
                    batch = _faults.poison_batch(batch)
                elif ev.kind == "straggler":
                    # deterministic host stall: skews this rank's step-
                    # boundary arrival against its peers (what the agreed
                    # preemption stop must absorb without shard skew)
                    time.sleep(_faults.STRAGGLER_STALL_S)
                elif ev.kind == "rank_loss":
                    # this rank's state is gone — NOT retryable; the caller
                    # routes the gang through Accelerator.recover()'s ladder
                    raise _faults.RankLostError(
                        f"injected rank loss at step {self.step_count + 1} "
                        f"(process {self.process_index})"
                    )
            if not getattr(self, "_in_accumulate", False):
                self.step_count += 1
                # goodput counts in step_count units (the accumulate()
                # context owns both when it wraps the call) so replay/skip
                # accounting subtracts like units from like
                self.goodput.record_step()
                self.gradient_state._set_sync_gradients(
                    mode != "across_steps" or (self.step_count % accum_steps == 0)
                )
            # training timeline (telemetry/timeline.py): host-side phase
            # spans only — jax dispatch is async, so step_dispatch measures
            # host dispatch time, not device compute (docs/observability.md)
            timeline = self.timeline
            slo = self.slo_monitor
            dispatch_cm = (
                timeline.phase("step_dispatch", step=self.step_count)
                if timeline is not None else contextlib.nullcontext()
            )
            with dispatch_cm:
                new_state, metrics = jitted(state, batch)
            if nan_guard and isinstance(metrics, dict) \
                    and "consecutive_nan_skips" in metrics:
                # one scalar host fetch per armed step: it keeps the goodput
                # counters (and bench's always-emitted nan_skips) truthful
                # even with the abort disabled, and training loops fetch the
                # loss scalar anyway so this rarely adds a real sync.  The
                # zero-sync option is disabling the guard, not the abort.
                if timeline is not None:
                    with timeline.phase("guard_sync", step=self.step_count):
                        consecutive = int(metrics["consecutive_nan_skips"])
                else:
                    consecutive = int(metrics["consecutive_nan_skips"])
                if bool(metrics["nan_skipped"]):
                    self.goodput.record_nan_skip()
                _guard.check_abort(consecutive, guard_abort_after)
            if slo is not None:
                # step_time_s is the INTER-STEP CADENCE (host wall time
                # between consecutive wrapped-step calls, first step
                # skipped) — a delta around the jitted call alone would
                # measure async dispatch, not compute (the GL109 hazard);
                # cadence tracks true steady-state step time with zero
                # added syncs because training loops fetch the loss scalar
                # between calls anyway
                now = time.perf_counter()
                prev = self._slo_prev_step_t
                self._slo_prev_step_t = now
                if prev is not None:
                    slo.observe("step_time_s", now - prev)
                slo.observe("goodput_frac", self.goodput.goodput_frac())
            rp = self.resilience_plugin
            if rp.peer_snapshot_every > 0 and not getattr(self, "_in_accumulate", False):
                # peer-redundant hot snapshot (resilience/peer_ckpt.py): armed
                # lazily at the first post-step boundary so the schema gate
                # sees the REAL prepared state; the device→host copy inside is
                # the only synchronous part (CheckFreq), and it runs on the
                # NEW state — the donated input buffers are already dead here,
                # so there is no aliasing window (the GL206 hazard)
                if self._peer_snapshotter is None:
                    self._peer_snapshotter = _peer_ckpt.PeerSnapshotter(
                        new_state, rp.peer_snapshot_every,
                        keep=rp.peer_snapshot_keep,
                    )
                self._peer_snapshotter.maybe_snapshot(new_state, self.step_count)
            if self._preemption is not None and self._agreed_preemption():
                # stop AT the step boundary: the post-step state is exactly
                # consistent with the dataloader position and step counters,
                # so the resumed run replays nothing and skips nothing.
                # Multi-process: the stop is AGREED (any-rank OR) so every
                # rank reaches the emergency checkpoint's collectives — a
                # single preempted rank exiting alone would deadlock the
                # sharded save on its peers.
                self._preemption_exit(new_state)
            return new_state, metrics

        wrapped._jitted = jitted
        wrapped._lint_report = None
        self._prepared_train_step = wrapped
        return wrapped

    def reset_step_cadence(self) -> None:
        """Re-anchor the SLO ``step_time_s`` cadence after a legitimate
        non-step pause (an eval loop, a manual stall): the next wrapped
        step starts a fresh gap instead of observing the pause as one giant
        step time (the P² p99 marker never forgets a max, so a single
        outlier could spuriously trip a healthy run's SLO).  Checkpoint
        drains reset this automatically."""
        self._slo_prev_step_t = None

    @property
    def dcn_sync(self) -> Optional[dict]:
        """How the last prepared train step resolved the ICI->DCN
        hierarchical reduction (``None`` before ``prepare_train_step``):
        ``{"enabled", "dcn_size", "ici_size", "compression", "why_not"}``."""
        return getattr(self, "_dcn_sync", None)

    def dcn_sync_accounting(self, params, step_compute_s: Optional[float] = None) -> dict:
        """Predicted per-device DCN bytes for ``params``'s gradient sync on
        this mesh (``parallel/hierarchical.dcn_comm_accounting``): the
        hierarchical schedule vs the flat-reduce twin, with the PowerSGD
        codec folded in when ``GradSyncKwargs.dcn_compression`` is set.
        Zeros-clean on meshes without a ``dcn`` axis."""
        from .parallel.hierarchical import dcn_comm_accounting

        axes = self._compression_axes()
        ici = int(np.prod([self.mesh.shape[a] for a in axes if a != "dcn"])) or 1
        dcn = int(self.mesh.shape.get("dcn", 1))
        sync = self.dcn_sync
        compression = (
            sync["compression"] if sync is not None
            else self.grad_sync_kwargs.dcn_compression
        )
        return dcn_comm_accounting(
            params, ici_size=ici, dcn_size=dcn,
            compression=compression, rank=self.grad_sync_kwargs.rank,
            step_compute_s=step_compute_s,
        )

    @property
    def compile_events(self) -> int:
        """Real XLA backend compiles observed since this accelerator was
        built (process-wide jax.monitoring stream, as a delta).  Snapshot
        after warmup and watch for growth: a steady-state training loop
        that keeps compiling is re-keying the jit cache every step — the
        GL304 promotion-drift shape the preflight rules exist to catch."""
        return self._compile_counter.count - self._compile_baseline

    def audit_step(self, step=None, *example_args, log: bool = True, **audit_kwargs):
        """Run the graft-lint jaxpr auditor over a prepared train step
        without executing it on device (``analysis/jaxpr_audit.py``).

        ``step`` defaults to the last :meth:`prepare_train_step` result;
        ``example_args`` are the ``(state, batch)`` the step would be called
        with — concrete arrays or ``jax.ShapeDtypeStruct`` stand-ins (the
        audit is a pure abstract trace, so donated buffers stay intact).
        Findings are reported through :mod:`.logging` and, when trackers are
        active, as ``graft_lint/*`` counters; the :class:`analysis.Report`
        is returned either way.  Opt-in at runtime with ``ACCELERATE_LINT=1``
        — every prepared step then audits itself at first call.
        """
        from .analysis import Severity, audit_jitted

        if step is None:
            step = getattr(self, "_prepared_train_step", None)
        if step is None:
            raise ValueError("no prepared train step to audit — call prepare_train_step first")
        report = audit_jitted(step, *example_args, **audit_kwargs)
        if log:
            for f in report.unsuppressed():
                emit = logger.error if f.severity >= Severity.ERROR else logger.warning
                emit("graft-lint %s at %s: %s", f.rule, f.location, f.message)
            counts = report.counts()
            logger.info(
                "graft-lint step audit: %d error(s), %d warning(s), %d suppressed",
                counts["error"], counts["warning"], counts["suppressed"],
            )
            if self.trackers:
                self.log({f"graft_lint/{k}": v for k, v in counts.items()})
        return report

    def prepare_eval_step(self, eval_fn: Callable) -> Callable:
        """jit an eval function ``(params, batch) -> outputs`` with compute
        casting applied (the autocast analog for eval, reference :1791).
        Host-offloaded masters are fetched to device memory first."""
        policy = self.policy

        @jax.jit
        def jitted(params, batch):
            return eval_fn(policy.cast_to_compute(params), batch)

        def step(params, batch):
            return jitted(self.device_params(params), batch)

        return step

    # ------------------------------------------------------------------
    # Reference training-loop API surface
    # ------------------------------------------------------------------

    def backward(self, loss=None, **kwargs):
        raise RuntimeError(
            "JAX autodiff is functional: there is no .backward(). Define "
            "`loss_fn(params, batch)` and use `accelerator.prepare_train_step(loss_fn)`; the returned "
            "step computes gradients, accumulation, clipping and the optimizer update in one jit."
        )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Accumulation bookkeeping context (reference accumulate :1254).

        With the default ``in_step`` mode this is a no-op provided for loop
        compatibility; with ``across_steps`` it flips
        ``GradientState.sync_gradients`` exactly like the reference
        (``_do_sync`` :1228), including the end-of-dataloader forced sync.

        ``step_count`` advances exactly once per batch: when a prepared train
        step runs *inside* this context (the reference loop shape
        ``with accelerator.accumulate(): step(...)``), the context owns the
        increment and the step skips its own bookkeeping."""
        self.step_count += 1
        self.goodput.record_step()
        end = self.gradient_state.end_of_dataloader and self.gradient_state.plugin.sync_with_dataloader
        sync = (
            self.gradient_state.plugin.mode == "in_step"
            or end
            or (self.step_count % self.gradient_state.num_steps == 0)
            or self.gradient_state.plugin.sync_each_batch
        )
        self.gradient_state._set_sync_gradients(sync)
        self._in_accumulate = True
        try:
            yield
        finally:
            self._in_accumulate = False

    def no_sync(self, model=None):
        """reference no_sync (:1131): under GSPMD the compiler owns collective
        placement; provided as an inert context for API compatibility."""
        return contextlib.nullcontext()

    def clip_grad_norm_(self, grads_or_params, max_norm: float, norm_type: float = 2.0):
        """Eager global-norm clip of a gradient pytree (reference :2918).
        Inside a prepared train step pass ``max_grad_norm`` instead."""
        if norm_type != 2.0:
            raise NotImplementedError("only L2 global-norm clipping is supported")
        gnorm = global_norm(grads_or_params)
        clip = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
        return jax.tree_util.tree_map(lambda g: g * clip, grads_or_params), gnorm

    def clip_grad_value_(self, grads, clip_value: float):
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -clip_value, clip_value), grads)

    # -- collectives façade (reference :3008-3236) -------------------------

    def gather(self, tensor):
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather eval outputs, dropping the duplicate tail samples that
        ``even_batches`` padding added (reference gather_for_metrics :3040)."""
        try:
            recursively_gathered = not use_gather_object and all(
                ops.is_array_like(x) for x in jax.tree_util.tree_leaves(input_data)
            )
        except Exception:
            recursively_gathered = False
        data = ops.gather(input_data) if recursively_gathered else ops.gather_object(input_data)

        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            def _drop(t):
                return t[: self.gradient_state.remainder]

            try:
                if recursively_gathered:
                    data = ops.recursively_apply(_drop, data)
                else:
                    data = data[: self.gradient_state.remainder]
            except (TypeError, IndexError) as e:
                # un-sliceable gathered objects: return everything, loudly —
                # silently wrong eval metrics are worse than duplicates
                # (reference gather_for_metrics logs and falls through :3070)
                logger.warning(
                    "gather_for_metrics could not drop the %d duplicate tail "
                    "samples (%s); returning the full gathered data.",
                    self.gradient_state.remainder, e,
                )
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        return ops.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return ops.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """Sharded training never wraps models under GSPMD; the one wrapping
        container is the pipeline-parallel PipelinedModel (reference
        extract_model_from_parallel utils/other.py:218)."""
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(model, keep_fp32_wrapper)

    def unscale_gradients(self, optimizer=None):
        return None  # unscaling happens inside the jitted step

    # -- NaN guard (reference set_trigger/check_trigger :2824/:2850) --------

    def set_trigger(self):
        self.flag_tensor = jnp.int32(1)

    def check_trigger(self) -> bool:
        flag = self.flag_tensor if self.flag_tensor is not None else jnp.int32(0)
        total = ops.reduce(np.asarray(flag), reduction="sum")
        if int(np.asarray(total)) >= 1:
            self.flag_tensor = None
            return True
        return False

    # -- contexts ----------------------------------------------------------

    @contextlib.contextmanager
    def autocast(self, autocast_handler: Optional[AutocastKwargs] = None):
        """Eager-mode compute-dtype context: inside, ``accelerator.cast`` /
        policy helpers apply; under jit the policy is baked into the step.
        Provided for API parity (reference autocast :4143)."""
        yield

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches: Optional[bool] = None):
        """reference join_uneven_inputs (:1299).  With even_batches sharding
        the batches are equalized up front, so this is a compatibility no-op
        unless even_batches=False was configured (then it warns)."""
        if even_batches is False:
            import warnings

            warnings.warn("join_uneven_inputs cannot retrofit uneven batches under GSPMD; use even_batches=True")
        yield

    @contextlib.contextmanager
    def maybe_context_parallel(self, buffers=None, buffer_seq_dims=None,
                               no_restore_buffers=None):
        """Per-step context-parallel buffer sharding (reference
        maybe_context_parallel :4076-4140).

        The reference mutates torch tensors in place and restores them on
        exit; JAX arrays are immutable, so this manager instead **yields the
        CP-sharded buffers**: each is zigzag-reordered along its sequence dim
        (load-balanced causal ordering, parallel/context_parallel.py) and
        device_put with the sequence dim sharded over ``cp``.  Use the
        yielded list inside the step::

            shift_labels = np.roll(batch["labels"], -1, axis=1)
            shift_labels[:, -1] = -100  # next-token align BEFORE sharding
            with accelerator.maybe_context_parallel(
                buffers=[batch["input_ids"], shift_labels], buffer_seq_dims=[1, 1]
            ) as (input_ids, labels):
                state, metrics = step(state, {"input_ids": input_ids, "shift_labels": labels})

        Like the reference, this is a silent no-op (yields the buffers
        unchanged) when ``cp_size <= 1``, so the same loop runs everywhere.
        ``no_restore_buffers`` is accepted for signature parity; restoration
        is moot without mutation.

        As in the reference (context_parallelism.md:113-121), labels must be
        **pre-shifted** before sharding: after the zigzag reorder "the next
        position" is no longer the next array index, so in-model label
        shifting would be wrong.  The model loss factories accept the
        pre-shifted labels under the ``shift_labels`` batch key.
        """
        if buffers is None:
            yield []
            return
        pcfg = self.parallelism_config
        if pcfg is None or pcfg.cp_size <= 1:
            yield list(buffers)
            return
        from .parallel.context_parallel import zigzag_shard

        cp = pcfg.cp_size
        seq_dims = buffer_seq_dims or [1] * len(buffers)
        if len(seq_dims) != len(buffers):
            raise ValueError("buffer_seq_dims must match buffers in length")
        sharded = []
        for buf, dim in zip(buffers, seq_dims):
            arr = zigzag_shard(buf, cp, axis=dim)
            spec = [None] * np.asarray(buf).ndim
            spec[dim] = "cp"
            sharded.append(
                jax.device_put(arr, NamedSharding(self.mesh, PartitionSpec(*spec)))
            )
        yield sharded

    @contextlib.contextmanager
    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        """Step-scheduled profiler context (reference profile :4168; the
        ProfileKwargs schedule semantics of reference dataclasses.py:484).

        Yields a :class:`~accelerate_tpu.utils.profiler.TPUProfiler`; call
        ``profiler.step()`` once per training step and exactly the
        ``active`` steps of each wait/warmup/active cycle are traced.
        Without ``step()`` calls the whole block is one active window::

            with accelerator.profile(ProfileKwargs(wait=1, warmup=1,
                                                   active=3,
                                                   output_trace_dir=d)) as p:
                for batch in loader:
                    train_step(batch)
                    p.step()
        """
        from .utils.profiler import TPUProfiler

        handler = profile_handler or self.profile_kwargs
        profiler = TPUProfiler(handler)
        profiler._enter()
        try:
            yield profiler
        finally:
            profiler._exit()

    # -- misc lifecycle ----------------------------------------------------

    def free_memory(self, *objects):
        """Release references + compiled executables (reference free_memory
        :3867)."""
        self._dataloaders = []
        self._optimizers = []
        self._schedulers = []
        self._models = []
        self._state_sharding = None
        self.step_count = 0
        jax.clear_caches()
        import gc

        gc.collect()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    def register_for_checkpointing(self, *objects):
        """Track stateful objects (must expose state_dict/load_state_dict) for
        save_state/load_state (reference :4039)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(f"Objects {invalid} lack state_dict/load_state_dict")
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        import uuid

        key = uuid.uuid4().hex
        self._save_model_state_pre_hooks[key] = hook
        return key

    def register_load_state_pre_hook(self, hook: Callable):
        import uuid

        key = uuid.uuid4().hex
        self._load_model_state_pre_hooks[key] = hook
        return key

    def save_state(self, output_dir: Optional[str] = None, train_state=None, **save_kwargs):
        """Checkpoint everything (reference save_state :3549): train state,
        dataloader positions, RNG, custom objects; automatic naming +
        retention GC under ProjectConfiguration."""
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, train_state=train_state, **save_kwargs)

    def load_state(self, input_dir: Optional[str] = None, train_state=None, **load_kwargs):
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, train_state=train_state, **load_kwargs)

    def wait_for_checkpoint(self):
        """Block until an in-flight ``save_state(async_save=True)`` write has
        committed.  Called automatically before the next save_state (and its
        retention GC), load_state, end_training, and at interpreter exit —
        call it directly only to bound checkpoint latency explicitly."""
        from .checkpointing import wait_for_pending_checkpoint

        wait_for_pending_checkpoint(self)

    # -- preemption / auto-resume (resilience/, docs/resilience.md) --------

    def install_preemption_handler(self, signals=None):
        """Arm graceful-stop handling: the listed signals (default the
        plugin's, i.e. ``SIGTERM``) set a flag, and the prepared train step
        exits at the next step boundary through :meth:`_preemption_exit`
        (emergency checkpoint + ``SystemExit(75)``).  Idempotent."""
        if self._preemption is None:
            from .resilience.preemption import PreemptionHandler

            self._preemption = PreemptionHandler(
                signals or self.resilience_plugin.preemption_signals
            ).install()
        return self._preemption

    @property
    def preemption_requested(self) -> bool:
        return self._preemption is not None and self._preemption.requested

    def _agreed_preemption(self) -> bool:
        """Cross-process agreement on the graceful stop: True when ANY rank's
        handler saw the signal.  A cloud preemption notice lands on one host;
        the whole gang must stop at the SAME step boundary because the
        emergency checkpoint (and the next run's resume point) is a
        collective.  A tiny host-blocking all-gather, only in multi-process
        runs with the handler installed — throttled by
        ``ResiliencePlugin.preemption_check_every`` for long runs (the
        predicate must depend only on the lockstep ``step_count``, never on
        the local flag: ranks disagreeing on whether to enter the
        collective would deadlock the gang)."""
        requested = self._preemption.requested
        if self.num_processes <= 1:
            return requested
        every = max(1, int(getattr(self.resilience_plugin,
                                   "preemption_check_every", 1)))
        if self.step_count % every != 0:
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.int32(bool(requested)))
        return bool(np.asarray(flags).sum() > 0)

    def _preemption_exit(self, train_state=None):
        """The graceful-stop tail: drain the in-flight async save, write an
        emergency checkpoint of the boundary state through the verified
        atomic path, and exit with the distinct resume exit code so the
        supervisor re-queues rather than fails the job."""
        rp = self.resilience_plugin
        logger.warning(
            "preemption requested: stopping at step boundary (step_count=%d)",
            self.step_count,
        )
        # count the preemption BEFORE the emergency save so the persisted
        # goodput counters (checkpoint metadata) include the very event
        # that wrote them — the resumed incarnation restores preemptions=1
        self.goodput.record_preemption()
        try:
            self.wait_for_checkpoint()
            if rp.emergency_checkpoint and train_state is not None:
                try:
                    ckpt = self.save_state(train_state=train_state)
                    logger.warning("emergency checkpoint written to %s", ckpt)
                except ValueError as e:
                    # no project_dir/output_dir configured: nothing to save
                    # into — exit promptly inside the grace window anyway
                    logger.warning("no emergency checkpoint written: %s", e)
        except Exception as e:
            # the exit code must stay 75 even when the drain or the emergency
            # save fails (I/O budget exhausted, poisoned async write): a
            # crash code here would make the supervisor fail a job that has
            # older valid checkpoints to resume from
            logger.error(
                "emergency checkpoint failed (%s: %s); exiting with the "
                "resume code anyway — resume will fall back to the newest "
                "valid periodic checkpoint", type(e).__name__, e,
            )
        raise SystemExit(rp.resume_exit_code)

    @property
    def resume_requested(self) -> bool:
        """True when this process was launched with ``accelerate_tpu launch
        --resume`` (the elastic-resume signal, transported as
        ``ACCELERATE_AUTO_RESUME``): the training script should call
        :meth:`maybe_resume` before its first step — the newest verified
        checkpoint then restores re-sharded onto THIS launch's mesh, which
        may span a different process/chip count than the one that wrote
        it."""
        return parse_flag_from_env("ACCELERATE_AUTO_RESUME")

    def maybe_resume(self, train_state=None, **load_kwargs):
        """Auto-resume: restore the newest *valid* checkpoint under the
        project dir, or return ``None`` when none exists (fresh start).
        Restores RNG streams, dataloader positions, step counters — and the
        TrainState when a ``train_state`` template is given (returned
        restored).  Counts the restart in :attr:`goodput`."""
        from .checkpointing import list_checkpoints

        if not list_checkpoints(self.project_dir or "."):
            return None
        restored = self.load_state(None, train_state=train_state, **load_kwargs)
        self.goodput.record_restart()
        logger.warning(
            "resumed from checkpoint at step_count=%d (restart #%d)",
            self.step_count, self.goodput.restarts,
        )
        return restored

    @property
    def peer_snapshotter(self):
        """The buddy-rank host-RAM snapshotter, or ``None`` until the
        prepared step arms it (``ResiliencePlugin.peer_snapshot_every > 0``
        and at least one snapshot boundary has passed construction)."""
        return self._peer_snapshotter

    def recover(self, train_state=None, *, lost_local: bool = False,
                **load_kwargs):
        """Walk the recovery ladder after a fault (``RankLostError``, a
        restarted rank, a torn snapshot): newest consistent **peer-RAM**
        wave → newest **verified disk** checkpoint → **fresh start**.

        Collective in multi-process runs — every rank must call it together
        (the wave agreement and any buddy re-stream are collectives).
        ``lost_local=True`` marks THIS rank's own state as gone (the
        ``rank_loss`` fault): its local waves are dropped first, so recovery
        exercises the buddy's copy for real.

        Returns ``(train_state_or_None, report)`` where ``report`` carries
        ``restore_path`` (``"peer"`` / ``"disk"`` / ``"fresh"``),
        ``restored_step``, ``steps_recomputed``, ``peer_snapshot_bytes`` and
        ``restore_time_s``.  Records the measured ``recovery.restore_time_s``
        twin."""
        from .telemetry import twin_registry

        t0 = time.perf_counter()
        prev_step = self.step_count
        report = {
            "restore_path": "fresh",
            "restored_step": 0,
            "steps_recomputed": 0,
            "peer_snapshot_bytes": 0,
            "restore_time_s": 0.0,
        }
        restored = None
        snap = self._peer_snapshotter
        if snap is not None and train_state is not None:
            if lost_local:
                snap.forget_local()
            got = snap.recover(train_state)
            if got is not None:
                restored, step = got
                self.step_count = int(step)
                report["restore_path"] = "peer"
                report["restored_step"] = int(step)
                report["peer_snapshot_bytes"] = snap.schema["snapshot_bytes"]
                self.goodput.record_restart(
                    steps_recomputed=max(0, prev_step - int(step)))
        if restored is None:
            # disk rung: newest VERIFIED checkpoint (corrupt ones fall
            # through inside load_state's valid-fallback scan)
            try:
                restored = self.maybe_resume(train_state=train_state,
                                             **load_kwargs)
            except Exception as e:  # corrupted-beyond-fallback → fresh
                logger.error(
                    "disk recovery failed (%s: %s); starting fresh",
                    type(e).__name__, e,
                )
                restored = None
            if restored is not None or self.step_count != prev_step:
                report["restore_path"] = "disk"
                report["restored_step"] = int(self.step_count)
                self.goodput.steps_recomputed += max(
                    0, prev_step - self.step_count)
            else:
                self.step_count = 0
                self.goodput.record_restart(steps_recomputed=prev_step)
        report["steps_recomputed"] = max(0, prev_step - report["restored_step"])
        report["restore_time_s"] = round(time.perf_counter() - t0, 6)
        twin_registry().record_measured(
            "recovery.restore_time_s", report["restore_time_s"],
            source="Accelerator.recover",
        )
        logger.warning(
            "recovered via %s rung at step %d (replaying %d steps, %.3fs)",
            report["restore_path"], report["restored_step"],
            report["steps_recomputed"], report["restore_time_s"],
        )
        return restored, report

    def save_model(self, train_state_or_params, save_directory: str, max_shard_size: str = "10GB", safe_serialization: bool = True):
        from .checkpointing import save_model

        return save_model(self, train_state_or_params, save_directory, max_shard_size, safe_serialization)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches=num_batches)

    # -- trackers (reference :3243-3404; backends in tracking.py) ----------

    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: Optional[dict] = None):
        from . import tracking

        init_kwargs = init_kwargs or {}
        self.trackers = []
        for logger in self.log_with:
            tracker = tracking.resolve_tracker(logger, project_name, self.project_configuration.logging_dir,
                                               **init_kwargs.get(str(logger), {}))
            if tracker is not None:
                self.trackers.append(tracker)
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not initialized")

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: Optional[dict] = None):
        log_kwargs = log_kwargs or {}
        for tracker in self.trackers:
            tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def end_training(self):
        from .checkpointing import close_async_checkpointer

        try:
            close_async_checkpointer(self)
        finally:
            # a failed checkpoint flush must not also drop the trackers'
            # buffered metrics
            if self.timeline is not None and self.telemetry_plugin.export_dir \
                    and self.is_main_process:
                # end-of-run timeline export (Chrome trace-event JSON,
                # Perfetto-loadable; docs/observability.md).  Best-effort: a
                # bad export dir must not drop the trackers' flush below or
                # desynchronize the wait_for_everyone barrier
                try:
                    export_dir = Path(self.telemetry_plugin.export_dir)
                    export_dir.mkdir(parents=True, exist_ok=True)
                    self.timeline.write_chrome_trace(
                        export_dir / "train_timeline.json"
                    )
                except OSError as e:
                    logger.warning("timeline export to %s failed: %s",
                                   self.telemetry_plugin.export_dir, e)
            for tracker in self.trackers:
                tracker.finish()
        self.wait_for_everyone()

    def __repr__(self):
        return f"Accelerator(state={self.state!r})"
