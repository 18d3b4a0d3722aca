"""Parameter-sharding planner: FSDP/ZeRO/TP/HSDP as PartitionSpec assignment.

This is the heart of the strategy layer (SURVEY §2.4): where the reference
wraps models in engines (torch FSDP accelerator.py:1885, DTensor
``fully_shard`` fsdp_utils.py:621, DeepSpeed zero-stage engines), the
TPU-native design assigns a :class:`NamedSharding` to every parameter — XLA's
GSPMD partitioner then *is* the runtime.  FSDP ≅ shard params/grads/optimizer
state over ``dp_shard`` (+``cp`` under the flattened ``dp_shard_cp`` joint dim,
reference parallelism_config.py:157-164); TP = rule-matched specs on attention
/MLP matrices; HSDP = replicate over ``dp_replicate`` (DCN) while sharding
over ``dp_shard`` (ICI).

The "auto wrap policy" analog (reference fsdp auto_wrap_policy
accelerator.py:1909-1937) is ``min_weight_size``: parameters smaller than it
stay replicated — sharding tiny tensors costs more in collective latency than
it saves in HBM.
"""

from __future__ import annotations

import logging
import re
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallelism_config import BATCH_AXES, SEQ_AXES, ParallelismConfig
from ..utils.dataclasses import FullyShardedDataParallelPlugin, ShardingStrategy

logger = logging.getLogger(__name__)


def path_str(path) -> str:
    """Render a jax key path as 'a/b/0/c' for regex rule matching."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _spec_for_leaf(
    path: str,
    shape: tuple[int, ...],
    mesh: Mesh,
    fsdp_axes: tuple[str, ...],
    min_weight_size: int,
    tp_rules: Sequence[tuple[str, PartitionSpec]],
) -> PartitionSpec:
    ndim = len(shape)
    spec: list = [None] * ndim

    # Scanned layer stacks (models' scan_layers=True) carry a leading
    # num_layers dim under the "layers_scan" module — TP rules written for
    # the per-layer shapes shift right by one
    offset = 1 if "layers_scan" in path else 0

    # 1. TP rules first (they own specific dims)
    for pattern, rule_spec in tp_rules:
        if re.search(pattern, path):
            for d, entry in enumerate(rule_spec):
                d += offset
                if d >= ndim or entry is None:
                    continue
                size = _axis_size(mesh, entry)
                if size > 1 and shape[d] % size == 0:
                    spec[d] = entry
                elif size > 1:
                    logger.warning(
                        "TP rule %r wants to shard dim %d of %s %s but %d %% %d != 0; replicating",
                        pattern, d, path, shape, shape[d], size,
                    )
            break

    # 2. FSDP: shard the largest still-free, divisible dim — but never below
    # the TPU tile (8 sublanes x 128 lanes): a shard extent smaller than the
    # tile forces the partitioner into replicate-then-reshard churn
    # ("involuntary full rematerialization") every time the param crosses a
    # differently-sharded region (e.g. the cp ring shard_map), costing ICI
    # traffic each step.  Small params replicate instead — the same trade
    # min_weight_size makes, applied per-dim.
    fsdp_size = _axis_size(mesh, fsdp_axes)
    if fsdp_size > 1 and int(np.prod(shape)) >= min_weight_size:
        def _tile_ok(d: int) -> bool:
            extent = shape[d] // fsdp_size
            return extent >= (128 if d == ndim - 1 else 8)

        candidates = sorted(
            (
                d for d in range(ndim)
                if spec[d] is None and shape[d] % fsdp_size == 0 and _tile_ok(d)
            ),
            key=lambda d: shape[d],
            reverse=True,
        )
        if candidates:
            spec[candidates[0]] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]

    return PartitionSpec(*spec)


def resolve_sharding_strategy(
    fsdp_plugin: Optional[FullyShardedDataParallelPlugin],
    parallelism_config: Optional[ParallelismConfig],
) -> ShardingStrategy:
    """The effective strategy a config resolves to: an explicit plugin wins;
    otherwise a non-trivial ``dp_shard`` axis implies FULL_SHARD (ZeRO-3 is
    the point of asking for that axis) and anything else is NO_SHARD."""
    if fsdp_plugin is not None:
        return fsdp_plugin.sharding_strategy
    cfg = parallelism_config or ParallelismConfig()
    return ShardingStrategy.FULL_SHARD if cfg.dp_shard_size > 1 else ShardingStrategy.NO_SHARD


def param_fsdp_axes(mesh: Mesh, cfg: ParallelismConfig, strategy: ShardingStrategy) -> tuple:
    """Mesh axes *parameters* actually shard over under ``strategy``.

    Empty means replicated params.  Under FULL_SHARD/HYBRID the axes come
    from ``fsdp_dim_names`` (default ``dp_shard`` when non-trivial), minus
    ``cp``: params consumed inside the cp ring shard_map (a *manual* region
    over cp) must be cp-replicated there; sharding them over the joint
    (dp_shard, cp) axes makes the partitioner replicate-then-reshard every
    layer every step ("involuntary full rematerialization" — wasted ICI).
    The optimizer state keeps the full joint ZeRO sharding (it never crosses
    the shard_map) — see make_opt_state_sharding_plan.  NO_SHARD /
    SHARD_GRAD_OP replicate parameters across dp (grad/optimizer sharding
    for SHARD_GRAD_OP is applied to opt_state only)."""
    if strategy not in (ShardingStrategy.FULL_SHARD, ShardingStrategy.HYBRID_SHARD):
        return ()
    fsdp_axes = cfg.fsdp_dim_names or (("dp_shard",) if mesh.shape.get("dp_shard", 1) > 1 else ())
    return tuple(a for a in fsdp_axes if a != "cp" and mesh.shape.get(a, 1) > 1)


def make_sharding_plan(
    params,
    mesh: Mesh,
    parallelism_config: Optional[ParallelismConfig] = None,
    fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
    tp_rules: Optional[Sequence[tuple[str, PartitionSpec]]] = None,
):
    """Assign a NamedSharding to every parameter leaf.

    ``params`` may be a real pytree or a tree of ``jax.ShapeDtypeStruct``
    (abstract planning — the big-model path, no materialization needed).
    Returns a pytree of :class:`NamedSharding` with the same structure.
    """
    cfg = parallelism_config or ParallelismConfig()
    tp_rules = list(tp_rules or [])

    strategy = resolve_sharding_strategy(fsdp_plugin, cfg)
    min_size = fsdp_plugin.min_weight_size if fsdp_plugin is not None else 2**12
    fsdp_axes = param_fsdp_axes(mesh, cfg, strategy)

    def _leaf(path, leaf):
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        if not shape:
            return NamedSharding(mesh, PartitionSpec())
        return NamedSharding(
            mesh, _spec_for_leaf(path_str(path), shape, mesh, tuple(fsdp_axes), min_size, tp_rules)
        )

    return jax.tree_util.tree_map_with_path(_leaf, params)


def make_opt_state_sharding_plan(
    opt_state_shapes,
    params_plan,
    mesh: Mesh,
    parallelism_config: Optional[ParallelismConfig] = None,
    fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
):
    """Sharding plan for optimizer state (the ZeRO-1/2 axis of the design).

    Moment tensors that mirror a parameter inherit that parameter's sharding;
    under SHARD_GRAD_OP (ZeRO-2 analog) mirrors are *additionally* sharded
    even though params are replicated.  Scalar counts replicate.
    """
    cfg = parallelism_config or ParallelismConfig()
    plugin = fsdp_plugin
    shard_opt = plugin is None or plugin.sharding_strategy != ShardingStrategy.NO_SHARD

    # index param shardings by path for mirror matching (optax moment trees
    # embed the param tree, so param paths appear as suffixes)
    flat_plan = {path_str(p): s for p, s in jax.tree_util.tree_flatten_with_path(
        params_plan, is_leaf=lambda x: isinstance(x, NamedSharding))[0]}

    min_size = plugin.min_weight_size if plugin is not None else 2**12
    if shard_opt:
        fsdp_axes = cfg.fsdp_dim_names or (("dp_shard",) if mesh.shape.get("dp_shard", 1) > 1 else ())
    else:
        fsdp_axes = ()
    # the entry shape the *params* plan uses for its (cp-excluded) fsdp axes,
    # so mirrors can be recognized and upgraded to the joint ZeRO sharding
    param_axes = tuple(a for a in fsdp_axes if a != "cp")
    param_entry = (param_axes if len(param_axes) > 1 else param_axes[0]) if param_axes else None
    joint_entry = (tuple(fsdp_axes) if len(fsdp_axes) > 1 else fsdp_axes[0]) if fsdp_axes else None
    joint_size = _axis_size(mesh, fsdp_axes)

    def _leaf(path, leaf):
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        if not shape:
            return NamedSharding(mesh, PartitionSpec())
        p = path_str(path)
        # moment tensors under optax appear with the param path as suffix
        for param_path, sharding in flat_plan.items():
            if p.endswith(param_path) and len(sharding.spec) <= len(shape):
                if sharding.spec and any(s is not None for s in sharding.spec):
                    spec = list(sharding.spec)
                    if joint_entry is not None and joint_entry != param_entry:
                        # moments never enter the cp shard_map: upgrade the
                        # param's fsdp entry to the joint (dp_shard, cp)
                        # sharding for the full ZeRO memory saving
                        for d, entry in enumerate(spec):
                            if entry == param_entry and shape[d] % joint_size == 0:
                                spec[d] = joint_entry
                    return NamedSharding(mesh, PartitionSpec(*spec))
                break
        return NamedSharding(mesh, _spec_for_leaf(p, shape, mesh, tuple(fsdp_axes), min_size, []))

    return jax.tree_util.tree_map_with_path(_leaf, opt_state_shapes)


# ---------------------------------------------------------------------------
# Built-in TP rule tables (the transformers tp_plan="auto" analog,
# reference accelerator.py:1870-1879)
# ---------------------------------------------------------------------------

# Megatron-style column/row parallel layout for transformer blocks:
# qkv/up projections column-parallel (shard output dim), out/down projections
# row-parallel (shard input dim), embeddings shard vocab, norms replicate.
TRANSFORMER_TP_RULES: list[tuple[str, PartitionSpec]] = [
    (r"(embed_tokens|embedding|wte|word_embeddings)/embedding$", PartitionSpec("tp", None)),
    (r"(q_proj|k_proj|v_proj|query|key|value|wq|wk|wv|in_proj|qkv)/kernel$", PartitionSpec(None, "tp")),
    (r"(o_proj|out_proj|wo|dense(?!_4h)|attn_out)/kernel$", PartitionSpec("tp", None)),
    (r"(gate_proj|up_proj|wi|wi_gate|wi_up|w1|w3|fc1|dense_h_to_4h|c_fc)/kernel$", PartitionSpec(None, "tp")),
    (r"(down_proj|wo_mlp|w2|fc2|dense_4h_to_h|c_proj)/kernel$", PartitionSpec("tp", None)),
    (r"(lm_head|output|score)/kernel$", PartitionSpec(None, "tp")),
]


def get_tp_rules(plan: str = "auto"):
    """Rule table lookup (models may register their own)."""
    if plan in ("auto", "transformer"):
        return TRANSFORMER_TP_RULES
    if plan in ("moe", "mixtral"):
        from .expert_parallel import get_moe_rules

        return get_moe_rules()
    if plan in ("none", None):
        return []
    raise ValueError(f"unknown tp plan {plan!r}")


def constrain_activation(x, tp_dim: Optional[int] = None):
    """Pin a training activation ``[B, T, ...]`` to its rows: the batch dim
    over the batch axes, the sequence dim over ``cp``/``sp``, dim ``tp_dim``
    (an MLP's width, the heads) over ``tp`` where ``tp`` divides it, every
    other dim whole.

    The parameter plan shards every matrix over ``dp_shard`` AND ``tp``.  With
    no layout stated for the activations GSPMD may read the FSDP axis as a
    second tensor-parallel axis: gather the batch, multiply against the weight
    shard it holds and all-reduce the partial products over ``dp_shard`` —
    activation-sized traffic on the critical path of every matmul.  FSDP means
    the weights move and the activations stay: with the rows pinned the
    partitioner gathers the weight shards (and reduces their gradients over
    the same axis), which depends on no activation and prefetches under the
    matmuls.

    The axes are those the mesh still leaves to GSPMD and that are wider than
    one device (``state.free_mesh_axes``: inside a GPipe stage ``pp`` is not
    among them, inside a fully manual region none is), read from the lists the
    batch spec is built from, so the batch as it arrives and the activations
    agree.  No mesh, no free batch axis (one device, ``tp`` alone), or rows or
    a sequence the axes do not divide: ``x`` itself.
    """
    from ..state import free_mesh_axes

    mesh, _, free = free_mesh_axes()
    rows = tuple(a for a in BATCH_AXES if a in free)
    seq = tuple(a for a in SEQ_AXES if a in free)
    if not rows or x.shape[0] % _axis_size(mesh, rows) or x.shape[1] % _axis_size(mesh, seq):
        return x
    spec = [rows, seq or None] + [None] * (x.ndim - 2)
    if tp_dim is not None and "tp" in free and x.shape[tp_dim] % free["tp"] == 0:
        spec[tp_dim] = "tp"
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*spec)))


def shard_params(params, plan):
    """device_put a real param pytree onto its plan (initial placement)."""
    return jax.tree_util.tree_map(lambda p, s: jax.device_put(p, s), params, plan)


def replicated_plan(params, mesh: Mesh):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, PartitionSpec()), params)


# ---------------------------------------------------------------------------
# Host (CPU-memory) offload of training state — the ZeRO-offload analog
# (reference DeepSpeedPlugin offload_optimizer_device/offload_param_device,
# dataclasses.py:1172-1187; FSDP CPUOffload).  On TPU, "offload" means the
# pytree lives in ``pinned_host`` memory and the optimizer update runs as XLA
# host compute — grads stream D2H, the update executes on the host CPU, and
# only the refreshed params return over PCIe.
# ---------------------------------------------------------------------------


def plan_bytes_per_device(abstract_tree, plan) -> int:
    """Per-device bytes of a pytree under a sharding plan (abstract: pure
    arithmetic over specs — works with :class:`jax.sharding.AbstractMesh`,
    no real devices needed).  Used by the dryrun's plan leg
    (``__graft_entry__.py``) to report multi-chip footprints from one host."""
    total = 0
    leaves = jax.tree_util.tree_leaves(
        abstract_tree, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
    )
    plans = jax.tree_util.tree_leaves(plan, is_leaf=lambda x: isinstance(x, NamedSharding))
    for leaf, sh in zip(leaves, plans):
        n = int(np.prod(leaf.shape)) * jax.dtypes.canonicalize_dtype(leaf.dtype).itemsize
        div = 1
        if isinstance(sh, NamedSharding):
            for entry in sh.spec:
                if entry is not None:
                    div *= _axis_size(sh.mesh, entry)
        total += -(-n // div)
    return total


def host_offload_supported() -> bool:
    """Whether in-``jit`` memory-kind placement works on this backend.

    The TPU runtime implements ``annotate_device_placement`` for
    ``pinned_host`` buffers; XLA:CPU rejects it (side-effecting custom call
    cannot be sharded), so on the CPU test mesh offload degrades to regular
    device placement while the host-compute update path is still exercised.
    """
    return jax.default_backend() == "tpu"


def with_memory_kind(sharding: NamedSharding, kind: str) -> NamedSharding:
    return NamedSharding(sharding.mesh, sharding.spec, memory_kind=kind)


def single_device_sharding(memory_kind: str = "device") -> NamedSharding:
    """Replicated sharding over the first local device, in the given memory
    kind — the placement handle for single-chip host-offload tiers."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))
    return NamedSharding(mesh, PartitionSpec(), memory_kind=memory_kind)


def host_plan(plan):
    """Map a sharding plan into ``pinned_host`` memory (same mesh/specs)."""
    return jax.tree_util.tree_map(
        lambda s: with_memory_kind(s, "pinned_host") if isinstance(s, NamedSharding) else s,
        plan,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )


def device_plan(plan):
    """Strip memory kinds from a plan (back to default device/HBM)."""
    return jax.tree_util.tree_map(
        lambda s: with_memory_kind(s, "device") if isinstance(s, NamedSharding) else s,
        plan,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )
