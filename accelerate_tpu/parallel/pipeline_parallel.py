"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh axis.

Capability parity with the reference's two PP paths (SURVEY §2.4 P7):
``prepare_pippy`` (reference inference.py:126-186 — stage split + GPipe
microbatch forward via torch.distributed.pipelining) and Megatron training PP
(reference utils/megatron_lm.py ``pp_degree``).  The TPU-native design is a
single SPMD program instead of per-stage processes:

- The model's homogeneous decoder blocks are **stacked** along a leading
  layer dim and sharded over the ``pp`` mesh axis — each stage holds
  ``num_layers/pp`` consecutive blocks and runs them with ``lax.scan``.
- The GPipe schedule is a ``lax.scan`` over ``num_microbatches + pp - 1``
  clock ticks inside ``jax.shard_map`` (manual over ``pp`` only; dp/tp/sp
  axes stay under GSPMD auto sharding, so PP composes with FSDP/TP by
  construction).  Stage hand-off is a single ``lax.ppermute`` per tick —
  point-to-point neighbor traffic that can ride DCN.
- Embedding and LM head run *outside* the pipeline loop on every stage
  (they are cheap relative to the blocks and keeping them out makes the
  pipelined activation buffer shape-homogeneous).
- The whole schedule is built from ``scan``/``ppermute``/``where`` — all
  reverse-differentiable — so ``jax.grad`` through a pipelined forward yields
  the pipelined backward schedule automatically: this gives *training* PP,
  which the reference only reaches via Megatron.

Bubble fraction is the classic ``(pp-1)/(mb+pp-1)``; pick
``num_microbatches >= 4*pp`` to keep it small.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import axis_size, partial_manual_kwargs


# ---------------------------------------------------------------------------
# Stage-parameter surgery
# ---------------------------------------------------------------------------


def stack_layer_params(params: dict, num_layers: int, prefix: str = "layers_"):
    """Split a flax param dict into (stacked block params, non-block rest).

    ``params`` is the inner ``{"params": ...}`` dict of a model whose decoder
    blocks live under ``{prefix}{i}`` keys (models/llama.py:228).  The stacked
    tree has a new leading layer dim of size ``num_layers``.
    """
    layers = []
    rest = {}
    for key, sub in params.items():
        if key.startswith(prefix) and key[len(prefix):].isdigit():
            layers.append((int(key[len(prefix):]), sub))
        else:
            rest[key] = sub
    if len(layers) != num_layers:
        raise ValueError(
            f"expected {num_layers} '{prefix}*' block subtrees, found {len(layers)}"
        )
    layers = [sub for _, sub in sorted(layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return stacked, rest


def unstack_layer_params(stacked, rest: dict, prefix: str = "layers_") -> dict:
    """Inverse of :func:`stack_layer_params` (checkpoint interchange)."""
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    out = dict(rest)
    for i in range(num_layers):
        out[f"{prefix}{i}"] = jax.tree.map(lambda x, i=i: x[i], stacked)
    return out


def stage_sharding(mesh: Mesh, axis_name: str = "pp"):
    """NamedSharding pinning the leading (layer) dim to pipeline stages."""
    return lambda leaf: NamedSharding(mesh, P(axis_name, *([None] * (leaf.ndim - 1))))


# ---------------------------------------------------------------------------
# The GPipe schedule (shard_map body, manual over the pp axis only)
# ---------------------------------------------------------------------------


def _gpipe_body(
    stage_params,
    x_mbs,
    block_fn: Callable,
    axis_name: str,
    num_microbatches: int,
):
    """Per-stage program.  ``stage_params``: this stage's stacked block params
    ``[layers_per_stage, ...]``; ``x_mbs``: ALL microbatch activations
    ``[num_mb, mb, T, H]`` (replicated over pp — only stage 0 reads them).

    Clock tick ``t``: stage ``s`` works on microbatch ``t - s`` (GPipe fill/
    steady/drain); the result is ppermute'd to stage ``s+1``.  The last stage
    records finished microbatches; a masked psum replicates them to every
    stage at the end.
    """
    pp = axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    total_ticks = num_microbatches + pp - 1
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def stage_forward(x):
        def layer(h, layer_params):
            return block_fn(layer_params, h), None

        h, _ = lax.scan(layer, x, stage_params)
        return h

    def tick(carry, t):
        buf, outs = carry
        in_idx = jnp.clip(t, 0, num_microbatches - 1)
        feed = lax.dynamic_index_in_dim(x_mbs, in_idx, 0, keepdims=False)
        x = jnp.where(rank == 0, feed, buf)
        y = stage_forward(x)
        out_idx = jnp.clip(t - (pp - 1), 0, num_microbatches - 1)
        cur = lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
        write = jnp.logical_and(rank == pp - 1, t >= pp - 1)
        outs = lax.dynamic_update_index_in_dim(outs, jnp.where(write, y, cur), out_idx, 0)
        buf = lax.ppermute(y, axis_name, perm)
        return (buf, outs), None

    carry0 = (jnp.zeros_like(x_mbs[0]), jnp.zeros_like(x_mbs))
    (buf, outs), _ = lax.scan(tick, carry0, jnp.arange(total_ticks))
    # Replicate the last stage's collected outputs to every stage so the
    # (replicated) head can run everywhere — one masked all-reduce.
    outs = jnp.where(rank == pp - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis_name)


def pipeline_blocks(
    stacked_params,
    x,
    block_fn: Callable,
    mesh: Mesh,
    *,
    num_microbatches: int,
    axis_name: str = "pp",
    remat: bool = False,
):
    """Run stacked decoder blocks as a ``pp``-stage GPipe pipeline.

    ``stacked_params``: block params with leading layer dim ``[L, ...]``
    (shard over ``axis_name``); ``x``: activations ``[B, T, H]``;
    ``block_fn(layer_params, h) -> h``.  Returns ``[B, T, H]``.
    Differentiable — grad gives the pipelined backward pass.
    """
    pp = mesh.shape[axis_name]
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if num_layers % pp:
        raise ValueError(f"num_layers {num_layers} not divisible by pp {pp}")
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(f"batch {batch} not divisible by num_microbatches {num_microbatches}")
    if remat:
        block_fn = jax.checkpoint(block_fn)

    # XLA CPU-backend workaround: bf16 schedule buffers crossing the
    # partial-manual shard_map boundary (select/ppermute/psum) hit an XLA
    # check failure ("Invalid binary instruction opcode copy") on multi-axis
    # meshes.  Keep the *schedule* buffers fp32 on CPU — the block still
    # computes in its own dtype, so the unit-test numerics match TPU.
    orig_dtype = x.dtype
    cpu_bf16 = jax.default_backend() == "cpu" and orig_dtype == jnp.bfloat16
    if cpu_bf16:
        inner_fn = block_fn
        block_fn = lambda p, h: inner_fn(p, h.astype(orig_dtype)).astype(jnp.float32)  # noqa: E731
        x = x.astype(jnp.float32)

    # [pp, layers_per_stage, ...] so the pp axis is the leading dim shard.
    staged = jax.tree.map(
        lambda p: p.reshape((pp, num_layers // pp) + p.shape[1:]), stacked_params
    )
    x_mbs = x.reshape((num_microbatches, batch // num_microbatches) + x.shape[1:])

    body = functools.partial(
        _gpipe_body, block_fn=block_fn, axis_name=axis_name,
        num_microbatches=num_microbatches,
    )
    param_specs = jax.tree.map(lambda p: P(axis_name, *([None] * (p.ndim - 1))), staged)
    out = shard_map(
        lambda sp, xs: body(jax.tree.map(lambda a: a[0], sp), xs),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        **partial_manual_kwargs({axis_name}),
    )(staged, x_mbs)
    if cpu_bf16:
        out = out.astype(orig_dtype)
    return out.reshape((batch,) + x.shape[1:])


# ---------------------------------------------------------------------------
# prepare_pipeline — the user-facing one-call API (reference prepare_pippy,
# inference.py:126)
# ---------------------------------------------------------------------------


class PipelinedModel:
    """A causal-LM wrapped for pipeline-parallel execution.

    Mirrors the contract of reference ``prepare_pippy`` (inference.py:126):
    hand in a model + params, get back a callable that runs a microbatched
    pipelined forward.  Works for any model following the
    ``LlamaForCausalLM`` skeleton (embed → homogeneous ``layers_i`` blocks →
    final norm → lm_head; models/llama.py:205).
    """

    def __init__(
        self,
        model,
        params,
        mesh: Mesh,
        *,
        num_microbatches: int = 8,
        axis_name: str = "pp",
        remat: Optional[bool] = None,
    ):
        cfg = model.config
        self.model = model
        self.config = cfg
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_microbatches = num_microbatches
        # Honor the model config's activation-checkpointing flag unless
        # explicitly overridden — a config that fit in HBM un-pipelined must
        # not silently lose remat when switched to PP.
        self.remat = getattr(cfg, "remat", False) if remat is None else remat

        inner = params["params"] if "params" in params else params
        stacked, rest = stack_layer_params(dict(inner), cfg.num_hidden_layers)
        # Pin stage params to their pipeline ranks; everything else stays
        # under whatever sharding it already has (GSPMD auto axes).
        pin = stage_sharding(mesh, axis_name)
        self.stacked = jax.tree.map(lambda p: jax.device_put(p, pin(p)), stacked)
        self.rest = rest
        self._block = type(model).block_cls(cfg)
        self._fwd = jax.jit(self._forward)

    # -- pieces ------------------------------------------------------------

    def _block_fn(self, positions):
        block = self._block

        def fn(layer_params, h):
            return block.apply({"params": layer_params}, h, positions)

        return fn

    def _forward(self, stacked, rest, input_ids):
        cfg = self.config
        b, t = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b // self.num_microbatches, t))
        emb = rest["embed_tokens"]["embedding"]
        x = emb[input_ids].astype(cfg.dtype)
        x = pipeline_blocks(
            stacked, x, self._block_fn(positions), self.mesh,
            num_microbatches=self.num_microbatches, axis_name=self.axis_name,
            remat=self.remat,
        )
        from ..models.llama import RMSNorm

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype).apply({"params": rest["norm"]}, x)
        if cfg.tie_word_embeddings:
            return x @ emb.astype(jnp.float32).T
        return x.astype(jnp.float32) @ rest["lm_head"]["kernel"].astype(jnp.float32)

    def __call__(self, input_ids):
        return self._fwd(self.stacked, self.rest, input_ids)

    # -- interchange -------------------------------------------------------

    def merged_params(self) -> dict:
        """Reassemble the original (non-stacked) param dict."""
        return {"params": unstack_layer_params(jax.device_get(self.stacked), self.rest)}


def prepare_pipeline(
    model,
    params,
    mesh: Optional[Mesh] = None,
    *,
    num_microbatches: int = 8,
    axis_name: str = "pp",
    remat: Optional[bool] = None,
) -> PipelinedModel:
    """One-call pipeline-parallel wrap (reference prepare_pippy inference.py:126).

    ``mesh`` defaults to the ambient :class:`AcceleratorState` mesh.
    """
    if mesh is None:
        from ..state import AcceleratorState

        mesh = AcceleratorState().mesh
    return PipelinedModel(
        model, params, mesh,
        num_microbatches=num_microbatches, axis_name=axis_name, remat=remat,
    )
