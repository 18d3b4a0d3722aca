"""Expert parallelism (MoE) over the ``ep`` mesh axis.

TPU-native design for SURVEY §2.4 P10.  The reference has no first-class EP —
MoE support there is DeepSpeed ZeRO-3 leaf-module marking
(``deepspeed_plugin.set_moe_leaf_modules``, reference accelerator.py:2258-2259,
``transformer_moe_cls_names`` dataclasses.py:1199-1205) plus Megatron
``num_experts`` plumbing (reference utils/megatron_lm.py).  Capability parity
= "MoE models train under sharding without materializing all experts per
device", which on TPU is an ``ep`` mesh axis plus token dispatch.

Two complementary mechanisms, both MXU-friendly:

1. **GSPMD einsum dispatch** (GShard-style): routing produces dense
   ``dispatch``/``combine`` tensors ``[tokens, experts, capacity]``; expert
   compute is a batched einsum with the expert dim sharded over ``ep`` —
   XLA's partitioner inserts the all_to_alls.  This is the default path used
   by :class:`~accelerate_tpu.models.mixtral.MixtralForCausalLM`.
2. **Explicit shard_map dispatch** (:func:`expert_parallel_apply`): manual
   ``all_to_all`` that re-shards grouped tokens from capacity-sharded to
   expert-sharded, for expert bodies that cannot be expressed as one einsum
   (the "ragged all-to-all" capability named in SURVEY §2.4 P10).

Routing follows Switch/Mixtral: top-k softmax gating with a load-balancing
auxiliary loss and an optional router z-loss.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import partial_manual_kwargs


class RoutingResult(NamedTuple):
    """Dense dispatch/combine tensors plus router diagnostics.

    dispatch: [S, E, C] bool — token s goes to expert e at capacity slot c.
    combine:  [S, E, C] f32  — gating weight for the dispatched slot.
    aux_loss: scalar — Switch load-balancing loss (1.0 when perfectly uniform).
    z_loss:   scalar — router logit magnitude regularizer.
    """

    dispatch: jax.Array
    combine: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array


def expert_capacity(num_tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Per-expert token capacity C = ceil(S * k / E * factor), padded to a
    multiple of 8 so the [E, C, D] expert batches tile onto the MXU."""
    raw = int(np.ceil(num_tokens * top_k / num_experts * capacity_factor))
    return max(8, int(np.ceil(raw / 8)) * 8)


def top_k_routing(
    router_logits: jax.Array,
    top_k: int,
    capacity: int,
    *,
    normalize_weights: bool = True,
) -> RoutingResult:
    """Capacity-constrained top-k routing (Switch Transformer §2.2 semantics,
    Mixtral-style top-k weight normalization).

    router_logits: [S, E].  Tokens beyond an expert's capacity are dropped
    (their combine weight is zero → residual connection passes them through).
    """
    s, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [S, E]
    gate_vals, gate_idx = lax.top_k(probs, top_k)  # [S, K]
    if normalize_weights:
        gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # One-hot expert assignment per k-slot: [K, S, E].  Priority is slot-major
    # (all tokens' 1st choices before any 2nd choices — Switch behavior).
    assign = jax.nn.one_hot(gate_idx.T, e, dtype=jnp.int32)  # [K, S, E]
    flat_assign = assign.reshape(top_k * s, e)
    # Position of each (slot, token) within its expert's queue.
    position = jnp.cumsum(flat_assign, axis=0) - flat_assign  # [K*S, E]
    position = jnp.sum(position * flat_assign, axis=-1).reshape(top_k, s)  # [K, S]
    kept = position < capacity

    # dispatch[s, e, c]: OR over k-slots of (token s → expert e at slot c)
    pos_oh = jax.nn.one_hot(jnp.where(kept, position, capacity), capacity, dtype=jnp.float32)
    dispatch_k = assign.astype(jnp.float32)[..., None] * pos_oh[:, :, None, :]  # [K, S, E, C]
    dispatch = jnp.sum(dispatch_k, axis=0)  # [S, E, C]
    combine = jnp.sum(dispatch_k * gate_vals.T[:, :, None, None], axis=0)  # [S, E, C]

    # Switch load-balancing loss: E * sum_e f_e * p_e where f_e is the
    # fraction of tokens whose FIRST choice is e and p_e the mean router prob.
    first_choice = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
    f = jnp.mean(first_choice, axis=0)
    p = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(f * p)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(router_logits.astype(jnp.float32), axis=-1)))

    return RoutingResult(dispatch > 0, combine, aux_loss, z_loss)


def moe_dispatch(x: jax.Array, routing: RoutingResult) -> jax.Array:
    """Gather tokens into per-expert batches: [S, D] → [E, C, D].

    With expert-dim outputs sharded over ``ep`` this einsum IS the all_to_all
    (GSPMD inserts it)."""
    return jnp.einsum("sec,sd->ecd", routing.dispatch.astype(x.dtype), x)


def moe_combine(expert_out: jax.Array, routing: RoutingResult) -> jax.Array:
    """Weighted scatter back: [E, C, D] → [S, D]."""
    return jnp.einsum("sec,ecd->sd", routing.combine.astype(expert_out.dtype), expert_out)


# ---------------------------------------------------------------------------
# Explicit shard_map dispatch (ragged all-to-all capability)
# ---------------------------------------------------------------------------


def _ep_body(x_grouped, axis_name: str, expert_fn: Callable):
    """shard_map body.  Local block: [E, C/ep, D] (capacity-sharded).

    all_to_all #1 re-shards experts→local, capacities→global:
    [E, C/ep, D] → [E/ep, C, D]; apply the local experts; all_to_all #2
    restores the original layout.  ``expert_fn(local_idx, batch)`` computes
    one expert's forward, vmapped over the local expert dim by the caller.
    """
    local = lax.all_to_all(x_grouped, axis_name, split_axis=0, concat_axis=1, tiled=True)
    ep_rank = lax.axis_index(axis_name)
    e_local = local.shape[0]
    out = expert_fn(ep_rank * e_local + jnp.arange(e_local), local)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0, tiled=True)


def expert_parallel_apply(
    mesh: Mesh,
    expert_fn: Callable,
    x_grouped: jax.Array,
    *,
    axis_name: str = "ep",
):
    """Apply per-expert compute to grouped tokens with explicit all_to_all.

    x_grouped: GLOBAL [E, C, D], capacity dim sharded over ``axis_name``.
    expert_fn: ``(global_expert_indices [E/ep], batch [E/ep, C, D]) → [E/ep, C, D]``.
    Returns [E, C, D] with the input's sharding.

    Use when the expert body is not expressible as a single einsum over a
    sharded expert dim (e.g. per-expert quantized weights, ragged kernels).
    """
    if mesh.shape.get(axis_name, 1) == 1:
        e = x_grouped.shape[0]
        return expert_fn(jnp.arange(e), x_grouped)
    spec = P(None, axis_name, None)
    fn = shard_map(
        functools.partial(_ep_body, axis_name=axis_name, expert_fn=expert_fn),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
        **partial_manual_kwargs({axis_name}),
    )
    return fn(x_grouped)


# ---------------------------------------------------------------------------
# Sharding rules for expert parameters
# ---------------------------------------------------------------------------

# Expert weight tensors carry a leading num_experts dim → shard it over "ep";
# the contraction dims follow the usual Megatron column/row TP layout.
MOE_EP_RULES: list[tuple[str, P]] = [
    (r"experts/(gate_proj|up_proj)$", P("ep", None, "tp")),
    (r"experts/down_proj$", P("ep", "tp", None)),
    (r"router/kernel$", P()),  # router stays replicated — it is tiny
]


def get_moe_rules():
    """EP+TP rule table for MoE transformer blocks (prepend to the dense
    TRANSFORMER_TP_RULES so expert patterns win)."""
    from .sharding import TRANSFORMER_TP_RULES

    return MOE_EP_RULES + TRANSFORMER_TP_RULES


# ---------------------------------------------------------------------------
# Dropless routing: sorted dispatch + grouped matmul (no capacity, no drop)
# ---------------------------------------------------------------------------


class DroplessRouting(NamedTuple):
    """Top-k routing of ``N`` tokens over ALL ``E`` experts, laid out for a
    layer that holds ``len(experts_held)`` of them.

    order:       [N*k] int32 — (token, choice) pairs sorted by the LOCAL index
                 of their expert; pairs of experts not held come last.
    group_sizes: [E_held] int32 — rows of ``order`` that belong to each held
                 expert, in the order of ``experts_held``.
    weights:     [N, k] f32 — gate of each choice (``p_e / sum_T p`` under
                 ``normalize``), whatever chip holds the expert.
    experts:     [N, k] int32 — global expert id of each choice.
    tokens_per_expert: [E] int32 — pairs routed to each expert, held or not.
    """

    order: jax.Array
    group_sizes: jax.Array
    weights: jax.Array
    experts: jax.Array
    tokens_per_expert: jax.Array


@jax.named_scope("moe_route")
def route_dropless(router_logits, top_k: int, experts_held=None, *,
                   normalize: bool = True, token_mask=None, scoring: str = "softmax",
                   select_bias=None, gate_scale: float = 1.0) -> DroplessRouting:
    """Scores over all experts, the ``top_k`` chosen, their gates, and the
    sort that the grouped matmul needs.  ``router_logits``: [N, E] (float32).

    ``scoring``: ``"softmax"`` over the experts (the default) or an
    elementwise ``"sigmoid"``.  ``select_bias`` [E] (optional): the choice is
    the ``top_k`` of ``score + select_bias`` while the gates are the UNBIASED
    scores of the chosen (a learned per-expert correction that balances load
    without touching the output's weights).  ``normalize``: gates divided by
    their sum over the ``top_k`` chosen, whoever holds them; ``gate_scale``
    multiplies them afterwards.  The defaults are softmax -> top_k ->
    renormalise, and trace exactly the operations they always have.

    ``experts_held``: the global ids this layer holds (None = all): the
    layer routes over every expert and computes its own experts' part; what
    the others would add is another chip's (``ROADMAP.md`` B1).  Tokens that
    ``token_mask`` [N] leaves out (dead slots, padding) are routed to no
    expert: they cost no row of the grouped matmul and are not counted."""
    n, e = router_logits.shape
    held = tuple(range(e)) if experts_held is None else tuple(int(i) for i in experts_held)
    if scoring == "softmax":
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    else:
        raise ValueError(f"scoring {scoring!r} is neither 'softmax' nor 'sigmoid'")
    if select_bias is None:
        gate, experts = lax.top_k(probs, top_k)                   # [N, k]
    else:
        _, experts = lax.top_k(probs + select_bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(probs, experts, axis=-1)
    if normalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if gate_scale != 1.0:
        gate = gate * gate_scale
    local = np.full((e,), len(held), np.int32)                    # not held -> one past the last
    local[list(held)] = np.arange(len(held), dtype=np.int32)
    flat = experts.reshape(-1).astype(jnp.int32)
    local_of_pair = jnp.asarray(local)[flat]
    counted = jnp.ones((n * top_k,), jnp.int32)
    if token_mask is not None:
        live = jnp.repeat(token_mask.astype(bool), top_k)
        local_of_pair = jnp.where(live, local_of_pair, len(held))
        counted = live.astype(jnp.int32)
    order = jnp.argsort(local_of_pair, stable=True).astype(jnp.int32)
    counts = jnp.zeros((e,), jnp.int32).at[flat].add(counted)
    return DroplessRouting(order, counts[jnp.asarray(held, jnp.int32)], gate,
                           experts.astype(jnp.int32), counts)


def held_row_block(routing: DroplessRouting) -> int:
    """Rows one step of :func:`grouped_ffn` covers.  A layer that holds every
    expert the router scores takes all ``N * k`` pairs at once.  A share
    takes a quarter over what an even routing sends to its experts, in whole
    128-row tiles (so that one step is the usual case and a skewed routing
    takes more steps, never fewer rows), and never more than the pairs there
    are."""
    pairs = routing.order.shape[0]
    held, experts = routing.group_sizes.shape[0], routing.tokens_per_expert.shape[0]
    if held == experts:
        return pairs
    even = -(-pairs * held // experts)
    return int(min(-(-pairs // 8) * 8, -(-(even + even // 4) // 128) * 128))


def held_rows_fed(routing: DroplessRouting):
    """Rows :func:`grouped_ffn` feeds its matmuls (int32 scalar): the rows
    routed to the held experts, up to the last block's padding."""
    block = held_row_block(routing)
    return (jnp.sum(routing.group_sizes) + block - 1) // block * block


@jax.named_scope("moe_experts")
def grouped_ffn(x, routing: DroplessRouting, w_gate, w_up, w_down):
    """``sum_{e in T held} g_e W_down,e (silu(W_gate,e x) * W_up,e x)`` for
    every token, by one grouped matmul per projection over the rows sorted by
    expert (``jax.lax.ragged_dot``: a Mosaic grouped kernel on TPU).  Every
    routed row is computed: there is no capacity.

    x: [N, H]; w_gate/w_up: [E_held, H, F]; w_down: [E_held, F, H].  Returns
    [N, H] float32 (the down projection's accumulator, gated and summed).

    The sorted rows are walked in blocks of :func:`held_row_block`, which the
    routing itself decides: it knows how many experts the router scores
    (``tokens_per_expert``) and how many are held (``group_sizes``).  All
    held: one block of ``N * k`` rows, gathered and multiplied at once.  A
    share would feed mostly other chips' rows that way, so its blocks run
    under a ``while`` whose trip count follows ``sum(group_sizes)``: the
    gather and the three matmuls cover the held rows only (up to the last
    block's padding), whatever the routing: no capacity, no drop
    (:func:`held_rows_fed` counts them)."""
    n, k = routing.weights.shape
    sizes = routing.group_sizes
    if sizes.shape[0] < routing.tokens_per_expert.shape[0]:
        return _held_rows_ffn(x, routing, w_gate, w_up, w_down, held_row_block(routing))
    rows = x[routing.order // k]                                   # [N*k, H], sorted by expert
    gate = lax.ragged_dot(rows, w_gate, sizes)
    up = lax.ragged_dot(rows, w_up, sizes)
    out = lax.ragged_dot(jax.nn.silu(gate) * up, w_down, sizes,
                         preferred_element_type=jnp.float32)
    # rows past the last held group are another chip's: they add nothing here
    mine = jnp.arange(n * k) < jnp.sum(sizes)
    out = jnp.where(mine[:, None], out * routing.weights.reshape(-1)[routing.order][:, None], 0.0)
    pairs = jnp.zeros_like(out).at[routing.order].set(out)        # back to (token, choice) order
    return jnp.sum(pairs.reshape(n, k, -1), axis=1)


def _held_rows_ffn(x, routing: DroplessRouting, w_gate, w_up, w_down, block: int):
    """The share's path of :func:`grouped_ffn`: ``block`` sorted rows a step,
    each step's groups cut from the layer's, its outputs gated and added to
    their tokens' rows.  Returns [N, H] float32."""
    n, k = routing.weights.shape
    sizes = routing.group_sizes
    total = jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    order = jnp.pad(routing.order, (0, -(n * k) % block))          # a step's slice never clamps
    gates = routing.weights.reshape(-1)

    def step(i, acc):
        at = i * block
        pairs = lax.dynamic_slice_in_dim(order, at, block)
        groups = jnp.clip(ends - at, 0, block) - jnp.clip(starts - at, 0, block)
        tokens = pairs // k
        rows = x[tokens]
        hidden = jax.nn.silu(lax.ragged_dot(rows, w_gate, groups)) * lax.ragged_dot(rows, w_up, groups)
        out = lax.ragged_dot(hidden, w_down, groups, preferred_element_type=jnp.float32)
        mine = at + jnp.arange(block) < total                      # the last block's padding
        out = jnp.where(mine[:, None], out * gates[pairs][:, None], 0.0)
        return acc.at[jnp.where(mine, tokens, n)].add(out, mode="drop")

    return lax.fori_loop(0, (total + block - 1) // block, step,
                         jnp.zeros((n, w_down.shape[-1]), jnp.float32))
