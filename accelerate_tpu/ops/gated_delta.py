"""The gated delta rule (Gated DeltaNet's linear attention): what a layer keeps
of a sequence is ONE matrix a value head, ``S`` ``[Dk, Dv]``, whatever the
context's length.  With ``q_t``, ``k_t`` ``[Dk]`` (L2-normed by the caller,
``q`` scaled), ``v_t`` ``[Dv]``, a decay ``g_t <= 0`` and a write strength
``beta_t`` in (0, 1)::

    S <- exp(g_t) S;    S <- S + k_t (beta_t (v_t - S^T k_t))^T;    o_t = S^T q_t

The state is a SUM over the whole past: a stale one cannot be masked out the
way a window's ring is.  So the discipline is the caller's and is spelled out
in every function here: a call says from which state it starts (zero, or the
stored one), and a position that is not live leaves the state as it was
(``beta = 0`` and ``g = 0`` make the recurrence the identity).

Two forms of the one recurrence:

- :func:`gated_delta_step`, a decode tick's one token a slot: the Pallas
  kernel ``gated_delta_step``.  The grid walks the call's lanes; a lane's
  state block ``[Hv, Dk, Dv]`` is addressed by its SLOT id (scalar prefetch),
  read once, decayed, corrected and written back to the same block of the same
  buffer (``input_output_aliases``: under a donating ``jit`` nothing is
  copied).  ``k`` and ``q`` arrive as columns ``[Dk, Hv]`` so that the outer
  product and both contractions are broadcasts and sublane sums: float32 on
  the vector unit, no transpose, no loop in the program around the kernel.
- :func:`gated_delta_chunk`, a prefill chunk of one sequence: the chunked
  form (the published ``torch_chunk_gated_delta_rule``), blocks of ``C = 64``
  tokens.  With ``gamma`` the running sum of ``g`` inside a block and ``D_ij =
  exp(gamma_i - gamma_j)`` for ``j <= i``::

      A = -(diag(beta) K K^T * D), strictly lower;   T = (I - A)^-1
      U = T diag(beta) V;   W = T (diag(beta) K * exp(gamma))
      V' = U - W S;   O = (Q * exp(gamma)) S + (Q K^T * D, lower with diagonal) V'
      S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

  ``T`` is built by halves (the inverse of a block-triangular matrix from its
  two diagonal blocks' inverses): twelve small matmuls for every block at once
  in place of 63 dependent rows of a forward substitution, and as stable as
  one.  Everything a block needs but ``S`` is computed for all blocks at once;
  the state is then carried block to block by one ``scan`` (and handed to the
  next chunk by the caller).  Plain XLA, float32 at the highest matmul
  precision: these products are ~4% of a chunk's operations and their sum
  runs over thousands of tokens.  That is what an UNDIFFERENTIATED call
  traces, all of it (a serving engine's prefill: the scan is not where a
  chunk's time goes, and a start pays no Mosaic lowering for it).

  Differentiable (the training path, ``models/olmo_hybrid.py``), and what
  ``jax.grad`` traces is another path, reachable only through ``_blocks``'
  ``defvjp``: the forward rule makes the same parts heads first and walks the
  blocks inside ONE Pallas kernel a segment (``linear_chunk_fwd``: grid (head
  group, block), the state ``[Dk, Dv]`` in VMEM across a head group's blocks,
  a block's parts streamed through, ``O`` and the state each block STARTED
  from written out); the backward rule walks them last to first in one kernel
  (``linear_chunk_bwd``: the state's cotangent in VMEM, ``V'`` made again
  from the stored start) and takes the parts' gradient by hand - through ``T``
  as ``dA = T^T dT T^T = (T^T dU) U^T + (T^T dW) W^T`` in place of autodiff
  through the inverse's six levels.  It keeps the inputs, one state a block
  and ``T`` and ``A`` (the inverse is not made twice; ``U``, ``W`` and the
  rest of what a walk streams are, from ``T``: kept as well they cost the
  train cell 2.3 GB of its step).  ``T`` and ``A`` carry a name
  (``KEPT_ACROSS_REMAT``), so a caller that recomputes its blocks may save
  the two across the recompute too (``save_only_these_names``:
  ``models/olmo_hybrid.py`` does, and ``remat``'s forward makes no inverse).
  The same products at the same precision as the primal, kernels included.

The short causal convolution in front of the rule (depthwise, ``K`` taps, the
last ``K - 1`` input rows kept per sequence) is here too:
:func:`causal_conv_step` and :func:`causal_conv_chunk`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallelism_config import BATCH_AXES
from .flash_attention import _on_tpu, per_shard

BLOCK = 64               # tokens of one block of the chunked form
SEGMENT = 32             # blocks whose parts are made at once: a longer sequence goes a segment at a time


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# the short convolution
# ---------------------------------------------------------------------------


def causal_conv_step(x, window, weight):
    """One token a lane.  ``x`` [B, C]; ``window`` [B, K - 1, C], the lane's
    last ``K - 1`` input rows (oldest first); ``weight`` [K, C] (tap ``K - 1``
    meets the current row).  Returns ``(y [B, C] float32, the new window)``."""
    rows = jnp.concatenate([window.astype(jnp.float32), x.astype(jnp.float32)[:, None]], axis=1)
    y = jnp.sum(rows * weight.astype(jnp.float32)[None], axis=1)
    return y, rows[:, 1:].astype(window.dtype)


def causal_conv_chunk(x, window, weight, length):
    """One chunk of one sequence.  ``x`` [T, C], of which the first ``length``
    rows are live; ``window`` [K - 1, C], the rows before the chunk (zeros at
    a sequence's start); ``weight`` [K, C].  Returns ``(y [T, C] float32, the
    window behind the chunk's last LIVE row)``."""
    taps = weight.shape[0]
    rows = jnp.concatenate([window.astype(jnp.float32), x.astype(jnp.float32)])
    t = x.shape[0]
    y = sum(rows[j:j + t] * weight[j].astype(jnp.float32) for j in range(taps))
    return y, lax.dynamic_slice_in_dim(rows, length, taps - 1, axis=0).astype(window.dtype)


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------


def _inverse_of_one_minus(a):
    """``(I - a)^-1`` of strictly lower-triangular ``a`` [..., C, C], ``C`` a
    power of two, by halves: the inverse of ``[[P, 0], [-R, Q]]`` is ``[[P^-1,
    0], [Q^-1 R P^-1, Q^-1]]``, from diagonal blocks of one element up.  With
    ``inv`` the block-diagonal matrix of the blocks' inverses and ``r`` the
    lower-left quarters of ``a`` between the blocks of a pair, one level is
    ``inv + inv r inv`` on whole ``[C, C]`` matrices (the zeros cost less than
    matmuls of 2 x 2).  Every intermediate is a diagonal block of the result
    itself, so nothing larger than the result is ever summed (the product
    ``(I + a)(I + a^2)(I + a^4)..`` is the same matrix, but its terms reach
    ``|a|^k C(C, k)`` and cancel: with keys a quarter aligned and ``beta``
    near 2 it lost every digit)."""
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    at = np.arange(c)
    inv, m = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape), 1
    while m < c:
        same_pair = at[:, None] // (2 * m) == at[None, :] // (2 * m)
        lower_left = same_pair & (at[:, None] % (2 * m) >= m) & (at[None, :] % (2 * m) < m)
        inv = inv + mm(mm(inv, jnp.where(lower_left, a, 0.0)), inv)
        m *= 2
    return inv


def _block_parts(q, k, v, g, beta):
    """What a block needs but ``S``, for every block at once.  ``q``, ``k``
    [Hv, n, C, Dk], ``v`` [Hv, n, C, Dv], ``g``, ``beta`` [Hv, n, C] ->
    ``(U, W, Q K^T * D, Q * exp(gamma), K * exp(gamma_C - gamma),
    exp(gamma_C))``, blocks first ([n, Hv, ...]: what the scan walks)."""
    block = q.shape[2]
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    gamma = jnp.cumsum(g, axis=-1)                                  # [Hv, n, C]
    at = np.arange(block)
    lower = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    a = -jnp.where(at[:, None] > at[None, :], mm("hnid,hnjd->hnij", k_beta, k) * decay, 0.0)
    inv = _inverse_of_one_minus(a)
    u = mm("hnij,hnjd->hnid", inv, v * beta[..., None])
    w = mm("hnij,hnjd->hnid", inv, k_beta * jnp.exp(gamma)[..., None])
    within = mm("hnid,hnjd->hnij", q, k) * decay                    # lower, with the diagonal
    q_in = q * jnp.exp(gamma)[..., None]
    k_out = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    last = jnp.exp(gamma[..., -1])                                  # [Hv, n]
    return tuple(jnp.moveaxis(x, 1, 0) for x in (u, w, within, q_in, k_out, last))


def _one_block(s, xs):
    """The state ``s`` [Hv, Dk, Dv] through one block: ``(the state behind it, O [Hv, C, Dv])``."""
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    u_i, w_i, within_i, q_i, k_i, last_i = xs
    v_new = u_i - mm("hid,hde->hie", w_i, s)
    o = mm("hid,hde->hie", q_i, s) + mm("hij,hje->hie", within_i, v_new)
    return s * last_i[:, None, None] + mm("hid,hie->hde", k_i, v_new), o


@jax.custom_vjp
def _blocks(q, k, v, g, beta, state):
    """The blocked rule, ``_block_parts``' shapes: ``(the last state, O [n, Hv, C, Dv])``."""
    return lax.scan(_one_block, state, _block_parts(q, k, v, g, beta))


# -- the differentiated path: an undifferentiated call traces nothing from here to ``defvjp`` --

KEPT_ACROSS_REMAT = "linear_chunk_inverse"    # the name ``T`` and ``A`` carry: a caller's ``remat`` may save them by it
# heads whose blocks one grid step of a walk holds (the largest divisor of Hv up to this): independent chains for the
# scheduler - with one, the train cell read 0.6% fewer tokens/s; the micro-benchmark flattens past three
HEADS_A_STEP = 3


def _dot(a, b, contract):
    """``a . b`` over one axis of each, float32 at the highest precision (inside a kernel)."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _walk_fwd_kernel(wq_ref, u_ref, within_ref, k_out_ref, last_ref, state_ref,
                     starts_ref, o_ref, s_ref, *, heads: int):
    """One block of ``heads`` heads: ``_one_block`` with the state in VMEM
    (``s_ref``, the last state's own output block, which stays while the grid
    walks a head group's blocks).  ``wq``: ``W`` over ``Q e^gamma``, [2 C, Dk]."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = state_ref[...]

    block = u_ref.shape[1]
    for h in range(heads):
        s = s_ref[h]
        starts_ref[h] = s
        ws_qs = _dot(wq_ref[h], s, (1, 0))
        v_new = u_ref[h] - ws_qs[:block]
        o_ref[h] = ws_qs[block:] + _dot(within_ref[h], v_new, (1, 0))
        s_ref[h] = s * last_ref[h] + _dot(k_out_ref[h], v_new, (0, 0))


def _walk_bwd_kernel(wq_ref, u_ref, within_ref, k_out_ref, last_ref, starts_ref, d_o_ref, d_state_ref,
                     d_wq_ref, d_u_ref, d_within_ref, d_k_out_ref, d_last_ref, d_s_ref, *, heads: int):
    """One block of ``heads`` heads, the last block first: ``_one_block``'s
    transpose with the state's cotangent in VMEM (``d_s_ref``, the incoming
    state's own cotangent block).  ``V'`` is made again from the start the
    forward walk stored; ``d(last)`` leaves as its sums over ``Dk``."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_s_ref[...] = d_state_ref[...]

    block = u_ref.shape[1]
    for h in range(heads):
        s, d_s, wq, d_o = starts_ref[h], d_s_ref[h], wq_ref[h], d_o_ref[h]
        v_new = u_ref[h] - _dot(wq[:block], s, (1, 0))
        d_v = _dot(within_ref[h], d_o, (0, 0)) + _dot(k_out_ref[h], d_s, (1, 0))
        d_u_ref[h] = d_v
        d_within_ref[h] = _dot(d_o, v_new, (1, 1))
        d_k_out_ref[h] = _dot(v_new, d_s, (1, 1))
        both = jnp.concatenate([-d_v, d_o])                         # against [W; Q e^gamma]
        d_wq_ref[h] = _dot(both, s, (1, 1))
        d_last_ref[h] = jnp.sum(s * d_s, axis=0, keepdims=True)
        d_s_ref[h] = d_s * last_ref[h] + _dot(wq, both, (0, 0))


def _walk(kernel, name, heads_first, blocks_first, carried, heads_first_out, blocks_first_out, *, backwards):
    """``kernel`` over the grid (head group, block), one launch a segment.  A
    step reads one block of each array of ``heads_first`` ([Hv, n, rows,
    cols]) and ``blocks_first`` ([n, Hv, ..], ``O``'s order) and writes one
    block ``(rows, cols)`` for each entry of ``heads_first_out`` and
    ``blocks_first_out``; ``carried`` [Hv, Dk, Dv] is read at a head group's
    first step, and the last output is one more of its shape whose block
    stays in VMEM across the group's blocks.  Heads know nothing of each
    other: under a mesh each device walks its own (``per_shard``; a batch's
    rows were folded into the heads rows first, so the batch axes split
    them, along a row's heads where the rows alone do not divide; where the
    folded axis does not divide either, every device walks all of it).  Only
    the batch axes are named: across ``tp`` the heads are seen whole (gathered
    at the boundary where the projections' ``tp`` rule had split them) and
    every device of a ``tp`` group walks the same ones - right, at ``tp``
    times the work: a row's heads over ``tp`` need rows and heads as two axes
    here, and ``gated_delta_chunk`` folds them for the primal too."""
    def launch(*arrays):
        hv, n = arrays[0].shape[:2]
        heads = max(m for m in range(1, HEADS_A_STEP + 1) if hv % m == 0)
        at = (lambda i: n - 1 - i) if backwards else (lambda i: i)
        of_heads = lambda block: pl.BlockSpec((heads, None) + block, lambda h, i: (h, at(i), 0, 0))
        of_blocks = lambda block: pl.BlockSpec((None, heads) + block, lambda h, i: (at(i), h, 0, 0))
        whole = pl.BlockSpec((heads,) + arrays[-1].shape[1:], lambda h, i: (h, 0, 0))
        ins = arrays[:len(heads_first)], arrays[len(heads_first):-1]
        shapes = ([(hv, n) + block for block in heads_first_out]
                  + [(n, hv) + block for block in blocks_first_out] + [arrays[-1].shape])
        return pl.pallas_call(
            functools.partial(kernel, heads=heads),
            grid=(hv // heads, n),
            in_specs=[of_heads(a.shape[2:]) for a in ins[0]] + [of_blocks(a.shape[2:]) for a in ins[1]] + [whole],
            out_specs=[of_heads(block) for block in heads_first_out]
            + [of_blocks(block) for block in blocks_first_out] + [whole],
            out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32) for shape in shapes],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
            interpret=not _on_tpu(),
            name=name,
        )(*arrays)

    def split(heads_first_count, blocks_first_count):
        def specs(free):
            axes = tuple(a for a in BATCH_AXES if a in free)
            axes = axes if axes and carried.shape[0] % int(np.prod([free[a] for a in axes])) == 0 else None
            return (P(axes),) * heads_first_count + (P(None, axes),) * blocks_first_count + (P(axes),)
        return specs

    return per_shard(launch, split(len(heads_first), len(blocks_first)),
                     split(len(heads_first_out), len(blocks_first_out)))(*heads_first, *blocks_first, carried)


def _decays(g):
    """Of ``g`` [Hv, n, C]: ``D`` [.., C, C] (zero above the diagonal), and as
    columns [.., C, 1] ``e^gamma`` and ``e^(gamma_C - gamma)``."""
    gamma = jnp.cumsum(g, axis=-1)
    at = np.arange(g.shape[-1])
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :], gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    return decay, jnp.exp(gamma)[..., None], jnp.exp(gamma[..., -1:] - gamma)[..., None]


def _made(q, k, v, g, beta, inverse=None):
    """``_block_parts`` for the walks' kernels, heads first and ``W`` stacked
    over ``Q e^gamma``: ``(wq, U, Q K^T * D, K e^(gamma_C - gamma), e^gamma_C
    along a row of Dv)``; behind them ``(T, A)``, which the gradient reads
    too - made here, or taken as ``inverse`` where the forward rule kept
    them - and ``_decays``' three with ``beta K``, for the gradient."""
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    decay, grown, shrunk = _decays(g)
    k_beta = k * beta[..., None]
    if inverse is None:
        a = -jnp.tril(mm("hnid,hnjd->hnij", k_beta, k) * decay, -1)
        inverse = tuple(checkpoint_name(x, KEPT_ACROSS_REMAT) for x in (_inverse_of_one_minus(a), a))
    u = mm("hnij,hnjd->hnid", inverse[0], v * beta[..., None])
    w = mm("hnij,hnjd->hnid", inverse[0], k_beta * grown)
    within = mm("hnid,hnjd->hnij", q, k) * decay
    last = jnp.broadcast_to(grown[:, :, -1:], g.shape[:2] + (1, v.shape[-1]))
    streamed = jnp.concatenate([w, q * grown], axis=2), u, within, k * shrunk, last
    return streamed, inverse, (decay, grown, shrunk, k_beta)


def _blocks_fwd(q, k, v, g, beta, state):
    """The forward walk as one kernel.  Kept for the backward pass: the
    inputs, the state each block STARTED from, and ``T`` and ``A`` (the
    inverse's products are not made again; what the walk streams
    is, from ``T``: kept, it stood 2.3 GB higher in the cell's step)."""
    streamed, inverse, _ = _made(q, k, v, g, beta)
    starts, o, last = _walk(_walk_fwd_kernel, "linear_chunk_fwd", streamed, (), state,
                            [state.shape[1:]], [v.shape[2:]], backwards=False)
    return (last, o), (q, k, v, g, beta, starts, inverse)


def _cotangent_of_a(inv, u, w, d_u, d_w):
    """``U = T b``, ``W = T c`` with ``T = (I - A)^-1``: ``dA = T^T dT T^T``
    (strictly lower) where ``dT = dU b^T + dW c^T``, which is ``(T^T dU) U^T +
    (T^T dW) W^T`` - no product of two ``[C, C]`` matrices.  Returns ``(T^T
    dU, T^T dW, dA)``: the first two are ``b``'s and ``c``'s cotangents."""
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    t_du, t_dw = mm("hnji,hnjd->hnid", inv, d_u), mm("hnji,hnjd->hnid", inv, d_w)
    return t_du, t_dw, jnp.tril(mm("hnid,hnjd->hnij", t_du, u) + mm("hnid,hnjd->hnij", t_dw, w), -1)


@jax.named_scope("linear_chunk")
def _blocks_bwd(kept, cotangents):
    """The backwards walk as one kernel, from the stored starts, then the
    parts' gradient by hand (``_made`` read backwards; every ``[C, C]``
    cotangent is zero above the diagonal because ``D`` is)."""
    q, k, v, g, beta, starts, inverse = kept
    (wq, u, within, k_out, last), (inv, a), (decay, grown, shrunk, k_beta) = _made(
        q, k, v, g, beta, inverse)
    d_last, d_o = cotangents
    block = q.shape[2]
    mm = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)
    d_wq, d_u, d_within, d_k_out, d_last_sums, d_state = _walk(
        _walk_bwd_kernel, "linear_chunk_bwd", (wq, u, within, k_out, last, starts), (d_o,), d_last,
        [a.shape[2:] for a in (wq, u, within, k_out, last)], [], backwards=True)
    d_w, d_q_in = d_wq[:, :, :block], d_wq[:, :, block:]
    w, q_in = wq[:, :, :block], wq[:, :, block:]

    t_du, t_dw, d_a = _cotangent_of_a(inv, u, w, d_u, d_w)
    d_v = t_du * beta[..., None]
    d_kk = -d_a * decay                                             # of (beta K) K^T
    d_qk = d_within * decay                                         # of Q K^T
    d_k_beta = t_dw * grown + mm("hnij,hnjd->hnid", d_kk, k)
    d_beta = jnp.sum(t_du * v, axis=-1) + jnp.sum(d_k_beta * k, axis=-1)
    d_q = mm("hnij,hnjd->hnid", d_qk, k) + d_q_in * grown
    d_k = (mm("hnij,hnid->hnjd", d_kk, k_beta) + mm("hnij,hnid->hnjd", d_qk, q)
           + d_k_out * shrunk + d_k_beta * beta[..., None])
    # gamma: through D (d(D) * D is dA * A + d(within) * within), e^gamma, e^(gamma_C - gamma), e^gamma_C
    through_d = d_a * a + d_within * within
    out = jnp.sum(d_k_out * k_out, axis=-1)
    d_gamma = (jnp.sum(through_d, axis=-1) - jnp.sum(through_d, axis=-2)
               + jnp.sum(t_dw * k_beta * grown + d_q_in * q_in, axis=-1) - out)
    at_end = jnp.sum(out, axis=-1) + jnp.sum(d_last_sums[:, :, 0], axis=-1) * last[:, :, 0, 0]
    d_gamma = d_gamma.at[..., -1].add(at_end)
    d_g = lax.cumsum(d_gamma, axis=2, reverse=True)
    return d_q, d_k, d_v, d_g, d_beta, d_state


_blocks.defvjp(_blocks_fwd, _blocks_bwd)


@jax.named_scope("linear_chunk")
def gated_delta_chunk(q, k, v, g, beta, state):
    """``q``, ``k`` [T, Hv, Dk], ``v`` [T, Hv, Dv], ``g``, ``beta`` [T, Hv]
    (all float32; ``beta = 0`` and ``g = 0`` at a position that is not live)
    from ``state`` [Hv, Dk, Dv].  Returns ``(o [T, Hv, Dv], the state behind
    the last position)``, float32.  With a leading batch axis on all six
    ([B, T, Hv, ..], [B, Hv, Dk, Dv]) every row is a sequence of its own.  More
    than ``SEGMENT`` blocks (a training row; a prefill chunk is one segment)
    go a segment at a time, the state handed on (the blocks behind the last
    whole segment as one shorter call: no row is padded past its last block):
    what is live of ``[Hv, n, C, C]`` is a segment's, forwards and backwards.

    Differentiable in all six (the training path, ``models/olmo_hybrid.py``):
    under ``jax.grad`` a segment's blocks are walked by two Pallas kernels
    (``_blocks_fwd``, ``_blocks_bwd``); a call that is not differentiated
    traces none of that.  ``Dk`` and ``Dv`` may differ, and ``beta`` may reach
    2 (a transition ``I - beta k k^T`` with a negative eigenvalue)."""
    if q.ndim == 4:             # rows become heads: the rule knows no other axis
        rows, hv = q.shape[0], q.shape[2]
        fold = lambda a: jnp.moveaxis(a, 0, 1).reshape((a.shape[1], rows * hv) + a.shape[3:])
        o, last = _chunk(*(fold(a) for a in (q, k, v, g, beta)),
                         state.reshape((rows * hv,) + state.shape[2:]))
        o = jnp.moveaxis(o.reshape((o.shape[0], rows, hv, o.shape[-1])), 1, 0)
        return o, last.reshape(state.shape)
    return _chunk(q, k, v, g, beta, state)


def _chunk(q, k, v, g, beta, state):
    """:func:`gated_delta_chunk` of one sequence (or of rows folded into its heads)."""
    t, hv, dk = q.shape
    dv = v.shape[-1]
    block = BLOCK
    pad = -t % block
    if pad:     # positions that change nothing
        widen = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // block
    heads_first = lambda a: jnp.moveaxis(a.reshape((n, block) + a.shape[1:]), 2, 0)  # [Hv, n, C, ..]
    parts = tuple(heads_first(a.astype(jnp.float32)) for a in (q, k, v, g, beta))
    state, outs = state.astype(jnp.float32), []
    whole = n - n % SEGMENT if n > SEGMENT else 0
    if whole:   # a segment's parts (and, backwards, their gradient) at a time
        cut = lambda a: jnp.moveaxis(a[:, :whole].reshape((hv, whole // SEGMENT, SEGMENT) + a.shape[2:]), 1, 0)
        state, o = lax.scan(lambda s, xs: _blocks(*xs, s), state, tuple(cut(a) for a in parts))
        outs.append(o.reshape((whole,) + o.shape[2:]))
    if n > whole:   # the blocks behind the last whole segment (all of them, up to one segment)
        state, o = _blocks(*(parts if not whole else (a[:, whole:] for a in parts)), state)
        outs.append(o)
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    o = jnp.moveaxis(o, 1, 0).reshape(hv, n * block, dv)            # [n, Hv, C, Dv] -> [Hv, T, Dv]
    return jnp.moveaxis(o, 0, 1)[:t], state


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------


def _step_kernel(slots_ref, flags_ref, cols_ref, rows_ref, s_ref, o_ref, s_out_ref, *, heads: int):
    """One lane: every value head's state [Dk, Dv] read, decayed, corrected,
    read out and written back.  ``cols``: q then k as columns [Dk, Hv];
    ``rows``: v, beta and the decay as rows [Hv, Dv] each (the scalars
    broadcast along the row)."""
    del slots_ref               # read by the index maps
    flag = flags_ref[pl.program_id(0)]
    live, fresh = (flag & 1) == 1, (flag & 2) == 2
    for h in range(heads):
        kept = s_ref[0, h]
        q, k = cols_ref[0, 0][:, h:h + 1], cols_ref[0, 1][:, h:h + 1]           # [Dk, 1]
        v, beta, decay = (rows_ref[0, j * heads + h:j * heads + h + 1] for j in range(3))  # [1, Dv]
        s = jnp.where(fresh, 0.0, kept) * decay
        s = s + k * (beta * (v - jnp.sum(s * k, axis=0, keepdims=True)))
        o_ref[0, h:h + 1] = jnp.where(live, jnp.sum(s * q, axis=0, keepdims=True), 0.0)
        s_out_ref[0, h] = jnp.where(live, s, kept)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(q, k, v, g, beta, state, slots, live, fresh, *, interpret):
    b, hv, dk = q.shape
    dv = v.shape[-1]
    f32 = lambda a: a.astype(jnp.float32)
    cols = jnp.stack([f32(q), f32(k)], axis=1).transpose(0, 1, 3, 2)            # [B, 2, Dk, Hv]
    along = lambda a: jnp.broadcast_to(f32(a)[..., None], (b, hv, dv))
    rows = jnp.concatenate([f32(v), along(beta), along(jnp.exp(f32(g)))], axis=1)   # [B, 3 Hv, Dv]
    flags = live.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)
    lane = lambda i, slots, flags: (i, 0, 0)
    mine = lambda i, slots, flags: (slots[i], 0, 0, 0)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, 2, dk, hv), lambda i, slots, flags: (i, 0, 0, 0)),
                      pl.BlockSpec((1, 3 * hv, dv), lane),
                      pl.BlockSpec((1, hv, dk, dv), mine)],
            out_specs=[pl.BlockSpec((1, hv, dv), lane), pl.BlockSpec((1, hv, dk, dv), mine)]),
        out_shape=[jax.ShapeDtypeStruct((b, hv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},            # the state, behind the two scalar operands
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gated_delta_step",
    )(slots.astype(jnp.int32), flags, cols, rows, state)
    return o, state


@jax.named_scope("linear_attend")
def gated_delta_step(q, k, v, g, beta, state, slots, live, fresh):
    """One token a lane against the states of ``slots``.

    q, k: ``[B, Hv, Dk]``; v: ``[B, Hv, Dv]``; g, beta: ``[B, Hv]``; state:
    ``[slots, Hv, Dk, Dv]`` float32 (every slot's, the call's lanes or not);
    slots: ``[B]`` int32, no slot twice; live, fresh: ``[B]`` bool.  A lane
    that is ``fresh`` starts from zero whatever its slot holds; a lane that
    is not ``live`` leaves its slot's state as it was and reads out zeros.
    Returns ``(o [B, Hv, Dv] float32, state)``; the state comes back in the
    buffer it came in when the caller donates it."""
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is kept in float32, not {state.dtype}")
    return _step(q, k, v, g, beta, state, slots, live, fresh, interpret=not _on_tpu())
