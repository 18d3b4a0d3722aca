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
  runs over thousands of tokens.  Differentiable (the training path,
  ``models/olmo_hybrid.py``): the backward pass keeps the inputs and ONE state
  a block, makes each block's ``A``, ``T``, ``U``, ``W`` again and walks the
  blocks backwards.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

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


def _blocks_fwd(q, k, v, g, beta, state):
    """What the backward pass keeps: the inputs and the state each block STARTED from."""
    def keeping(s, xs):
        behind, o = _one_block(s, xs)
        return behind, (o, s)

    last, (o, starts) = lax.scan(keeping, state, _block_parts(q, k, v, g, beta))
    return (last, o), (q, k, v, g, beta, starts)


@jax.named_scope("linear_chunk")
def _blocks_bwd(kept, cotangents):
    """Every block's parts made again from the inputs (the triangular inverse
    included), the blocks walked backwards from the stored starts, then the
    parts' own gradient: nothing of ``[Hv, n, C, C]`` outlives this call."""
    *inputs, starts = kept
    d_last, d_o = cotangents
    parts, pull_parts = jax.vjp(_block_parts, *inputs)

    def one_back(d_s, xs):
        s, xs_i, d_o_i = xs
        _, pull = jax.vjp(_one_block, s, xs_i)
        return pull((d_s, d_o_i))

    d_state, d_parts = lax.scan(one_back, d_last, (starts, parts, d_o), reverse=True)
    return pull_parts(d_parts) + (d_state,)


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

    Differentiable in all six (the training path, ``models/olmo_hybrid.py``),
    with a backward pass that keeps the inputs and one state a block
    (``_blocks_bwd``).  ``Dk`` and ``Dv`` may differ, and ``beta`` may reach 2
    (a transition ``I - beta k k^T`` with a negative eigenvalue)."""
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
