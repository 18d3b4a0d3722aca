"""The two float32 passes over the rows that a TRAINED Gated DeltaNet mixer
runs around the delta rule (``models/olmo_hybrid.py``), each ONE pass over the
rows forward and ONE backward:

- :func:`conv_silu_l2norm`, before the rule, over the q, k and v projections'
  outputs at once: the depthwise causal conv over four positions (every row
  from zeros before position 0) and silu; then per head ``q <- l2norm(q) *
  Dk^-0.5``, ``k <- l2norm(k)`` (eps inside the root), ``v`` as it is.
- :func:`gated_rmsnorm`, behind it: ``rmsnorm(o; w) * silu(z)``, cast once.

Plain XLA splits each: the compiler for the described chip makes a reduce, a
broadcast and an elementwise fusion of a norm, and of its hand-written
gradient three to five, each a pass over ``[T, C]`` in float32 with the rows
moved to the minor dimension and back.  So each pass is a Pallas kernel over
the grid (batch row, block of channels, block of rows) and its gradient
another, by hand (``jax.custom_vjp``): the backward keeps the pass's INPUTS
only and makes conv, silu and the norm again inside the one pass that writes
the inputs' cotangents and sums the taps' (the weight's) over the rows.

A block of channels holds whole heads on whole registers: ``n`` heads with
``n * Dk`` and ``n * Dv`` multiples of 128 lanes (four heads: 384 channels of
q, 384 of k and 768 of v side by side in one step, each read from and written
to its OWN array: nothing is concatenated or split around a call).  Where an
array's width is not whole blocks its last block hangs over the edge and the
kernel blanks those lanes.  A head's sum is a product with the block's 0/1
matrix ``[channels, heads]`` and back with its transpose: on the MXU, a
float32 operand as three bfloat16 terms, exact against 0/1.  The conv reads
the 8 rows before its block of rows (the gradient also the 8 behind it)
through a second view of the same array.  No loop in a kernel but the taps.

Each pass is traced ONCE and lowered once however many layers call it (the
forward once more for a ``remat``'s second forward, whose ``jit`` a partial
evaluation has rebuilt): the launches sit under ``jax.jit``, one function of
this module a kernel, and the scope words that the device metrics read
(``linear_conv``, ``linear_out``) are stated inside, for the gradient too.
Under a mesh the batch rows divide over the batch axes (``per_shard``, as the
rule's walks): rows know nothing of each other, and a sum over the rows
leaves the call as one partial a batch row.

The serving path (``models/qwen3_next.py``: a carried window a slot, ragged
lengths, never differentiated) keeps ``ops/gated_delta.py``'s plain helpers;
nothing here is reached from there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallelism_config import BATCH_AXES
from .flash_attention import _on_tpu, per_shard

TAPS = 4
HALO = 8        # rows read before (and, backwards, behind) a block of rows: one float32 tile, at least TAPS - 1
LANES = 128
L2_EPS = 1e-6   # inside the root, as ``gated_delta.l2norm``
BLOCK_BYTES = 3 * 2**18    # of the float32 values a step writes, [rows, its channels]: the conv's gradient holds ~20


# ---------------------------------------------------------------------------
# inside a kernel
# ---------------------------------------------------------------------------


def _against(a, ones):
    """``a @ ones`` for float32 ``a`` and a 0/1 matrix in bfloat16: ``a`` as
    three bfloat16 terms that sum to it, so three MXU passes are exact where
    ``Precision.HIGHEST`` would run six."""
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    dot = lambda term: jnp.dot(term, ones, preferred_element_type=jnp.float32)
    return dot(hi) + dot(mid) + dot(low)


def _blanked(x, widths, blocks):
    """``x`` [.., sum(blocks)], this step's blocks of several arrays side by side, with zeros in the lanes that
    lie outside their arrays (a last block that hangs over the edge holds anything there); ``x`` itself where
    every array is whole blocks wide."""
    if not any(width % block for width, block in zip(widths, blocks)):
        return x
    j = pl.program_id(1)
    inside = jnp.concatenate([lax.broadcasted_iota(jnp.int32, (1, block), 1) < width - j * block
                              for width, block in zip(widths, blocks)], axis=1)
    return jnp.where(inside, x, 0.0)


def _stacked(views, first, last):
    """``(before, block, behind)`` of several arrays (``before`` or ``behind`` may be None), stacked along
    the rows, a halo read as zeros at a sequence's ends; the arrays' lanes side by side."""
    columns = []
    for before, block, behind in views:
        parts = ([] if before is None else [jnp.where(first, 0.0, before[...])]) + [block[...]]
        columns.append(jnp.concatenate(parts + ([] if behind is None else [jnp.where(last, 0.0, behind[...])])))
    return jnp.concatenate(columns, axis=1)


def _conv(window, weights, rows):
    """``rows`` rows of the causal conv; ``window`` starts ``HALO`` rows before the first of them.  Summed
    from the oldest tap on, as ``gated_delta.causal_conv_chunk`` sums."""
    return sum(window[HALO - s:HALO - s + rows] * weights[TAPS - 1 - s:TAPS - s] for s in reversed(range(TAPS)))


def _silu_slope(x, sig):
    return sig * (1.0 + x * (1.0 - sig))


def _q_scale(normed, key_dim):
    """[1, normed] float32: ``Dk^-0.5`` over q's half of the normed lanes, 1 over k's."""
    return jnp.where(lax.broadcasted_iota(jnp.int32, (1, normed), 1) < normed // 2, key_dim ** -0.5, 1.0)


def _conv_fwd_kernel(*refs, widths, key_dim):
    """q, k, v, each ``(before, block)``; their taps; the 0/1 matrices; then the three outputs."""
    views, taps_refs, (heads_ref, lanes_ref), outs = refs[:6], refs[6:9], refs[9:11], refs[11:]
    rows, blocks = outs[0].shape[0], tuple(out.shape[1] for out in outs)
    window = _stacked([views[a:a + 2] + (None,) for a in (0, 2, 4)], pl.program_id(2) == 0, None)
    weights = jnp.concatenate([ref[...] for ref in taps_refs], axis=1)
    pre = _blanked(_conv(window, weights, rows), widths, blocks)
    y = pre * jax.nn.sigmoid(pre)
    normed = 2 * blocks[0]
    qk = y[:, :normed]
    r = lax.rsqrt(_against(qk * qk, heads_ref[...]) + L2_EPS)               # a head a lane
    qk = qk * _against(r, lanes_ref[...]) * _q_scale(normed, key_dim)      # the norm, then q's scale, as the model had
    outs[0][...], outs[1][...], outs[2][...] = qk[:, :blocks[0]], qk[:, blocks[0]:], y[:, normed:]


def _conv_bwd_kernel(*refs, widths, key_dim):
    """q, k, v, each ``(before, block, behind)``; the outputs' cotangents,
    each ``(block, behind)``; the taps; the 0/1 matrices; then ``dx`` of the
    three and the taps' gradients.  The block's rows and the ``HALO`` behind
    them are made again (a row's ``dx`` reads the pre-activation's cotangent
    up to three rows on)."""
    views, cots, taps_refs, (heads_ref, lanes_ref) = refs[:9], refs[9:15], refs[15:18], refs[18:20]
    dx_refs, d_taps_refs = refs[20:23], refs[23:26]
    rows, blocks = dx_refs[0].shape[0], tuple(dx.shape[1] for dx in dx_refs)
    i, last = pl.program_id(2), pl.program_id(2) == pl.num_programs(2) - 1
    window = _stacked([views[a:a + 3] for a in (0, 3, 6)], i == 0, last)
    d_out = _blanked(_stacked([(None,) + cots[a:a + 2] for a in (0, 2, 4)], None, last), widths, blocks)
    weights = jnp.concatenate([ref[...] for ref in taps_refs], axis=1)
    pre = _blanked(_conv(window, weights, rows + HALO), widths, blocks)
    sig = jax.nn.sigmoid(pre)
    y = pre * sig
    normed = 2 * blocks[0]
    qk, d_qk = y[:, :normed], d_out[:, :normed] * _q_scale(normed, key_dim)
    r = lax.rsqrt(_against(qk * qk, heads_ref[...]) + L2_EPS)
    along = r * r * r * _against(d_qk * qk, heads_ref[...])                 # of a head's own direction
    d_qk = d_qk * _against(r, lanes_ref[...]) - qk * _against(along, lanes_ref[...])
    d_pre = jnp.concatenate([d_qk, d_out[:, normed:]], axis=1) * _silu_slope(pre, sig)
    dx = sum(d_pre[s:s + rows] * weights[TAPS - 1 - s:TAPS - s] for s in range(TAPS))
    d_taps = jnp.concatenate([jnp.sum(d_pre[:rows] * window[HALO - s:HALO - s + rows], axis=0, keepdims=True)
                              for s in reversed(range(TAPS))])

    @pl.when(i == 0)
    def _():
        for d_taps_ref in d_taps_refs:
            d_taps_ref[...] = jnp.zeros_like(d_taps_ref)

    at = 0
    for dx_ref, d_taps_ref, block in zip(dx_refs, d_taps_refs, blocks):
        dx_ref[...] = dx[:, at:at + block]
        d_taps_ref[...] += d_taps[:, at:at + block]
        at += block


def _gate_fwd_kernel(o_ref, z_ref, weight_ref, heads_ref, lanes_ref, out_ref, *, widths, eps, head_dim):
    o, z = (_blanked(ref[...], widths, out_ref.shape[1:]) for ref in (o_ref, z_ref))
    r = lax.rsqrt(_against(o * o, heads_ref[...]) / head_dim + eps)
    out_ref[...] = (o * _against(r, lanes_ref[...]) * weight_ref[...] * (z * jax.nn.sigmoid(z))).astype(out_ref.dtype)


def _gate_bwd_kernel(o_ref, z_ref, d_out_ref, weight_ref, heads_ref, lanes_ref, d_o_ref, d_z_ref, d_weight_ref,
                     *, widths, eps, head_dim):
    o, z, d_out = (_blanked(ref[...].astype(jnp.float32), widths, d_o_ref.shape[1:])
                   for ref in (o_ref, z_ref, d_out_ref))
    weight = weight_ref[...]
    sig = jax.nn.sigmoid(z)
    r = lax.rsqrt(_against(o * o, heads_ref[...]) / head_dim + eps)
    r_wide = _against(r, lanes_ref[...])
    normed = o * r_wide
    d_scaled = d_out * (z * sig)                                            # of ``normed * weight``
    d_z_ref[...] = d_out * normed * weight * _silu_slope(z, sig)
    d_normed = d_scaled * weight
    along = r * r * r * _against(d_normed * o, heads_ref[...]) * (1.0 / head_dim)
    d_o_ref[...] = d_normed * r_wide - o * _against(along, lanes_ref[...])

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_weight_ref[...] = jnp.zeros_like(d_weight_ref)

    d_weight_ref[...] += jnp.sum(d_scaled * normed, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# around a kernel
# ---------------------------------------------------------------------------


def _heads_a_block(*head_dims):
    """The fewest heads whose channels fill whole registers at every one of the head sizes."""
    return math.lcm(*(LANES // math.gcd(LANES, d) for d in head_dims))


def _launch(kernel, name, streamed, blocks, halos, fixed, normed, head_dim, outs, sums):
    """``kernel`` over the grid (batch row, block of channels, block of rows).

    ``streamed``: arrays ``[B, T, C_a]``; a step reads of array ``a`` its
    ``[rows, blocks[a]]`` block, and for each ``-1`` / ``+1`` of ``halos[a]``
    the ``HALO`` rows before / behind it (clamped at a row's ends, where the
    kernel reads zeros instead), those before ahead of the block.  ``fixed``:
    ``(array [n, C_a], a)``, a step reads the columns of block ``a``; ``a =
    None``: the whole of it at every step.  Behind them the 0/1 matrices of
    ``normed`` lanes in heads of ``head_dim`` (channels to heads, one head a
    lane, and back).  ``outs``: ``(dtype, a)``, written as ``streamed[a]`` is
    read; ``sums``: ``(n, a)``, float32 ``[B, n, C_a]`` whose block stays in
    VMEM while the grid walks a batch row's blocks of rows (the kernel zeroes
    it at the first and adds), summed over ``B`` here.  ``T`` is padded with
    zero rows to whole blocks, and the outputs cut back."""
    b, t = streamed[0].shape[:2]
    widths = [a.shape[2] for a in streamed]
    rows = min(max(BLOCK_BYTES // (4 * sum(blocks[a] for _, a in outs)) // 16 * 16, 16), -(-t // 16) * 16)
    pad = -t % rows
    if pad:
        streamed = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in streamed]
    tiles, ends = rows // HALO, (t + pad) // HALO - 1
    at = lambda a: pl.BlockSpec((None, rows, blocks[a]), lambda r, j, i: (r, i, j))
    halo = lambda a, tile: pl.BlockSpec((None, HALO, blocks[a]), lambda r, j, i: (r, tile(i), j))
    beside = {-1: lambda a: halo(a, lambda i: jnp.maximum(i * tiles - 1, 0)),
              +1: lambda a: halo(a, lambda i: jnp.minimum((i + 1) * tiles, ends))}
    columns = lambda n, a: pl.BlockSpec((n, blocks[a]), lambda r, j, i: (0, j))
    whole = lambda shape: pl.BlockSpec(shape, lambda r, j, i: (0, 0))
    # channels to heads, one head a lane (made of iotas: as a literal it would be most of the lowered text)
    heads = (jnp.arange(normed)[:, None] // head_dim == jnp.arange(LANES)[None]).astype(jnp.bfloat16)
    in_specs, take = [], []
    for a, sides in enumerate(halos):
        for side in [s for s in sides if s < 0] + [0] + [s for s in sides if s > 0]:
            in_specs.append(beside[side](a) if side else at(a))
            take.append(a)
    in_specs += [whole(array.shape) if a is None else columns(array.shape[0], a) for array, a in fixed]
    in_specs += [whole(heads.shape), whole(heads.shape[::-1])]
    out_specs = ([at(a) for _, a in outs]
                 + [pl.BlockSpec((None, n, blocks[a]), lambda r, j, i: (r, 0, j)) for n, a in sums])

    def launch(heads, *arrays):
        fixed_here, streamed_here = arrays[:len(fixed)], arrays[len(fixed):]
        mine = streamed_here[0].shape[0]                            # a device's own batch rows
        out_shape = ([jax.ShapeDtypeStruct(streamed_here[a].shape, dtype) for dtype, a in outs]
                     + [jax.ShapeDtypeStruct((mine, n, widths[a]), jnp.float32) for n, a in sums])
        return pl.pallas_call(
            kernel, grid=(mine, pl.cdiv(widths[0], blocks[0]), (t + pad) // rows),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 2**20),
            interpret=not _on_tpu(), name=name,
        )(*(streamed_here[a] for a in take), *fixed_here, heads, heads.T)

    def over_rows(count):
        def specs(free):
            axes = tuple(a for a in BATCH_AXES if a in free)
            axes = axes if axes and b % int(np.prod([free[a] for a in axes])) == 0 else None
            return (P(axes),) * count
        return specs

    results = per_shard(launch, lambda free: (P(),) * (1 + len(fixed)) + over_rows(len(streamed))(free),
                        over_rows(len(outs) + len(sums)))(heads, *(array for array, _ in fixed), *streamed)
    return ([o[:, :t] if pad else o for o in results[:len(outs)]]
            + [jnp.sum(s, axis=0) for s in results[len(outs):]])


def _ambient():
    """What a trace under ``per_shard`` depends on besides its arguments: part of the ``jit`` cache's key."""
    from ..state import free_mesh_axes

    mesh, rest, _ = free_mesh_axes()
    return mesh, frozenset(rest)


def _conv_blocks(x, heads):
    key_dim, value_dim = x[0].shape[-1] // heads, x[2].shape[-1] // heads
    n = _heads_a_block(key_dim, value_dim)
    return key_dim, (n * key_dim, n * key_dim, n * value_dim)


@functools.partial(jax.jit, static_argnames=("heads", "ambient"))
@jax.named_scope("linear_conv")
def _conv_forward(x, taps, *, heads, ambient):
    del ambient
    key_dim, blocks = _conv_blocks(x, heads)
    kernel = functools.partial(_conv_fwd_kernel, widths=tuple(a.shape[-1] for a in x), key_dim=key_dim)
    return tuple(_launch(kernel, "linear_conv_fwd", list(x), blocks, [(-1,)] * 3, [(w, a) for a, w in enumerate(taps)],
                         2 * blocks[0], key_dim, [(jnp.float32, a) for a in range(3)], []))


@functools.partial(jax.jit, static_argnames=("heads", "ambient"))
@jax.named_scope("linear_conv")
def _conv_backward(x, taps, d_out, *, heads, ambient):
    del ambient
    key_dim, blocks = _conv_blocks(x, heads)
    kernel = functools.partial(_conv_bwd_kernel, widths=tuple(a.shape[-1] for a in x), key_dim=key_dim)
    results = _launch(kernel, "linear_conv_bwd", list(x) + list(d_out), blocks * 2, [(-1, +1)] * 3 + [(+1,)] * 3,
                      [(w, a) for a, w in enumerate(taps)], 2 * blocks[0], key_dim,
                      [(jnp.float32, a) for a in range(3)], [(TAPS, a) for a in range(3)])
    return tuple(results[:3]), tuple(results[3:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_pass(x, taps, heads):
    return _conv_forward(x, taps, heads=heads, ambient=_ambient())


def _conv_pass_fwd(x, taps, heads):
    return _conv_pass(x, taps, heads), (x, taps)


def _conv_pass_bwd(heads, kept, d_out):
    return _conv_backward(*kept, d_out, heads=heads, ambient=_ambient())


_conv_pass.defvjp(_conv_pass_fwd, _conv_pass_bwd)


def conv_silu_l2norm(q, k, v, q_taps, k_taps, v_taps, heads: int):
    """``q``, ``k`` [B, T, heads * Dk], ``v`` [B, T, heads * Dv], float32,
    every row a sequence from position 0; each one's taps [4, C] (tap 3 meets
    the current row).  Returns ``(l2norm(silu(conv(q))) * Dk^-0.5,
    l2norm(silu(conv(k))), silu(conv(v)))``, the norms per head with eps
    ``1e-6`` inside the root, float32, in the inputs' shapes.  Differentiable
    in all six; the backward keeps those six."""
    x, taps = (q, k, v), (q_taps, k_taps, v_taps)
    if any(w.shape != (TAPS, a.shape[-1]) or a.shape[-1] % heads for a, w in zip(x, taps)) or q.shape != k.shape:
        raise ValueError(f"{TAPS} taps a channel and {heads} whole heads an array, q's as many as k's: "
                         f"{[a.shape for a in x + taps]}")
    f32 = lambda arrays: tuple(a.astype(jnp.float32) for a in arrays)
    return _conv_pass(f32(x), f32(taps), heads)


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "ambient"))
@jax.named_scope("linear_out")
def _gate_forward(o, z, weight, *, eps, dtype, ambient):
    del ambient
    head_dim = weight.shape[0]
    block = _heads_a_block(head_dim) * head_dim
    kernel = functools.partial(_gate_fwd_kernel, widths=(o.shape[-1],), eps=eps, head_dim=head_dim)
    return _launch(kernel, "linear_out_fwd", [o, z], (block, block), [(), ()],
                   [(jnp.tile(weight, block // head_dim)[None], None)], block, head_dim, [(dtype, 0)], [])[0]


@functools.partial(jax.jit, static_argnames=("eps", "ambient"))
@jax.named_scope("linear_out")
def _gate_backward(o, z, weight, d_out, *, eps, ambient):
    del ambient
    head_dim = weight.shape[0]
    block = _heads_a_block(head_dim) * head_dim
    kernel = functools.partial(_gate_bwd_kernel, widths=(o.shape[-1],), eps=eps, head_dim=head_dim)
    d_o, d_z, d_weight = _launch(
        kernel, "linear_out_bwd", [o, z, d_out], (block,) * 3, [(), (), ()],
        [(jnp.tile(weight, block // head_dim)[None], None)], block, head_dim,
        [(jnp.float32, 0), (jnp.float32, 1)], [(1, 0)])
    return d_o, d_z, jnp.sum(d_weight.reshape(-1, head_dim), axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gate_pass(o, z, weight, eps, dtype):
    return _gate_forward(o, z, weight, eps=eps, dtype=dtype, ambient=_ambient())


def _gate_pass_fwd(o, z, weight, eps, dtype):
    return _gate_pass(o, z, weight, eps, dtype), (o, z, weight)


def _gate_pass_bwd(eps, dtype, kept, d_out):
    return _gate_backward(*kept, d_out, eps=eps, ambient=_ambient())


_gate_pass.defvjp(_gate_pass_fwd, _gate_pass_bwd)


def gated_rmsnorm(o, z, weight, eps: float, dtype):
    """``(rmsnorm(o; weight) * silu(z)).astype(dtype)``: ``o``, ``z`` [B, T,
    Hv * Dv] float32, the norm over each head's ``Dv = len(weight)`` channels
    with the one ``weight`` for all heads.  Differentiable in ``o``, ``z``
    and ``weight``; the backward keeps those three."""
    if o.shape != z.shape or o.shape[-1] % weight.shape[0]:
        raise ValueError(f"whole heads of {weight.shape[0]} channels in o and z alike: {o.shape}, {z.shape}")
    f32 = lambda a: a.astype(jnp.float32)
    return _gate_pass(f32(o), f32(z), f32(weight), float(eps), jnp.dtype(dtype))
