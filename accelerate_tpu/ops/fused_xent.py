"""Fused linear + cross-entropy: the vocab projection without the logits.

For a causal LM the [tokens, vocab] logits tensor is the single largest
activation (batch 8 x seq 2048 x vocab 32k fp32 = 2.1 GB) and it is consumed
by exactly one reduction.  This op chunks the ROWS: ``num_chunks`` pieces of
the sequence, each against every column of the head a device holds.  A
chunk's log-sum-exp is complete when its logits are, so the one loop builds
each chunk's logits once and, where the call is differentiated, makes
``p - onehot``, the chunk's ``dh`` rows and its term of ``dw`` from them at
once, in the ``custom_vjp``'s forward rule: three matmuls a chunk.  The
backward rule scales the two saved fp32 gradients by the cotangent and casts
them; a call that is not differentiated runs the loop without the gradient.
Peak memory drops from O(N*V) to O(N*V/chunks) while every matmul stays
MXU-shaped: bf16 operands, fp32 accumulation, ``p`` from the fp32 logits.

This is the TPU-native analog of the fused-loss kernels the reference gets
from its engines (e.g. DeepSpeed/Megatron fused CE, reference
megatron_lm.py loss paths); here it is a custom_vjp over XLA dots, which is
exactly what the hardware wants (no Pallas needed — the win is scheduling,
not kernel fusion).

Under a mesh every row stays on the device that holds it.  A chunk is a piece
of the sequence EACH device holds (``hidden`` is viewed as [batch groups, B/g,
sequence groups, T/g, H], split as ``parallel/sharding.constrain_activation``
pins it), the head is gathered over the FSDP axes once, before the loop, and
the loop carries each device's own unreduced fp32 ``dw``: one reduction over
the data-parallel axes, after the loop, as every parameter's gradient has.

The op is vocabulary-parallel too.  When the context leaves a ``tp`` axis
wider than one to GSPMD and the vocabulary divides by it, the loop runs inside
a ``shard_map`` that is manual over ``tp`` alone (scope
``fused_xent/.../vocab_shard``) on a shard's slice of the head.  What crosses
``tp``: a chunk's max, sum-exp and label logit, three [N/chunks] fp32 vectors a
chunk; the partial ``dh`` [N, H], summed once in fp32 after the loop.  ``dw``
of a slice stays on its shard.  One chip, FSDP only, a region already manual
over ``tp`` or a vocabulary ``tp`` does not divide: the bare loop, with no
``shard_map`` traced.  Nothing selects the path but the mesh and the
vocabulary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _dot(a, b, contract, batch=((), ())):
    return jax.lax.dot_general(a, b, (contract, batch), preferred_element_type=jnp.float32)


def _row_chunks(hidden, weight, labels, coef=None, *, num_chunks, vocab_major, tp=False):
    """A tuple: ``lse - label_logit`` of every row ([B, T] fp32), a chunk of
    rows at a time against all of ``weight``'s columns, and with ``coef``
    ([B, T] fp32, d loss / d that) the fp32 ``dh`` [B, T, H] and ``dw``
    (``weight``'s shape) too, from the same logits.  ``labels`` index
    ``weight``'s own columns.  ``tp``: ``weight`` is a ``tp`` shard's slice
    inside the region manual over ``tp``; a label in another shard's slice is
    -1, the row statistics are reduced over ``tp`` a chunk at a time and
    ``dh`` once, after the loop."""
    from ..parallel.sharding import BATCH_AXES, SEQ_AXES, _axis_size
    from ..state import free_mesh_axes

    b, t, _ = hidden.shape
    mesh, _, free = free_mesh_axes()
    rows = tuple(a for a in BATCH_AXES if a in free)
    seq = tuple(a for a in SEQ_AXES if a in free)
    rows = rows if b % _axis_size(mesh, rows) == 0 else ()
    seq = seq if t % _axis_size(mesh, seq) == 0 else ()
    gb, gs = _axis_size(mesh, rows), _axis_size(mesh, seq)
    tc = -(-(t // gs) // num_chunks)  # a chunk: ``tc`` positions of what a device holds
    pad = num_chunks * tc - t // gs   # masked rows behind a tail chunk
    h_dim, v_dim = (1, 0) if vocab_major else (0, 1)

    def pin(x, *spec):  # only where the mesh splits the rows: elsewhere GSPMD's own choice
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec))) if rows or seq else x

    def grouped(x):  # [B, T, ...] -> [gb, B/gb, gs, T/gs + pad, ...]: dims 0 and 2 are the devices'
        x = x.reshape(gb, b // gb, gs, t // gs, *x.shape[2:])
        return jnp.pad(x, [(0, 0)] * 3 + [(0, pad)] + [(0, 0)] * (x.ndim - 4)) if pad else x

    def zeros(*shape):
        return pin(jnp.zeros(shape, jnp.float32), rows or None, None, seq or None)

    weight = pin(weight)  # an FSDP-sharded head is gathered once, for the whole loop
    hidden, labels = grouped(hidden), grouped(labels)
    coef = None if coef is None else grouped(coef)

    def body(c, carry):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, c * tc, tc, axis=3)
        put = lambda x, x_c: jax.lax.dynamic_update_slice_in_dim(x, x_c, c * tc, axis=3)
        h_c, lab = cut(hidden), cut(labels)
        logits = _dot(h_c, weight, ((4,), (h_dim,)))  # [gb, B/gb, gs, tc, V] fp32, once
        m = jnp.max(logits, axis=-1)
        m = jax.lax.pmax(m, "tp") if tp else m
        l = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        label_logit = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        label_logit = jnp.where(lab >= 0, label_logit, 0.0)
        if tp:
            l, label_logit = jax.lax.psum((l, label_logit), "tp")
        lse = m + jnp.log(l)
        nll = put(carry[0], lse - label_logit)
        if coef is None:
            return (nll,)
        onehot = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 4) == lab[..., None]
        dlogits = ((jnp.exp(logits - lse[..., None]) - onehot) * cut(coef)[..., None]).astype(h_c.dtype)
        # written once: fused into both matmuls' operands the exp is redone for every output tile
        dlogits = jax.lax.optimization_barrier(dlogits)
        dh = put(carry[1], _dot(dlogits, weight, ((4,), (v_dim,))))
        # rows contract, the devices' dims are batch: each device's own term, [gb, gs, *weight.shape]
        pair = (dlogits, h_c) if vocab_major else (h_c, dlogits)
        return nll, dh, carry[2] + _dot(*pair, ((1, 3), (1, 3)), ((0, 2), (0, 2)))

    init = (zeros(*hidden.shape[:4]),)
    if coef is not None:
        dw = jnp.zeros((gb, gs, *weight.shape), jnp.float32)  # each device's own, unreduced
        init += (zeros(*hidden.shape), pin(dw, rows or None, seq or None))
    nll, *grads = jax.lax.fori_loop(0, num_chunks, body, init)
    whole = lambda x: x[:, :, :, : t // gs].reshape(b, t, *x.shape[4:])
    if coef is None:
        return (whole(nll),)
    dh, dw = whole(grads[0]), grads[1].sum((0, 1))  # dw crosses the data-parallel axes here, once
    return whole(nll), jax.lax.psum(dh, "tp") if tp else dh, dw


def _per_vocab_shard(weight, grads, **how):
    """:func:`_row_chunks` wrapped to run once per ``tp`` shard on that shard's
    slice of the vocabulary, or None where the bare call is the one to make:
    no mesh, no ``tp`` axis wider than one that the context still leaves to
    GSPMD (one chip, FSDP only, a region already manual over ``tp``), or a
    vocabulary ``tp`` does not divide.

    Only ``tp`` goes manual.  Rows stay split over the data-parallel axes by
    GSPMD inside as outside: the gather of an FSDP-sharded head slice and the
    reduction of ``dw`` over ``dp_shard`` are the ones every parameter has."""
    from ..state import free_mesh_axes

    mesh, _, free = free_mesh_axes()
    tp = free.get("tp", 1)
    v = weight.shape[0 if how["vocab_major"] else 1]
    if tp == 1 or v % tp:
        return None
    w_spec = P("tp", None) if how["vocab_major"] else P(None, "tp")
    width = v // tp

    @jax.named_scope("vocab_shard")
    def shard(hidden, w, labels, *coef):
        local = labels - jax.lax.axis_index("tp") * width  # columns of the slice, -1 outside it
        local = jnp.where((local >= 0) & (local < width), local, -1)
        return _row_chunks(hidden, w, local, *coef, tp=True, **how)

    # jit: a shard_map that leaves axes to GSPMD cannot run eagerly
    return jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(), w_spec, P()) + (P(),) * grads,
        out_specs=(P(), P(), w_spec) if grads else (P(),), axis_names={"tp"}, check_vma=False,
    ))


@jax.named_scope("fused_xent")
def _loss(hidden, weight, labels, mask, num_chunks, vocab_major, grads):
    """The mean loss over ``mask``; with ``grads`` its fp32 gradients too."""
    how = dict(num_chunks=num_chunks, vocab_major=vocab_major)
    run = _per_vocab_shard(weight, grads, **how) or functools.partial(_row_chunks, **how)
    valid = mask.astype(jnp.float32)
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    nll, *made = run(hidden, weight, labels, *((valid / n_valid,) if grads else ()))
    return jnp.sum(nll * valid) / n_valid, *made


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_linear_xent(hidden, weight, labels, mask, num_chunks, vocab_major):
    """hidden [B, T, H], weight [V, H] (``vocab_major``) or [H, V], labels and
    mask [B, T]: mean of ``logsumexp(hidden @ weight) - label logit`` over the
    rows ``mask`` keeps."""
    return _loss(hidden, weight, labels, mask, num_chunks, vocab_major, False)[0]


def _fwd(hidden, weight, labels, mask, num_chunks, vocab_major):
    loss, dh, dw = _loss(hidden, weight, labels, mask, num_chunks, vocab_major, True)
    return loss, (dh, dw, jnp.zeros((), hidden.dtype), jnp.zeros((), weight.dtype))


@jax.named_scope("fused_xent")
def _bwd(num_chunks, vocab_major, res, gbar):
    dh, dw, like_hidden, like_weight = res  # the fp32 sums: the cotangent scales them before the cast
    return (dh * gbar).astype(like_hidden.dtype), (dw * gbar).astype(like_weight.dtype), None, None


fused_linear_xent.defvjp(_fwd, _bwd)


def fused_causal_lm_loss(hidden, weight, labels, *, vocab_major: bool,
                         num_chunks: int = 8, ignore_index: int = -100,
                         shifted: bool = False):
    """Shifted next-token CE from pre-head hidden states.

    hidden [B, T, H], weight [V, H] (``vocab_major``, e.g. a tied embedding
    table) or [H, V] (an lm_head kernel), labels [B, T].  ``shifted=True``:
    labels are already next-token aligned (the context-parallel contract —
    see models/llama.py:causal_lm_loss).  Otherwise the labels move one
    position left and the last position carries none: all T rows go through
    the loop, so the rows stay a multiple of the tile and of ``num_chunks``.
    """
    if not shifted:
        labels = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], ignore_index)], axis=1)
    mask = labels != ignore_index
    return fused_linear_xent(hidden, weight, jnp.where(mask, labels, 0), mask, num_chunks, vocab_major)
