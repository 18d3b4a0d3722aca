"""Fused linear + cross-entropy: the vocab projection without the logits.

For a causal LM the [tokens, vocab] logits tensor is the single largest
activation (batch 8 x seq 2048 x vocab 32k fp32 = 2.1 GB) and it is consumed
by exactly one reduction.  This op chunks the vocab axis: the forward scans
weight chunks keeping only online logsumexp stats + the label logit; the
backward rebuilds each chunk's probabilities and immediately contracts them
into d_hidden / d_weight.  Peak memory drops from O(N*V) to O(N*V/chunks)
while every matmul stays MXU-shaped.

This is the TPU-native analog of the fused-loss kernels the reference gets
from its engines (e.g. DeepSpeed/Megatron fused CE, reference
megatron_lm.py loss paths); here it is a custom_vjp over XLA dots, which is
exactly what the hardware wants (no Pallas needed — the win is scheduling,
not kernel fusion).

Under a mesh the op is vocabulary-parallel.  When the context leaves a ``tp``
axis wider than one to GSPMD and the vocabulary divides by it, forward and
backward each run inside a ``shard_map`` that is manual over ``tp`` alone
(scope ``fused_xent/.../vocab_shard``): a shard chunks ITS slice of the head
(``num_chunks`` chunks of what a device holds), so no slice runs across
shards.  What crosses ``tp``: the max, the rescaled sum-exp and the label
logit, three [N] fp32 vectors, forward; the partial ``dh`` [N, H], summed once
in fp32 before the cast, backward.  ``dw`` of a slice stays on its shard.  Rows
stay split over the data-parallel axes and an FSDP-sharded hidden dim of the
head is gathered a chunk at a time, by GSPMD inside the region as outside.
One chip, FSDP only, a region already manual over ``tp`` or a vocabulary
``tp`` does not divide: the bare chunk loop, with no ``shard_map`` traced.
Nothing selects the path but the mesh and the vocabulary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_MASK = -0.7 * float(np.finfo(np.float32).max)


def _chunk_logits(hidden, weight, c, chunk, vocab_major: bool, gather: bool = False):
    """Logits for vocab chunk ``c``: [N, chunk] fp32 (bf16 operands, fp32
    accumulation), with out-of-vocab columns masked.  ``gather`` (inside the
    per-``tp``-shard region): the chunk is asked whole along its hidden dim
    on every device GSPMD still places, so an FSDP-sharded head is gathered
    a chunk at a time and the rows stay where they are."""
    axis = 0 if vocab_major else 1
    w_c = jax.lax.dynamic_slice_in_dim(weight, c * chunk, chunk, axis=axis)
    if gather:
        w_c = jax.lax.with_sharding_constraint(w_c, jax.sharding.PartitionSpec())
    contract = (((1,), (1 - axis,)), ((), ()))  # [V, H] or [H, V]
    logits = jax.lax.dot_general(hidden, w_c, contract, preferred_element_type=jnp.float32)
    return logits, w_c


def _num_vocab(weight, vocab_major):
    return weight.shape[0] if vocab_major else weight.shape[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_linear_xent(hidden, weight, labels, mask, num_chunks, vocab_major):
    loss, _ = _fwd(hidden, weight, labels, mask, num_chunks, vocab_major)
    return loss


def _pad_vocab(weight, num_chunks, vocab_major):
    """Pad the vocab axis to a multiple of the chunk size so
    dynamic_slice_in_dim never clamps the last chunk's start (a clamped slice
    would silently desynchronize the column-index masking and the dw
    scatter).  Padded columns are masked out by the ``cols < v`` guards."""
    v = _num_vocab(weight, vocab_major)
    chunk = -(-v // num_chunks)
    pad = num_chunks * chunk - v
    if pad:
        widths = ((0, pad), (0, 0)) if vocab_major else ((0, 0), (0, pad))
        weight = jnp.pad(weight, widths)
    return weight, v, chunk


def _softmax_stats(hidden, weight, labels, num_chunks, vocab_major, gather=False):
    """Online max ``m``, sum-exp ``l`` (relative to ``m``) and the label logit
    over the columns of ``weight``, a chunk at a time: three [N] fp32 vectors.
    ``labels`` index ``weight``'s own columns; one outside them (-1: the
    label lives in another shard's slice) leaves ``label_logit`` at 0."""
    n = hidden.shape[0]
    weight_p, v, chunk = _pad_vocab(weight, num_chunks, vocab_major)

    def body(c, carry):
        m, l, label_logit = carry
        logits, _ = _chunk_logits(hidden, weight_p, c, chunk, vocab_major, gather)
        cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(cols < v, logits, _MASK)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=1)
        idx = jnp.clip(labels - c * chunk, 0, chunk - 1)
        in_chunk = (labels >= c * chunk) & (labels < (c + 1) * chunk)
        ll = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        label_logit = jnp.where(in_chunk, ll, label_logit)
        return m_new, l, label_logit

    init = (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    return jax.lax.fori_loop(0, num_chunks, body, init)


def _chunk_grads(hidden, weight, labels, lse, coef, num_chunks, vocab_major, gather=False):
    """fp32 ``dh`` [N, H] (the sum over ``weight``'s columns only) and ``dw``
    (``weight``'s shape) from ``p - onehot`` a chunk at a time, ``p`` rebuilt
    from the global ``lse``.  ``labels`` as in :func:`_softmax_stats`."""
    weight_p, v, chunk = _pad_vocab(weight, num_chunks, vocab_major)

    def body(c, carry):
        dh, dw = carry
        logits, w_c = _chunk_logits(hidden, weight_p, c, chunk, vocab_major, gather)
        cols = c * chunk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        p = jnp.where(cols < v, jnp.exp(logits - lse[:, None]), 0.0)
        onehot = (cols == labels[:, None]).astype(jnp.float32)
        dlogits = ((p - onehot) * coef).astype(hidden.dtype)  # [N, chunk]
        if vocab_major:  # w_c [chunk, H]
            dh = dh + jax.lax.dot_general(
                dlogits, w_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            dw_c = jax.lax.dot_general(
                dlogits, hidden, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )  # [chunk, H]
            dw = jax.lax.dynamic_update_slice_in_dim(dw, dw_c, c * chunk, axis=0)
        else:  # w_c [H, chunk]
            dh = dh + jax.lax.dot_general(
                dlogits, w_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            dw_c = jax.lax.dot_general(
                hidden, dlogits, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )  # [H, chunk]
            dw = jax.lax.dynamic_update_slice_in_dim(dw, dw_c, c * chunk, axis=1)
        return dh, dw

    init = (
        jnp.zeros(hidden.shape, jnp.float32),
        jnp.zeros(weight_p.shape, jnp.float32),
    )
    dh, dw = jax.lax.fori_loop(0, num_chunks, body, init)
    if weight_p.shape != weight.shape:  # drop the padded vocab tail
        dw = dw[:v] if vocab_major else dw[:, :v]
    return dh, dw


def _per_vocab_shard(shard_fn, weight, vocab_major, weight_out=False):
    """``shard_fn(hidden, weight_slice, local_labels, *row_vectors)`` wrapped
    to run once per ``tp`` shard on that shard's slice of the vocabulary, or
    None where the bare call is the one to make: no mesh, no ``tp`` axis wider
    than one that the context still leaves to GSPMD (one chip, FSDP only, a
    region already manual over ``tp``), or a vocabulary ``tp`` does not divide.

    Only ``tp`` goes manual.  Rows stay split over the data-parallel axes and
    the head's other dim over FSDP's by GSPMD inside as outside: the gather of
    an FSDP-sharded head chunk and the reduction of ``dw`` over ``dp_shard``
    are the ones every parameter has.  ``local_labels`` are the labels as
    columns of the slice, -1 where the label is another shard's.  ``shard_fn``
    returns arrays it has already reduced over ``tp`` and, with
    ``weight_out``, last one shaped like the weight slice."""
    from jax.sharding import PartitionSpec as P

    from ..state import free_mesh_axes

    mesh, _, free = free_mesh_axes()
    tp = free.get("tp", 1)
    v = _num_vocab(weight, vocab_major)
    if tp == 1 or v % tp:
        return None
    w_spec = P("tp", None) if vocab_major else P(None, "tp")
    width = v // tp

    @jax.named_scope("vocab_shard")
    def shard(hidden, w, labels, *rows):
        local = labels - jax.lax.axis_index("tp") * width
        local = jnp.where((local >= 0) & (local < width), local, -1)
        return shard_fn(hidden, w, local, *rows)

    def run(hidden, weight, labels, *rows):
        # jit: a shard_map that leaves axes to GSPMD cannot run eagerly
        return jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(P(), w_spec) + (P(),) * (1 + len(rows)),
            out_specs=(P(), w_spec) if weight_out else P(),
            axis_names={"tp"}, check_vma=False,
        ))(hidden, weight, labels, *rows)

    return run


@jax.named_scope("fused_xent")
def _fwd(hidden, weight, labels, mask, num_chunks, vocab_major):
    def shard_stats(hidden, w, local):
        m, l, label_logit = _softmax_stats(hidden, w, local, num_chunks, vocab_major, True)
        m_all = jax.lax.pmax(m, "tp")
        return m_all, jax.lax.psum(l * jnp.exp(m - m_all), "tp"), jax.lax.psum(label_logit, "tp")

    stats = _per_vocab_shard(shard_stats, weight, vocab_major) or functools.partial(
        _softmax_stats, num_chunks=num_chunks, vocab_major=vocab_major)
    m, l, label_logit = stats(hidden, weight, labels)
    lse = m + jnp.log(jnp.where(l == 0, 1.0, l))
    n_valid = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    loss = jnp.sum((lse - label_logit) * mask) / n_valid
    return loss, (hidden, weight, labels, mask, lse, n_valid)


@jax.named_scope("fused_xent")
def _bwd(num_chunks, vocab_major, res, gbar):
    hidden, weight, labels, mask, lse, n_valid = res
    coef = (mask.astype(jnp.float32) * (gbar / n_valid))[:, None]  # [N, 1]

    def shard_grads(hidden, w, local, lse, coef):
        dh, dw = _chunk_grads(hidden, w, local, lse, coef, num_chunks, vocab_major, True)
        return jax.lax.psum(dh, "tp"), dw  # the one [N, H] that crosses tp, in fp32

    grads = _per_vocab_shard(shard_grads, weight, vocab_major, weight_out=True) or functools.partial(
        _chunk_grads, num_chunks=num_chunks, vocab_major=vocab_major)
    dh, dw = grads(hidden, weight, labels, lse, coef)
    return (
        dh.astype(hidden.dtype),
        dw.astype(weight.dtype),
        np.zeros(labels.shape, jax.dtypes.float0),
        np.zeros(mask.shape, jax.dtypes.float0),
    )


fused_linear_xent.defvjp(
    lambda h, w, lab, m, nc, vm: _fwd(h, w, lab, m, nc, vm),
    _bwd,
)


def fused_causal_lm_loss(hidden, weight, labels, *, vocab_major: bool,
                         num_chunks: int = 8, ignore_index: int = -100,
                         shifted: bool = False):
    """Shifted next-token CE from pre-head hidden states.

    hidden [B, T, H], weight [V, H] (``vocab_major``, e.g. a tied embedding
    table) or [H, V] (an lm_head kernel), labels [B, T].  ``shifted=True``:
    labels are already next-token aligned (the context-parallel contract —
    see models/llama.py:causal_lm_loss).
    """
    if shifted:
        h = hidden.reshape(-1, hidden.shape[-1])
        lab = labels.reshape(-1)
    else:
        h = hidden[:, :-1].reshape(-1, hidden.shape[-1])
        lab = labels[:, 1:].reshape(-1)
    mask = lab != ignore_index
    safe = jnp.where(mask, lab, 0)
    return fused_linear_xent(h, weight, safe, mask, num_chunks, vocab_major)
