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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_MASK = -0.7 * float(np.finfo(np.float32).max)


def _chunk_logits(hidden, weight, c, chunk, vocab_major: bool):
    """Logits for vocab chunk ``c``: [N, chunk] fp32 (bf16 operands, fp32
    accumulation), with out-of-vocab columns masked."""
    if vocab_major:  # weight [V, H]
        w_c = jax.lax.dynamic_slice_in_dim(weight, c * chunk, chunk, axis=0)
        logits = jax.lax.dot_general(
            hidden, w_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # weight [H, V]
        w_c = jax.lax.dynamic_slice_in_dim(weight, c * chunk, chunk, axis=1)
        logits = jax.lax.dot_general(
            hidden, w_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
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


@jax.named_scope("fused_xent")
def _fwd(hidden, weight, labels, mask, num_chunks, vocab_major):
    n = hidden.shape[0]
    weight_p, v, chunk = _pad_vocab(weight, num_chunks, vocab_major)

    def body(c, carry):
        m, l, label_logit = carry
        logits, _ = _chunk_logits(hidden, weight_p, c, chunk, vocab_major)
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
    m, l, label_logit = jax.lax.fori_loop(0, num_chunks, body, init)
    lse = m + jnp.log(jnp.where(l == 0, 1.0, l))
    n_valid = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    loss = jnp.sum((lse - label_logit) * mask) / n_valid
    return loss, (hidden, weight, labels, mask, lse, n_valid)


@jax.named_scope("fused_xent")
def _bwd(num_chunks, vocab_major, res, gbar):
    hidden, weight, labels, mask, lse, n_valid = res
    weight_p, v, chunk = _pad_vocab(weight, num_chunks, vocab_major)
    coef = (mask.astype(jnp.float32) * (gbar / n_valid))[:, None]  # [N, 1]

    def body(c, carry):
        dh, dw = carry
        logits, w_c = _chunk_logits(hidden, weight_p, c, chunk, vocab_major)
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
