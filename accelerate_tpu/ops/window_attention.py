"""Sliding-window attention over a per-slot ring: what a window layer keeps of
a sequence is its last ``ring`` tokens, whatever the context's length.

A ring is ``[slots, ring, Hkv * D]`` (a token's heads in one row, as the page
pools of ``ops/paged_cache.py`` have them); the token at position ``p``
of the sequence in slot ``s`` lives in row ``p % ring`` of ``ring[s]``, so
after the token at ``t`` is written, row ``r`` holds position
``t - (t - r) % ring``.  A row is read only when that position lies in
``(t - window, t]`` and is not negative: every such position has been written
by the request that owns the slot NOW, so a slot is handed on without being
cleared (``ring >= window``).

Two paths, plain XLA, both banded — no score outside the band is computed:

- decode (:func:`ring_decode_attention`): one query a slot against its
  ring's ``ring`` rows;
- a prefill chunk (:func:`ring_chunk_attention`): the chunk's own fresh keys
  behind the ring's last ``window`` rows in position order, a block of
  ``window`` queries against its own and the previous block of keys.

Writes (:func:`ring_writer`) are in place and in the layout the reads use:
one row a slot (:func:`~.paged_cache.write_token_rows` with the slot as
the page) or the chunk's last ``ring`` tokens into one slot's ring
(:func:`write_chunk_ring`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .paged_cache import write_token_rows


def masked_attention(qg, k, v, visible, scale):
    """``qg`` [..., T, Hkv, g, D], ``k``/``v`` [..., S, Hkv, D], ``visible``
    [..., T, S] -> [..., T, Hkv, g, D]; float32 scores, a row that sees
    nothing comes back zero."""
    s = jnp.einsum("...thgd,...shd->...hgts", qg, k, preferred_element_type=jnp.float32) * scale
    sel = visible[..., None, None, :, :]
    m = jnp.max(jnp.where(sel, s, -jnp.inf), axis=-1, keepdims=True)
    p = jnp.where(sel, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("...hgts,...shd->...thgd", (p / jnp.where(l > 0, l, 1.0)).astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


@jax.named_scope("window_attend")
def ring_decode_attention(q, k_ring, v_ring, slots, q_positions, window: int):
    """``q`` [B, 1, H, D] at ``q_positions`` [B, 1] (-1: a dead slot) against
    the rings of ``slots`` [B], AFTER the step's own row was written.
    Returns [B, 1, H, D]."""
    b, _, h, d = q.shape
    ring = k_ring.shape[1]
    hkv = k_ring.shape[2] // d
    k = k_ring[slots].reshape(b, ring, hkv, d)
    v = v_ring[slots].reshape(b, ring, hkv, d)
    held = q_positions - (q_positions - jnp.arange(ring, dtype=jnp.int32)[None, :]) % ring   # [B, ring]
    visible = (held >= 0) & (held > q_positions - window) & (q_positions >= 0)
    out = masked_attention(q.reshape(b, 1, hkv, h // hkv, d), k, v, visible[:, None, :],
                           1.0 / np.sqrt(d))
    return out.reshape(b, 1, h, d).astype(q.dtype)


@jax.named_scope("window_attend")
def ring_chunk_attention(q, k, v, k_ring, v_ring, slot, q_positions, window: int):
    """One prefill chunk of one sequence: ``q`` [1, C, H, D], its fresh ``k``
    / ``v`` [1, C, Hkv, D] at contiguous ``q_positions`` [1, C] (-1 past the
    chunk's real length), and the ring of ``slot`` (scalar) as it stood
    BEFORE this chunk.  Returns [1, C, H, D]."""
    _, c, h, d = q.shape
    hkv = k.shape[2]
    ring = k_ring.shape[1]
    start = q_positions[0, 0]
    # the window's reach before the chunk, in position order: start - window .. start - 1
    before = start - window + jnp.arange(window, dtype=jnp.int32)
    take = lambda r: lax.dynamic_index_in_dim(r, slot, axis=0, keepdims=False)[before % ring]
    keys = jnp.concatenate([take(k_ring).reshape(window, hkv, d).astype(k.dtype), k[0]])
    vals = jnp.concatenate([take(v_ring).reshape(window, hkv, d).astype(v.dtype), v[0]])
    key_pos = jnp.concatenate([before, start + jnp.arange(c, dtype=jnp.int32)])
    bq = window if c % window == 0 else c          # a block of queries and the window behind it
    span = np.arange(c // bq)[:, None] * bq + np.arange(window + bq)[None, :]    # [blocks, window + bq]
    qp = q_positions[0].reshape(c // bq, bq)
    kp = key_pos[span]
    visible = (kp[:, None, :] <= qp[:, :, None]) & (kp[:, None, :] > qp[:, :, None] - window) \
        & (kp[:, None, :] >= 0)
    out = masked_attention(q[0].reshape(c // bq, bq, hkv, h // hkv, d), keys[span], vals[span],
                           visible, 1.0 / np.sqrt(d))
    return out.reshape(1, c, h, d).astype(q.dtype)


def ring_writer(slots, positions, live, ring: int):
    """``write(ring_rows, rows [B, T, W])`` of one paged call, for K and V
    alike (``paged_cache.page_writer``'s twin for slot-addressed state):
    a decode step ``[B, 1]`` writes one row a slot at ``position % ring``, a
    prefill chunk ``[1, C]`` the last ``ring`` of its first ``sum(live)`` rows."""
    if positions.shape[1] == 1:
        return lambda ring_rows, rows: write_token_rows(
            ring_rows, rows[:, 0], slots, positions[:, 0] % ring, live[:, 0])
    length = jnp.sum(live[0].astype(jnp.int32))
    return lambda ring_rows, rows: write_chunk_ring(
        ring_rows, rows[0], slots[0], positions[0, 0], length)


def write_chunk_ring(ring_rows, rows, slot, start, length):
    """Prefill: the last ``ring`` of the chunk's first ``length`` ``rows``
    [C, W] (the tokens at ``start`` ..) into ``ring_rows[slot]`` [ring, W],
    each at its position's row; the rows no token of the chunk lands on keep
    what they hold.  One gather of ``ring`` rows and one ``[1, ring, W]``
    update of the donated array."""
    _, ring, width = ring_rows.shape
    last = start + length - 1
    lands = last - (last - jnp.arange(ring, dtype=jnp.int32)) % ring        # newest position on each row
    at = (slot, 0, 0)
    old = lax.dynamic_slice(ring_rows, at, (1, ring, width))
    new = rows.astype(ring_rows.dtype)[jnp.clip(lands - start, 0, rows.shape[0] - 1)]
    fresh = (lands >= start) & (length > 0)
    return lax.dynamic_update_slice(ring_rows, jnp.where(fresh[None, :, None], new[None], old), at)
