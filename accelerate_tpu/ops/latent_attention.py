"""The absorbed decode walk over latent pages as one Pallas kernel
(``latent_decode``; ``models/joyai_flash.py``'s ``[S, 1]`` step is its caller).

A token's latent row ``[c ; kr ; zeros]`` is the key of every head, whole, and
in its first ``r`` values the value.  With ``q_h = [qa_h ; qr_h]`` the absorbed
query of head ``h`` of the slot's one token at position ``t``::

    score_h(s) = q_h . row_s * scale          for s <= t
    u_h        = sum_s softmax_s(score_h) row_s[:r]

The mathematics and the precision are those of
``ops/sparse_attention.paged_masked_attention(..., value_width=r)`` under the
causal mask (float32 scores from the pool's dtype, a running maximum and sum
in float32, ``p`` cast to the pool's dtype before it meets the rows, a float32
sum divided once at the end), which stays the oracle this kernel is tested
against.  What differs is what is read: that walk gathers EVERY slot's pages up
to the LONGEST live context into a ``[S, block, row]`` array, writes it and
reads it twice; here each slot reads ITS OWN pages ONCE, up to ITS OWN length.

Grid ``(slots,)``, walked in order.  The block table and the positions ride as
scalar-prefetch operands, the pool stays in HBM (``pl.ANY``) and the kernel
walks a slot's pages itself, ``_CHUNK_PAGES`` at a time: one copy a page (a
page is contiguous in the pool) into one of two VMEM buffers, the next chunk's
copies in flight while the current chunk is scored — and behind a slot's last
chunk the NEXT slot's first, so that only the first slot of a call waits for
copies it has just started (the buffer's parity is carried from slot to slot
in SMEM).  A page past the slot's last is neither copied nor stepped over; its
place in the last chunk's buffer is zeroed (whatever an earlier chunk left
there would meet ``p = 0``, and ``0 x NaN`` is not 0).  A slot that sees
nothing (``q_pos < 0``) copies nothing of its own and writes zeros.

Timed on a v5e at ``joyai-flash.serve_docs``'s shapes (48 slots, 4 heads, 5,915
pages of 64 x 640 bf16 a layer; PR 37): 0.704 ms a layer at 16 pages a chunk
(0.764 at 8, 0.721 at 32; 0.762 without the copies across slots) against the
XLA walk's 6.80.  The other scheme — ``BlockSpec``s that hand the pool in 16
times under 16 scalar-prefetched page ids, index maps held at each operand's
last page so that steps past a slot's length fetch nothing — took 1.40 ms (a
grid step for every chunk of the table, a copy of each page inside VMEM) and
is not in the package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu

# pages one compute step scores: 16 pages of 64 rows of 640 bf16 values are 1.25 MiB a buffer,
# two buffers (the timings are in the module's docstring)
_CHUNK_PAGES = 16


def _latent_decode_kernel(bt_ref, pos_ref, qa_ref, qr_ref, pool_ref, o_ref, q_scr, buf, sem,
                          par_ref, *, scale: float, pages_per_slot: int, chunk: int):
    """One slot: its pages ``0 .. q_pos // page`` in chunks of ``chunk`` under
    a running softmax kept in registers.  ``par_ref[0]``: which of the two
    buffers holds this slot's first chunk (started by the slot before it)."""
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    page, row = pool_ref.shape[1:]
    heads, r = qa_ref.shape[1:]
    dr = qr_ref.shape[2]
    rv = o_ref.shape[2]
    pos = pos_ref[slot]
    pages_of = lambda s: jnp.maximum(pos_ref[s] + page, 0) // page
    pages = pages_of(slot)
    chunks = (pages + chunk - 1) // chunk
    nxt = jnp.minimum(slot + 1, slots - 1)
    nxt_pages = jnp.where(slot + 1 < slots, pages_of(nxt), 0)

    def page_copy(s, c, b, j):
        page_id = bt_ref[s * pages_per_slot + c * chunk + j]
        return pltpu.make_async_copy(pool_ref.at[page_id], buf.at[b, pl.ds(j * page, page)],
                                     sem.at[b, j])

    def start(s, s_pages, c, b):
        for j in range(chunk):
            @pl.when(c * chunk + j < s_pages)
            def _():
                page_copy(s, c, b, j).start()

    def wait(c, b):
        for j in range(chunk):
            held = c * chunk + j < pages

            @pl.when(held)
            def _():
                page_copy(slot, c, b, j).wait()

            @pl.when(jnp.logical_not(held))
            def _():
                buf[b, pl.ds(j * page, page), :] = jnp.zeros((page, row), buf.dtype)

    @pl.when(slot == 0)
    def _():
        par_ref[0] = 0
        start(slot, pages, 0, 0)

    par = par_ref[0]

    @pl.when(chunks == 0)
    def _():
        start(nxt, nxt_pages, 0, par)

    q_scr[...] = jnp.zeros_like(q_scr)
    q_scr[:, :r] = qa_ref[0]
    q_scr[:, r:r + dr] = qr_ref[0]
    q = q_scr[...]

    def attend_chunk(c, carry):
        m, l, acc = carry
        b = (par + c) % 2
        last = c + 1 == chunks
        start(jnp.where(last, nxt, slot), jnp.where(last, nxt_pages, pages),
              jnp.where(last, 0, c + 1), 1 - b)
        wait(c, b)
        s = lax.dot_general(q, buf[b], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale     # [H, chunk * page]
        seen = c * (chunk * page) + lax.broadcasted_iota(jnp.int32, s.shape, 1) <= pos
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(buf.dtype), buf[b, :, :rv], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    init = (jnp.full((heads, 1), -jnp.inf, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, rv), jnp.float32))
    _, l, acc = lax.fori_loop(0, chunks, attend_chunk, init)
    par_ref[0] = (par + chunks) % 2
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def latent_decode_attention(qa, qr, latent_pages, block_tables, q_positions, *, scale: float):
    """One decode step's absorbed attention over paged latent rows.

    qa: ``[S, H, r]`` (``W_UK^T qn``: meets a row's first ``r`` values, which
    are also the value); qr: ``[S, H, dr]`` (rotary applied: meets the next
    ``dr``); latent_pages: ``[P, page, row]`` in the queries' dtype, ``row >=
    r + dr``, the rest of a row zeros; block_tables: ``[S, n]`` int32;
    q_positions: ``[S]`` int32, the token's position (keys ``0 .. position``
    are seen), -1 for a slot that sees nothing.  Returns ``u`` ``[S, H, r]``
    in ``qa``'s dtype; a slot that sees nothing comes back zero.

    Entries of a block table past ``position // page`` are never read, and
    neither are the pages they name."""
    return _latent_decode(qa, qr, latent_pages, block_tables, q_positions, scale=scale,
                          interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_decode(qa, qr, latent_pages, block_tables, q_positions, *, scale, interpret):
    """Under a ``jit`` of its own, so that a program of many layers traces and
    lowers the kernel once and not once a layer (a quarter of a second each)."""
    s_slots, heads, r = qa.shape
    dr = qr.shape[2]
    _, page, row = latent_pages.shape
    n = block_tables.shape[1]
    if r + dr > row:
        raise ValueError(f"a latent row of {row} values does not hold {r} + {dr}")
    rv = min(row, -(-r // 128) * 128)       # the sum runs over whole lane tiles, cut below
    chunk = min(_CHUNK_PAGES, n)
    mine = lambda s, bt, pos: (s, 0, 0)
    u = pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale, pages_per_slot=n, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s_slots,),
            in_specs=[pl.BlockSpec((1, heads, r), mine), pl.BlockSpec((1, heads, dr), mine),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, rv), mine),
            scratch_shapes=[pltpu.VMEM((heads, row), latent_pages.dtype),
                            pltpu.VMEM((2, chunk * page, row), latent_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, chunk)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s_slots, heads, rv), qa.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode",
    )(block_tables.reshape(-1).astype(jnp.int32), q_positions.astype(jnp.int32), qa, qr,
      latent_pages)
    return u if rv == r else u[..., :r]
