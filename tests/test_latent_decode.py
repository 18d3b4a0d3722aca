"""``ops/page_walk.latent_decode_attention`` (the ``latent_decode``
Pallas kernel, interpret mode here) against the XLA walk it takes the place of
in ``models/joyai_flash.py``'s decode step:
``ops/page_walk.paged_masked_attention(..., value_width=r)`` under the
causal mask, on the same pool.

Every case scatters its pages over the pool and points every block-table entry
past a slot's last page at page 0, which holds NaN: the kernel must come back
finite (a page past a slot's length is never read), and the oracle, which
gathers whole blocks of the table, runs on a copy of the pool with page 0
zeroed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import page_walk as pw
from accelerate_tpu.ops.page_walk import _CHUNK_PAGES, latent_decode_attention

TINY = dict(heads=2, r=32, dr=8, row=128, page=8)          # the CPU rehearsal's widths
PUBLISHED = dict(heads=4, r=512, dr=64, row=640, page=64)  # the cell's
# name: (widths, pages a slot, positions; -1 = a slot that sees nothing)
CASES = {
    "ragged": (TINY, 40, (5, 130, 77, 319, 200)),
    "dead_slots": (TINY, 40, (20, -1, 300, -1)),
    "all_dead": (TINY, 8, (-1, -1)),
    # a context that ends on a page's last row, on a page's first row, on a chunk's last row
    # and on a chunk's first row; and one of a single key
    "page_edges": (TINY, 40, (23, 24, 8 * _CHUNK_PAGES - 1, 8 * _CHUNK_PAGES, 0)),
    "full_beside_one_page": (TINY, 40, (319, 3, 0, 7)),
    "rehearsal": (TINY, 8, (11, 63, -1, 30)),               # 4 slots of 8 pages of 8
    "published_widths": (PUBLISHED, 20, (1279, 64, -1, 700)),
}


def scattered(widths, pages_per_slot, positions, dtype, seed):
    """``(qa, qr, pool, tables, positions)``: each slot's pages drawn without
    order from pages 1.., entries past its last page 0, page 0 NaN."""
    h, r, dr, row, page = (widths[k] for k in ("heads", "r", "dr", "row", "page"))
    pos = np.asarray(positions, np.int32)
    used = (pos + page) // page
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, 1 + int(used.sum()) + 5))
    tables = np.zeros((len(pos), pages_per_slot), np.int32)
    for s, (u, at) in enumerate(zip(used, np.cumsum(used) - used)):
        tables[s, :u] = ids[at:at + u]
    k = jax.random.split(jax.random.key(seed), 3)
    pool = jax.random.normal(k[0], (len(ids) + 1, page, row), jnp.float32)
    pool = pool.at[..., r + dr:].set(0.0).at[0].set(jnp.nan).astype(dtype)
    qa = jax.random.normal(k[1], (len(pos), h, r), jnp.float32).astype(dtype)
    qr = jax.random.normal(k[2], (len(pos), h, dr), jnp.float32).astype(dtype)
    return qa, qr, pool, jnp.asarray(tables), jnp.asarray(pos)


def xla_walk(qa, qr, pool, tables, pos, scale):
    s, h, r = qa.shape
    page, row = pool.shape[1:]
    q_abs = jnp.concatenate([qa, qr, jnp.zeros((s, h, row - r - qr.shape[2]), qa.dtype)], -1)
    padded = pw.pad_block_tables(tables, pw.block_pages_for(s, 1, h, page))
    return pw.paged_masked_attention(
        q_abs[:, None], pool.at[0].set(0.0), None, padded, jnp.max(pos) + 1,
        pw.causal_mask(pos[:, None]), scale=scale, value_width=r)[:, 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_matches_the_xla_walk_and_reads_no_page_past_a_slots_length(case, dtype):
    widths, pages_per_slot, positions = CASES[case]
    qa, qr, pool, tables, pos = scattered(widths, pages_per_slot, positions, dtype, seed=3)
    scale = 1.0 / np.sqrt(widths["r"] // 4 + widths["dr"])     # the cell's 1 / sqrt(128 + 64) at its widths
    got = latent_decode_attention(qa, qr, pool, tables, pos, scale=scale)
    assert got.shape == qa.shape and got.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(got)))                    # page 0 was never read
    dead = np.asarray(positions) < 0
    assert not np.asarray(got, np.float32)[dead].any()         # a dead slot: zeros
    want = xla_walk(qa, qr, pool, tables, pos, scale)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    # bf16: no further from the float32 walk over the same values than the bf16 XLA walk is
    # (blocks of other sizes round in other places, hence the half again)
    up = lambda x: x.astype(jnp.float32)
    exact = xla_walk(up(qa), up(qr), up(pool), tables, pos, scale)
    walk_gap = float(jnp.max(jnp.abs(up(want) - exact)))
    assert float(jnp.max(jnp.abs(up(got) - exact))) <= 1.5 * walk_gap + 1e-6
