"""``ops/page_walk.paged_walk_decode_attention`` (the ``paged_walk_decode``
Pallas kernel, interpret mode here) against the XLA walk it takes the place of
in ``models/k_exaone.py``'s decode step:
``ops/page_walk.paged_causal_attention`` on the same two pools.

As in ``test_latent_decode.py`` every case scatters its pages over the pools
and points every block-table entry past a slot's last page at page 0, which
holds NaN in both: the kernel must come back finite (a page past a slot's
length is never read), and the oracle, which gathers whole blocks of the
table, runs on copies of the pools with page 0 zeroed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import page_walk as pw
from accelerate_tpu.ops.page_walk import chunk_pages, paged_walk_decode_attention

TINY = dict(heads=4, kv_heads=2, d=32, page=8)         # models/k_exaone.KExaoneConfig.tiny's widths
GROUPED = dict(heads=16, kv_heads=2, d=32, page=8)     # eight query heads a KV head
ONE_KV = dict(heads=2, kv_heads=1, d=32, page=8)
CELL = dict(heads=8, kv_heads=1, d=128, page=64)       # k-exaone.serve_reason's share
UNCUT = dict(heads=16, kv_heads=2, d=128, page=64)     # the published 8 heads a KV head, two of its KV heads
CHUNK = 8 * chunk_pages(8 * 2 * 32 * 4, 40)            # rows of a chunk at TINY's widths (float32, the larger page)
# name: (widths, pages a slot, positions; -1 = a slot that sees nothing)
CASES = {
    "ragged": (TINY, 40, (5, 130, 77, 319, 200)),
    "dead_slots": (TINY, 40, (20, -1, 300, -1)),
    "all_dead": (TINY, 8, (-1, -1)),
    # a context that ends on a page's last row, on a page's first row, on a chunk's last row
    # and on a chunk's first row; and one of a single key
    "page_edges": (TINY, 40, (23, 24, CHUNK - 1, CHUNK, 0)),
    "one_key": (TINY, 8, (0, 0, 0)),
    "four_chunks": (TINY, 100, (799, 5, 3 * CHUNK - 1, 2 * CHUNK)),      # 100 pages: 32, 32, 32 and 4
    "full_beside_one_page": (TINY, 40, (319, 3, 0, 7)),
    "eight_heads_a_kv_head": (GROUPED, 40, (5, 130, -1, 319)),
    "one_kv_head": (ONE_KV, 40, (200, 64, CHUNK, -1, 7)),
    "rehearsal": (TINY, 8, (11, 63, -1, 30)),               # 4 slots of 8 pages of 8
    "cell_widths": (CELL, 20, (1279, 64, -1, 700)),
    "published_group": (UNCUT, 6, (383, -1, 63, 130)),
}


def scattered(widths, pages_per_slot, positions, dtype, seed):
    """``(q, k_pool, v_pool, tables, positions)``: each slot's pages drawn
    without order from pages 1.., entries past its last page 0, page 0 NaN."""
    h, hkv, d, page = (widths[k] for k in ("heads", "kv_heads", "d", "page"))
    pos = np.asarray(positions, np.int32)
    used = (pos + page) // page
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, 1 + int(used.sum()) + 5))
    tables = np.zeros((len(pos), pages_per_slot), np.int32)
    for s, (u, at) in enumerate(zip(used, np.cumsum(used) - used)):
        tables[s, :u] = ids[at:at + u]
    k = jax.random.split(jax.random.key(seed), 3)
    pool = lambda key: jax.random.normal(key, (len(ids) + 1, page, hkv * d),
                                         jnp.float32).at[0].set(jnp.nan).astype(dtype)
    q = jax.random.normal(k[2], (len(pos), h, d), jnp.float32).astype(dtype)
    return q, pool(k[0]), pool(k[1]), jnp.asarray(tables), jnp.asarray(pos)


def xla_walk(q, k_pool, v_pool, tables, pos):
    s, h, _ = q.shape
    padded = pw.pad_block_tables(tables, pw.block_pages_for(s, 1, h, k_pool.shape[1]))
    return pw.paged_causal_attention(q[:, None], k_pool.at[0].set(0.0), v_pool.at[0].set(0.0),
                                     padded, pos[:, None], jnp.max(pos) + 1)[:, 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_matches_the_xla_walk_and_reads_no_page_past_a_slots_length(case, dtype):
    widths, pages_per_slot, positions = CASES[case]
    q, k_pool, v_pool, tables, pos = scattered(widths, pages_per_slot, positions, dtype, seed=5)
    got = paged_walk_decode_attention(q, k_pool, v_pool, tables, pos)
    assert got.shape == q.shape and got.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(got)))                    # page 0 was never read
    dead = np.asarray(positions) < 0
    assert not np.asarray(got, np.float32)[dead].any()         # a dead slot: zeros
    want = xla_walk(q, k_pool, v_pool, tables, pos)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    # bf16: no further from the float32 walk over the same values than the bf16 XLA walk is
    # (blocks of other sizes round in other places, hence the half again)
    up = lambda x: x.astype(jnp.float32)
    exact = xla_walk(up(q), up(k_pool), up(v_pool), tables, pos)
    walk_gap = float(jnp.max(jnp.abs(up(want) - exact)))
    assert float(jnp.max(jnp.abs(up(got) - exact))) <= 1.5 * walk_gap + 1e-6
