"""``ops/paged_cache.py`` tested by name: the structure, the allocator's full
cycle, both page writes against NumPy scatters, and each layout read back.
Arrays of a few hundred elements; nothing here compiles a model."""

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.ops import paged_cache as pc

PAGE = 4


def test_pools_around_mixed_layers_and_one_allocator_cycle():
    """A layer dict that mixes paged and slot-addressed arrays rides through
    untouched, and allocate / push_pages / release (jitted, as the engine runs
    them) account for every page."""
    pages, slots, per_slot = 10, 3, 4
    layers = [{"k_pages": jnp.zeros((pages, PAGE, 8)), "v_pages": jnp.zeros((pages, PAGE, 8))},
              {"ring": jnp.zeros((slots, 6, 8)), "state": jnp.zeros((slots, 2, 2))}]
    cache = pc.init_paged_pools(layers, pages, slots, per_slot, tick_counters=jnp.zeros((2,), jnp.int32))
    assert cache["layers"] is layers and cache["tick_counters"].shape == (2,)
    assert cache["block_tables"].shape == (slots, per_slot) and cache["seq_lens"].shape == (slots,)
    assert sorted(np.asarray(cache["free_stack"])) == list(range(pages)) and int(cache["free_top"]) == pages

    bt, stack, top, lens = (cache["block_tables"], cache["free_stack"], cache["free_top"],
                            cache["seq_lens"])
    # slot 0 takes 3 pages, slot 2 takes 2; the lane that needs nothing takes nothing
    lanes = jnp.asarray([0, 0, 0, 1, 2, 2])
    logical = jnp.asarray([0, 1, 2, 0, 0, 1])
    need = jnp.asarray([True, True, True, False, True, True])
    bt, top = jax.jit(pc.allocate)(bt, stack, top, lanes, logical, need)
    assert int(top) == pages - 5
    table = np.asarray(bt)
    held = table[0, :3].tolist() + table[2, :2].tolist()
    assert len(set(held)) == 5                                       # five distinct pages
    assert set(held) == {int(p) for p in np.asarray(stack)[pages - 5:]}    # popped off the top
    lens = jnp.asarray([3 * PAGE - 1, 0, PAGE + 1], jnp.int32)      # pages_for: 3 and 2
    assert [pc.pages_for(n, PAGE) for n in (0, 1, PAGE, PAGE + 1)] == [0, 1, 1, 2]

    # a rollback hands slot 0's last page back by itself (the speculative verify's shape)
    stack, top = jax.jit(pc.push_pages)(stack, top, bt[0, 2:3], jnp.asarray([True]))
    lens = jnp.asarray([2 * PAGE, 0, PAGE + 1], jnp.int32)
    assert int(top) == pages - 4
    # then both slots are released: every page is free again, each exactly once
    lens, stack, top = jax.jit(pc.release, static_argnums=5)(
        bt, lens, stack, top, jnp.asarray([True, False, True]), PAGE)
    assert int(top) == pages and np.asarray(lens).tolist() == [0, 0, 0]
    assert sorted(np.asarray(stack)[:pages].tolist()) == list(range(pages))


def test_row_writer_of_a_decode_step_drops_dead_lanes():
    """``page_writer`` + ``write_token_rows`` against a NumPy scatter: a dead
    slot's row must not land, though its block table names a live page."""
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(6, PAGE, 5)).astype(np.float32)
    tables = np.array([[1, 4], [2, 5], [1, 3]], np.int32)         # slot 2 is dead and names page 1
    positions = np.array([[5], [2], [1]], np.int32)
    live = np.array([[True], [True], [False]])
    rows = rng.normal(size=(3, 1, 5)).astype(np.float32)
    want = pool.copy()
    for b in range(3):
        if live[b, 0]:
            p = positions[b, 0]
            want[tables[b, p // PAGE], p % PAGE] = rows[b, 0]
    write = pc.page_writer(jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(live), PAGE)
    np.testing.assert_array_equal(np.asarray(write(jnp.asarray(pool), jnp.asarray(rows))), want)
    # the same rows through the row writer by name
    got = pc.write_token_rows(jnp.asarray(pool), jnp.asarray(rows[:, 0]),
                              jnp.asarray([4, 2, 1]), jnp.asarray([1, 2, 1]), jnp.asarray(live[:, 0]))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_chunk_writer_crosses_a_page_boundary_and_ends_inside_a_page():
    """``write_chunk_pages``: 6 of a bucket's 8 rows from token 4 on fill one
    page and half of the next; what lies behind the chunk's end is kept."""
    rng = np.random.default_rng(1)
    pool = rng.normal(size=(7, PAGE, 3)).astype(np.float32)
    page_row = np.array([6, 2, 5, 0], np.int32)
    rows = rng.normal(size=(8, 3)).astype(np.float32)
    start, length = PAGE, 6
    want = pool.copy()
    for t in range(length):
        pos = start + t
        want[page_row[pos // PAGE], pos % PAGE] = rows[t]
    got = pc.write_chunk_pages(jnp.asarray(pool), jnp.asarray(rows), jnp.asarray(page_row),
                               jnp.asarray(start), jnp.asarray(length))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert not np.array_equal(want[5, :2], pool[5, :2]) and np.array_equal(want[5, 2:], pool[5, 2:])
    # through page_writer: a prefill chunk [1, C] whose live rows are its first six
    live = (np.arange(8) < length)[None]
    positions = (start + np.arange(8, dtype=np.int32))[None]
    write = pc.page_writer(jnp.asarray(page_row[None]), jnp.asarray(positions), jnp.asarray(live), PAGE)
    np.testing.assert_array_equal(np.asarray(write(jnp.asarray(pool), jnp.asarray(rows[None]))), want)


def test_head_major_write_read_back_through_the_gather():
    """``paged_write_kv`` into ``[Hkv, P, page, D]`` pools, read back by
    ``paged_gather_kv``: every token lies where a NumPy reference puts it, and
    a lane with an out-of-bounds page id is dropped."""
    rng = np.random.default_rng(2)
    hkv, pages, d = 2, 6, 3
    tables = np.array([[3, 1], [5, 0]], np.int32)
    k = rng.normal(size=(2, 3, hkv, d)).astype(np.float32)
    v = rng.normal(size=(2, 3, hkv, d)).astype(np.float32)
    positions = np.array([[2, 3, 4], [0, 1, 2]], np.int32)       # row 0 crosses into its 2nd page
    keep = np.array([[True, True, True], [True, True, False]])
    page_ids = np.where(keep, np.take_along_axis(tables, positions // PAGE, axis=1), pages)
    zeros = jnp.zeros((hkv, pages, PAGE, d), jnp.float32)
    k_pages = pc.paged_write_kv(zeros, jnp.asarray(k), jnp.asarray(page_ids), jnp.asarray(positions % PAGE))
    v_pages = pc.paged_write_kv(zeros, jnp.asarray(v), jnp.asarray(page_ids), jnp.asarray(positions % PAGE))
    k_lin, v_lin, kv_pos = pc.paged_gather_kv(k_pages, v_pages, jnp.asarray(tables))
    want_k = np.zeros((2, 2 * PAGE, hkv, d), np.float32)
    want_v = np.zeros_like(want_k)
    for b in range(2):
        for t in range(3):
            if keep[b, t]:
                want_k[b, positions[b, t]] = k[b, t]
                want_v[b, positions[b, t]] = v[b, t]
    np.testing.assert_array_equal(np.asarray(k_lin), want_k)
    np.testing.assert_array_equal(np.asarray(v_lin), want_v)
    np.testing.assert_array_equal(np.asarray(kv_pos), np.broadcast_to(np.arange(2 * PAGE), (2, 2 * PAGE)))


def test_row_layout_write_read_back_through_the_block_table():
    """A prefill chunk then a decode step into a ``[P, page, Hkv * D]`` pool;
    indexing the pool through the block table gives the sequence's rows."""
    rng = np.random.default_rng(3)
    pool = jnp.zeros((5, PAGE, 6), jnp.float32)
    tables = jnp.asarray([[4, 2, 0]], jnp.int32)
    chunk = rng.normal(size=(1, 8, 6)).astype(np.float32)
    live = (np.arange(8) < 7)[None]                               # seven prompt tokens
    pool = pc.page_writer(tables, jnp.arange(8, dtype=jnp.int32)[None], jnp.asarray(live), PAGE)(
        pool, jnp.asarray(chunk))
    row = rng.normal(size=(1, 1, 6)).astype(np.float32)           # the eighth token, decoded
    pool = pc.page_writer(tables, jnp.asarray([[7]], jnp.int32), jnp.asarray([[True]]), PAGE)(
        pool, jnp.asarray(row))
    linear = np.asarray(pool)[np.asarray(tables)[0]].reshape(3 * PAGE, 6)
    np.testing.assert_array_equal(linear[:7], chunk[0, :7])
    np.testing.assert_array_equal(linear[7], row[0, 0])
    assert not linear[8:].any()
