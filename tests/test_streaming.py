"""ops/streaming.py — the double-buffered host↔device streaming pipeline.

The accelerator's chunked host update and generate_streamed's layer
prefetcher are both built from these pieces; their end-to-end parity lives
in tests/test_offload.py and tests/test_generation.py.  Here the machinery
itself is pinned: chunk partitioning (a numerics contract — SR hash streams
key on group-relative leaf indices), congruent slice/merge round-trips,
prefetcher ordering/accounting, and the overlap arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.streaming import (
    HOST_BYTES_PER_PARAM,
    LayerPrefetcher,
    StreamStats,
    chunk_groups,
    merge_congruent,
    offload_transfer_accounting,
    predicted_overlap,
    slice_congruent,
    stage_put,
    tree_bytes,
)


def _params():
    return {
        "a": {"kernel": jnp.arange(12.0).reshape(3, 4), "bias": jnp.zeros((4,))},
        "b": {"kernel": jnp.ones((4, 2)), "bias": jnp.full((2,), 3.0)},
    }


def test_tree_bytes_concrete_and_abstract():
    p = _params()
    want = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(p))
    assert tree_bytes(p) == want
    abstract = jax.eval_shape(lambda: p)
    assert tree_bytes(abstract) == want


def test_chunk_groups_partition_and_bounds():
    p = _params()
    leaves = jax.tree_util.tree_leaves(p)
    # one leaf per group at a tiny budget
    groups = chunk_groups(p, 1)
    assert groups == [[i] for i in range(len(leaves))]
    # everything in one group at a huge budget
    assert chunk_groups(p, 1 << 40) == [list(range(len(leaves)))]
    # arbitrary budget: a contiguous exact partition, each group under
    # budget unless it is a single oversized leaf
    budget = 40
    groups = chunk_groups(p, budget)
    assert sorted(i for g in groups for i in g) == list(range(len(leaves)))
    for g in groups:
        size = sum(int(np.prod(leaves[i].shape)) * 4 for i in g)
        assert size <= budget or len(g) == 1


def test_slice_merge_congruent_roundtrip_with_scalar_state():
    p = _params()
    treedef = jax.tree_util.tree_structure(p)
    # adam-shaped state: congruent moment tree + a shared scalar count
    state = {"mu": jax.tree_util.tree_map(lambda x: x * 2, p), "count": jnp.int32(7)}
    groups = chunk_groups(p, 1)
    outs = []
    for idxs in groups:
        sl = slice_congruent(state, treedef, idxs)
        assert isinstance(sl["mu"], tuple) and len(sl["mu"]) == len(idxs)
        assert sl["count"].shape == ()  # scalar passes whole
        outs.append(sl)
    merged = merge_congruent(state, outs, treedef, groups)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), merged, state
    )


def test_stage_put_identity_and_placement():
    p = _params()
    # None shardings pass through untouched
    none_sh = jax.tree_util.tree_map(lambda _: None, p)
    out = stage_put(p, none_sh)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(a, b), out, p)
    # real shardings place without changing values (the bitwise contract the
    # accelerator's stage A/C lean on)
    sh = jax.tree_util.tree_map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), p
    )
    placed = stage_put(p, sh)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(a, b), placed, p)
    assert all(
        leaf.sharding == jax.sharding.SingleDeviceSharding(jax.devices()[0])
        for leaf in jax.tree_util.tree_leaves(placed)
    )


class _CountingFetch:
    def __init__(self, n):
        self.layers = [{"w": jnp.full((4,), float(i))} for i in range(n)]
        self.calls: list[int] = []

    def __call__(self, i):
        self.calls.append(i)
        return self.layers[i]


def test_layer_prefetcher_values_and_single_fetch_per_layer():
    fetch = _CountingFetch(4)
    stats = StreamStats()
    pf = LayerPrefetcher(fetch, 4, stats=stats)
    for i in range(4):
        out = pf.get(i)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.full((4,), float(i)))
    # one fetch per layer — layers 1..3 were issued as prefetches
    assert sorted(fetch.calls) == [0, 1, 2, 3]
    assert stats.fetches == 4 and stats.prefetch_hits == 3
    assert stats.h2d_bytes == 4 * 4 * 4  # 4 layers x 4 floats


def test_layer_prefetcher_dispatch_order():
    fetch = _CountingFetch(3)
    pf = LayerPrefetcher(fetch, 3)
    pf.get(0)
    # cold miss: the layer needed NOW is dispatched first (queueing the
    # lookahead ahead of it would delay time-to-first-token), then layer
    # 1's upload is in flight before get(0) returns (the double buffer)
    assert fetch.calls == [0, 1]
    pf.get(1)
    # hit: only the lookahead (layer 2) is newly dispatched
    assert fetch.calls == [0, 1, 2]


def test_layer_prefetcher_depth0_explicit_prefetch():
    """depth=0 disables the sequential lookahead; the caller drives the
    double buffer through prefetch() — the adapter hot-swap contract
    (serving/adapters.py), where "next" is a scheduler decision, not i+1."""
    fetch = _CountingFetch(4)
    stats = StreamStats()
    pf = LayerPrefetcher(fetch, 4, depth=0, stats=stats)
    pf.get(0)
    assert fetch.calls == [0]          # no i+1 lookahead at depth 0
    assert pf.prefetch(2)              # explicit, non-blocking dispatch
    assert not pf.prefetch(2)          # already in flight: no re-issue
    out = pf.get(2)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full((4,), 2.0))
    assert fetch.calls == [0, 2]       # the get() consumed the staged slot
    assert stats.prefetch_hits == 1
    with pytest.raises(IndexError):
        pf.prefetch(9)
    with pytest.raises(ValueError):
        LayerPrefetcher(fetch, 4, depth=-1)


def test_layer_prefetcher_wrap_prefetches_layer0_for_next_pass():
    fetch = _CountingFetch(3)
    pf = LayerPrefetcher(fetch, 3, wrap=True)
    hits = 0
    for _ in range(2):  # two decode passes
        for i in range(3):
            before = len(fetch.calls)
            pf.get(i)
            # after the cold start, every get is a hit: the previous get
            # (incl. the wrap at the pass boundary) already issued it
            hits += fetch.calls[before:].count(i) == 0
    # 6 gets = 1 cold miss + 6 prefetch issues (one per get; the last is
    # layer 0 in flight for a third pass that never runs)
    assert len(fetch.calls) == 7
    assert hits == 5  # all but the cold first layer


def test_layer_prefetcher_depth_2():
    fetch = _CountingFetch(5)
    stats = StreamStats()
    pf = LayerPrefetcher(fetch, 5, depth=2, stats=stats)
    for i in range(5):
        pf.get(i)
    assert sorted(fetch.calls) == list(range(5))
    assert stats.prefetch_hits == 4  # all but layer 0


def test_layer_prefetcher_disabled_is_serial():
    fetch = _CountingFetch(3)
    stats = StreamStats()
    pf = LayerPrefetcher(fetch, 3, enabled=False, stats=stats)
    for i in range(3):
        pf.get(i)
    assert fetch.calls == [0, 1, 2]  # strict order, no lookahead
    assert stats.prefetch_hits == 0 and stats.fetches == 3


def test_layer_prefetcher_bounds():
    pf = LayerPrefetcher(_CountingFetch(2), 2)
    with pytest.raises(IndexError):
        pf.get(2)
    with pytest.raises(ValueError):
        LayerPrefetcher(_CountingFetch(1), 0)


def test_stream_stats_overlap_report():
    s = StreamStats(h2d_bytes=100, d2h_bytes=50, fetches=4, prefetch_hits=3,
                    fetch_wait_s=0.2, wall_s=2.0)
    rep = s.overlap_report(serial_transfer_s=1.0)
    assert rep["h2d_bytes"] == 100 and rep["d2h_bytes"] == 50
    assert rep["stall_frac"] == pytest.approx(0.1)
    assert rep["overlap_frac"] == pytest.approx(0.8)
    # no baseline -> no overlap_frac claim (honest accounting)
    assert "overlap_frac" not in s.overlap_report()


def test_predicted_overlap_regimes():
    assert predicted_overlap(1.0, 10.0) == 1.0   # host-bound: all hideable
    assert predicted_overlap(10.0, 1.0) == pytest.approx(0.1)
    assert predicted_overlap(0.0, 1.0) == 1.0


def test_offload_transfer_accounting_7b_shape():
    n = 7_000_000_000
    rep = offload_transfer_accounting(n, optimizer="lion-sr",
                                      grad_bytes_per_param=2)
    assert rep["d2h_bytes"] == 2 * n and rep["h2d_bytes"] == 2 * n
    assert rep["host_update_bytes"] == int(HOST_BYTES_PER_PARAM["lion-sr"] * n)
    # the 7B regime is host-DRAM-bound: the whole transfer hides
    assert rep["overlap_frac"] == 1.0 and rep["kind"] == "predicted"
    resident = offload_transfer_accounting(n, optimizer="lion-sr",
                                           offload_params=False)
    assert resident["h2d_bytes"] == 0
