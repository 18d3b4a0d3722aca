"""Collectives conformance tests (mirror of reference
test_utils/scripts/test_ops.py + tests/test_utils.py operations coverage)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu.ops import operations as ops
from accelerate_tpu.parallel import collectives


def test_recursively_apply_nested():
    data = {"a": np.ones(2), "b": [np.zeros(3), (np.ones(1), "str")]}
    out = ops.recursively_apply(lambda t: t + 1, data)
    assert out["a"].tolist() == [2.0, 2.0]
    assert out["b"][0].tolist() == [1.0, 1.0, 1.0]
    assert out["b"][1][1] == "str"


def test_recursively_apply_namedtuple():
    from collections import namedtuple

    Point = namedtuple("Point", ["x", "y"])
    p = Point(np.ones(2), np.zeros(2))
    out = ops.recursively_apply(lambda t: t * 2, p)
    assert isinstance(out, Point)
    assert out.x.tolist() == [2.0, 2.0]


def test_recursively_apply_error_on_other():
    with pytest.raises(TypeError):
        ops.recursively_apply(lambda t: t, {"a": "str"}, error_on_other_type=True)


def test_send_to_device():
    batch = {"x": np.ones((2, 2)), "y": [np.zeros(3)]}
    out = ops.send_to_device(batch, jax.devices()[0])
    assert isinstance(out["x"], jax.Array)
    assert out["x"].devices() == {jax.devices()[0]}


def test_send_to_device_skip_keys():
    batch = {"x": np.ones(2), "meta": np.zeros(2)}
    out = ops.send_to_device(batch, jax.devices()[0], skip_keys=["meta"])
    assert isinstance(out["x"], jax.Array)
    assert isinstance(out["meta"], np.ndarray)


def test_get_data_structure_and_initialize():
    data = {"x": np.ones((2, 3), dtype=np.float32)}
    skel = ops.get_data_structure(data)
    assert skel["x"].shape == (2, 3)
    out = ops.initialize_tensors(skel)
    assert out["x"].shape == (2, 3)
    assert (out["x"] == 0).all()


def test_find_batch_size():
    assert ops.find_batch_size({"a": np.ones((5, 2))}) == 5
    assert ops.find_batch_size([np.ones((3,))]) == 3
    assert ops.find_batch_size({"a": 1}) is None


def test_slice_and_concat():
    data = {"a": np.arange(10)}
    sliced = ops.slice_tensors(data, slice(0, 4))
    assert sliced["a"].tolist() == [0, 1, 2, 3]
    merged = ops.concatenate([sliced, sliced])
    assert merged["a"].shape == (8,)


def test_convert_to_fp32():
    data = {"a": jnp.ones(2, dtype=jnp.bfloat16), "b": np.ones(2, dtype=np.int32)}
    out = ops.convert_to_fp32(data)
    assert out["a"].dtype == jnp.float32
    assert out["b"].dtype == np.int32  # non-float untouched


def test_gather_single_process():
    x = np.ones((4, 2))
    assert ops.gather(x) is x


def test_gather_object_single_process():
    assert ops.gather_object([1, 2]) == [1, 2]
    assert ops.gather_object("a") == ["a"]


def test_broadcast_single_process():
    x = np.ones(3)
    assert ops.broadcast(x) is x


def test_reduce_single_process():
    out = ops.reduce({"a": np.ones(2)}, reduction="sum")
    assert out["a"].tolist() == [1.0, 1.0]


def test_pad_input_tensors():
    out = ops.pad_input_tensors(np.arange(10).reshape(10, 1), batch_size=10, num_processes=4)
    assert out.shape == (12, 1)
    # duplicated head samples
    assert out[10, 0] == 0 and out[11, 0] == 0


def test_listify():
    assert ops.listify({"a": np.arange(3)}) == {"a": [0, 1, 2]}


# ---------------------------------------------------------------------------
# In-jit collectives over the 8-device mesh (shard_map plane)
# ---------------------------------------------------------------------------


def test_psum_over_mesh(mesh8):
    from jax import shard_map

    x = jnp.arange(8.0)

    def body(x):
        return collectives.psum(x, "dp_shard")

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard"), out_specs=P("dp_shard"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, np.arange(8.0).sum()))


def test_all_gather_over_mesh(mesh8):
    from jax import shard_map

    x = jnp.arange(8.0)

    def body(x):
        return collectives.all_gather(x, "dp_shard", axis=0, tiled=True)

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard"), out_specs=P(None), check_vma=False)
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_ring_permute(mesh8):
    from jax import shard_map

    x = jnp.arange(8.0)

    def body(x):
        return collectives.ring_permute(x, "dp_shard", shift=1)

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard"), out_specs=P("dp_shard"))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_reduce_scatter(mesh8):
    from jax import shard_map

    x = jnp.ones((64, 8))

    def body(x):
        # local block is (8, 8); scatter dim 0 splits it 8-ways after the sum
        return collectives.reduce_scatter(x, "dp_shard", axis=0)

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard", None), out_specs=P("dp_shard", None))
    out = f(x)
    assert out.shape == (8, 8)
    # every element is the sum over the 8 shards' ones → 8.0
    np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))


def test_all_to_all(mesh8):
    from jax import shard_map

    x = jnp.arange(64.0).reshape(8, 8)

    def body(x):
        return collectives.all_to_all(x, "dp_shard", split_axis=1, concat_axis=0)

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard", None), out_specs=P(None, "dp_shard"))
    out = f(x)
    # all_to_all transposes the sharding: result is the matrix re-tiled
    assert out.shape == (8, 8)


def test_ring_permute_larger_and_negative_shift(mesh8):
    from jax import shard_map

    x = jnp.arange(8.0)

    def body_shift(shift):
        def body(x):
            return collectives.ring_permute(x, "dp_shard", shift=shift)

        return shard_map(body, mesh=mesh8, in_specs=P("dp_shard"), out_specs=P("dp_shard"))

    # shift=3: shard i lands on rank (i+3) % 8
    np.testing.assert_allclose(np.asarray(body_shift(3)(x)), np.roll(np.arange(8.0), 3))
    # negative shift rotates the other way around the ring
    np.testing.assert_allclose(np.asarray(body_shift(-1)(x)), np.roll(np.arange(8.0), -1))
    # a full revolution is the identity
    np.testing.assert_allclose(np.asarray(body_shift(8)(x)), np.arange(8.0))


def test_all_to_all_values(mesh8):
    from jax import shard_map

    x = jnp.arange(64.0).reshape(8, 8)

    def body(x):
        return collectives.all_to_all(x, "dp_shard", split_axis=1, concat_axis=0)

    f = shard_map(body, mesh=mesh8, in_specs=P("dp_shard", None), out_specs=P(None, "dp_shard"))
    # the all_to_all transposes the tiling: rank j ends with every rank's
    # j-th column block — i.e. the global matrix re-tiled column-major,
    # which for the [8, 8] arange is exactly the transpose-of-blocks
    out = np.asarray(f(x))
    want = np.asarray(x).reshape(8, 8)  # block size 1x1: all_to_all == value-level identity here
    np.testing.assert_allclose(out, want)


def test_broadcast_from_nonzero_src(mesh8):
    from jax import shard_map

    x = jnp.arange(8.0) * 10.0

    def body(src):
        def inner(x):
            return collectives.broadcast_from(x, "dp_shard", src=src)

        return shard_map(inner, mesh=mesh8, in_specs=P("dp_shard"),
                         out_specs=P("dp_shard"), check_vma=False)

    for src in (0, 3, 7):
        out = np.asarray(body(src)(x))
        np.testing.assert_allclose(out, np.full(8, src * 10.0))


def test_broadcast_from_rejects_out_of_range_src(mesh8):
    # the old gather-then-index form raised at trace time on a bad src; the
    # one-hot+psum rewrite must not degrade that into silent zeros
    from jax import shard_map

    f = shard_map(
        lambda x: collectives.broadcast_from(x, "dp_shard", src=8),
        mesh=mesh8, in_specs=P("dp_shard"), out_specs=P("dp_shard"), check_vma=False,
    )
    with pytest.raises(ValueError, match="out of range"):
        f(jnp.arange(8.0))


def test_broadcast_from_pins_old_gather_select_behavior(mesh8):
    """The O(n) one-hot+psum broadcast must be drop-in for the previous
    all-gather-then-index implementation, including 2-D payloads and bools."""
    from jax import shard_map
    from jax import lax

    def old_broadcast(x, axis_name, src):
        full = lax.all_gather(x, axis_name, axis=0, tiled=False)
        return full[src]

    x2d = jnp.arange(32.0).reshape(8, 4) - 7.0

    for src in (0, 5):
        new = shard_map(
            lambda x: collectives.broadcast_from(x, "dp_shard", src=src),
            mesh=mesh8, in_specs=P("dp_shard", None), out_specs=P("dp_shard", None),
            check_vma=False,
        )(x2d)
        old = shard_map(
            lambda x: old_broadcast(x, "dp_shard", src),
            mesh=mesh8, in_specs=P("dp_shard", None), out_specs=P("dp_shard", None),
            check_vma=False,
        )(x2d)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

    flags = jnp.asarray([True, False] * 4)
    got = shard_map(
        lambda x: collectives.broadcast_from(x, "dp_shard", src=2),
        mesh=mesh8, in_specs=P("dp_shard"), out_specs=P("dp_shard"), check_vma=False,
    )(flags)
    assert got.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got), np.full(8, True))


def test_host_local_to_global(mesh8):
    batch = {"x": np.arange(16.0).reshape(8, 2)}
    out = ops.host_local_to_global(batch, mesh8, P("dp_shard", None))
    assert isinstance(out["x"], jax.Array)
    assert out["x"].shape == (8, 2)
    assert len(out["x"].sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out["x"]), batch["x"])
