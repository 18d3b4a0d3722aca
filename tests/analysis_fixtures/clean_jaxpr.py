"""Corrected twins of ``planted_jaxpr.py`` — same shapes, same audit
parameters, zero findings."""

import jax
import jax.numpy as jnp
import numpy as np

_BIG_TABLE = np.ones((600, 600), np.float32)


def wasted_donation_step(state, batch):
    """GL101 fixed: the update has the donated argument's shape/dtype, so
    XLA aliases the donated buffer to it — donation actually frees HBM."""
    new_state = state * 0.9 + batch
    return new_state, (state * batch).sum()


def key_reuse_step(key, x):
    """GL104 fixed: one split child per consumer, parent retired."""
    k_noise, k_mask = jax.random.split(key)
    noise = jax.random.normal(k_noise, x.shape)
    mask = jax.random.uniform(k_mask, x.shape) > 0.1
    return jnp.where(mask, x + noise, x)


def key_reuse_after_split_step(key, x):
    """GL104 fixed: only the split children are consumed."""
    k1, k2 = jax.random.split(key)
    return jax.random.normal(k1, x.shape) + jax.random.normal(k2, x.shape)


def const_capture_step(x, table):
    """GL102 fixed: the table rides in as an argument — shardable,
    donatable, absent from the jaxpr consts."""
    return x @ table


def transfer_in_trace_step(x):
    """GL103 fixed: no placement change inside the trace; the caller owns
    transfers (or routes them through the streaming pipeline stages)."""
    return x * 2.0


def unsharded_output_step(x):
    """GL105 fixed: the producer is a sharding constraint, like the
    accelerator's ``pinned_step_fn``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    return jax.lax.with_sharding_constraint(x + 1.0, NamedSharding(mesh, PartitionSpec()))


def collective_matmul_hint_step(x, w):
    """GL106 fixed: the gather-then-matmul pipe rides the ring schedule —
    ppermute ticks hidden under partial matmuls, no all_gather in the
    trace (ops/collective_matmul.py)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from accelerate_tpu.ops.collective_matmul import ring_all_gather_matmul

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("x",))

    def body(xl, wl):
        return ring_all_gather_matmul(xl, wl, "x")[0]

    return _shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "x", None), P(None, None)),
        out_specs=P(None, None), **_no_check,
    )(x[None], w)


def collective_matmul_rs_hint_step(x, w):
    """GL107 fixed: the matmul-then-scatter pipe rides the ring schedule —
    per-chunk partial matmuls with ppermute accumulator hops hidden under
    them, no reduce_scatter in the trace (ops/collective_matmul.py)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from accelerate_tpu.ops.collective_matmul import ring_matmul_reduce_scatter

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("x",))

    def body(xl, wl):
        return ring_matmul_reduce_scatter(xl, wl, "x")

    return _shard_map(body, mesh=mesh,
                      in_specs=(P(None, None, "x"), P("x", None)),
                      out_specs=P(None, "x", None), **_no_check)(x, w)


def unscaled_fp8_dot_step(x, w):
    """GL110 fixed: the accumulator is multiplied by the combined inverse
    scale before anything else consumes it — what rule GL110 asks."""
    x_scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    w_scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(w)), 1e-12)
    qx = (x * x_scale).astype(jnp.float8_e4m3fn)
    qw = (w * w_scale).astype(jnp.float8_e4m3fn)
    y = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y * (1.0 / (x_scale * w_scale)) + 1.0


def fused_decode_unscaled_kv_step(q, k_codes, v_codes, k_scale, v_scale):
    """GL110 fixed (the fused-decode shape): the in-kernel dequant of
    ``fused_bgmv_paged_decode`` modeled at the jaxpr level — scores carry
    ``k_scale`` and the weighted sum carries ``v_scale`` before anything
    downstream consumes them (the kv_qmax contract)."""
    qk = (q * 448.0).astype(jnp.float8_e4m3fn)
    scores = jax.lax.dot_general(qk, k_codes, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * (k_scale / 448.0)
    out = jax.lax.dot_general(scores, v_codes.astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out * v_scale + 1.0


def fused_verify_unscaled_kv_step(q_tokens, k_codes, v_codes, k_scale, v_scale):
    """GL110 fixed (the multi-token verify shape): every contraction over
    the quantized pages is rescaled before the residual add sees it."""
    qk = (q_tokens * 448.0).astype(jnp.float8_e4m3fn)
    scores = jax.lax.dot_general(qk, k_codes, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * (k_scale / 448.0)
    out = jax.lax.dot_general(scores, v_codes.astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out * v_scale + q_tokens


def flat_dcn_reduce_step(g):
    """GL108 fixed: the hierarchical decomposition — reduce-scatter inside
    the slice over ICI, all-reduce only the 1/p slab over dcn, all-gather
    back (parallel/hierarchical.py).  The only psum spanning dcn operates
    on the slab, and a dcn-only psum is the hierarchical path's own hop —
    quiet by design."""
    from jax.sharding import Mesh, PartitionSpec as P

    from accelerate_tpu.parallel.hierarchical import hierarchical_sync

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dcn", "dp_shard"))

    def body(gl):
        out, _, _ = hierarchical_sync({"g": gl[0]}, ("dp_shard",), "dcn")
        return out["g"]

    from jax.sharding import NamedSharding

    out = _shard_map(body, mesh=mesh, in_specs=P(("dcn", "dp_shard")),
                     out_specs=P(None, None), **_no_check)(g)
    # pin the large output so the fixture stays single-rule (GL105 quiet)
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P(None, None)))


def example_args():
    return {
        "wasted_donation_step": (jnp.ones((64, 64)), jnp.ones((64, 64))),
        "key_reuse_step": (jax.random.key(0), jnp.ones((8,))),
        "key_reuse_after_split_step": (jax.random.key(0), jnp.ones((8,))),
        "const_capture_step": (jnp.ones((600,)), jnp.asarray(_BIG_TABLE)),
        "transfer_in_trace_step": (jnp.ones((8,)),),
        "unsharded_output_step": (jax.ShapeDtypeStruct((1024, 1024), jnp.float32),),
        "collective_matmul_hint_step": (jnp.ones((8, 16)), jnp.ones((16, 4))),
        "collective_matmul_rs_hint_step": (jnp.ones((1, 8, 16)), jnp.ones((16, 4))),
        "unscaled_fp8_dot_step": (jnp.ones((8, 16)), jnp.ones((16, 4))),
        "fused_decode_unscaled_kv_step": (
            jnp.ones((4, 16)), jnp.ones((8, 16), jnp.float8_e4m3fn),
            jnp.ones((8, 16), jnp.float8_e4m3fn), jnp.float32(0.1),
            jnp.float32(0.1),
        ),
        "fused_verify_unscaled_kv_step": (
            jnp.ones((5, 16)), jnp.ones((8, 16), jnp.float8_e4m3fn),
            jnp.ones((8, 16), jnp.float8_e4m3fn), jnp.float32(0.1),
            jnp.float32(0.1),
        ),
        "flat_dcn_reduce_step": (jax.ShapeDtypeStruct((4, 520, 520), jnp.float32),),
    }
