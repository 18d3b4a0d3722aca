"""PLANTED BUGS for the jaxpr auditor — one function per GL1xx rule.

These ARE imported and traced (abstractly — ``jax.jit(...).trace``, no
device execution) by ``tests/test_analysis.py``; each function carries the
hazard in its traced program, invisible to a source-level linter.
Corrected twins: ``clean_jaxpr.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np

# ~1.4 MiB closed-over constant (above the 1 MiB default threshold)
_BIG_TABLE = np.ones((600, 600), np.float32)


def wasted_donation_step(state, batch):
    """GL101: ``state`` is donated (the test jits with donate_argnums=(0,))
    but the function returns only a scalar — no output can alias the
    donated (64, 64) buffer, so the donation frees nothing."""
    return (state * batch).sum()


def key_reuse_step(key, x):
    """GL104: the same key feeds two random primitives — the 'noise' and
    'dropout' streams are identical."""
    noise = jax.random.normal(key, x.shape)
    mask = jax.random.uniform(key, x.shape) > 0.1
    return jnp.where(mask, x + noise, x)


def key_reuse_after_split_step(key, x):
    """GL104 (the classic): the parent key is split AND consumed directly —
    the direct stream correlates with the children."""
    k1, _k2 = jax.random.split(key)
    direct = jax.random.normal(key, x.shape)  # parent already retired by split
    child = jax.random.normal(k1, x.shape)
    return direct + child


def const_capture_step(x):
    """GL102: ``_BIG_TABLE`` closes over into the jaxpr as a constant —
    re-uploaded per executable, invisible to donation and sharding."""
    return x @ _BIG_TABLE


def transfer_in_trace_step(x):
    """GL103 (audited with ``default_memory_kind='device'``): an explicit
    device_put to host memory inside traced code — a host<->device copy
    serialized into the step.  The destination is named, not read off the
    backend: the CPU backend's default memory kind is "device" too."""
    y = x * 2.0
    dst = jax.sharding.SingleDeviceSharding(jax.devices()[0], memory_kind="pinned_host")
    return jax.device_put(y, dst)


def unsharded_output_step(x):
    """GL105: a 4 MiB output whose producer is a plain add — GSPMD may
    resolve it fully replicated."""
    return x + 1.0  # x: (1024, 1024) f32


def collective_matmul_hint_step(x, w):
    """GL106 (hint): the gathered activations feed exactly ONE dot_general —
    the monolithic all-gather→matmul pipe a ring collective-matmul would
    hide inside the partial matmuls.  Only the trace sees the fan-out."""
    from jax.sharding import Mesh, PartitionSpec as P

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("x",))

    def body(xl, wl):
        full = jax.lax.all_gather(xl, "x", axis=0, tiled=True)
        return jax.lax.dot_general(full, wl, (((1,), (0,)), ((), ())))

    return _shard_map(body, mesh=mesh, in_specs=(P("x", None), P(None, None)),
                      out_specs=P(None, None), **_no_check)(x, w)


def collective_matmul_rs_hint_step(x, w):
    """GL107 (hint): the row-parallel mirror of GL106 — the full partial
    matmul finishes before ONE monolithic reduce_scatter starts.  Only the
    trace sees the single-consumer pipe."""
    from jax.sharding import Mesh, PartitionSpec as P

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("x",))

    def body(xl, wl):
        part = jax.lax.dot_general(xl, wl, (((2,), (0,)), ((), ())))
        return jax.lax.psum_scatter(part, "x", scatter_dimension=1, tiled=True)

    return _shard_map(body, mesh=mesh,
                      in_specs=(P(None, None, "x"), P("x", None)),
                      out_specs=P(None, "x", None), **_no_check)(x, w)


def unscaled_fp8_dot_step(x, w):
    """GL110: both operands cast to fp8 codes, matmul'd, and the
    accumulator consumed by an add with NO dequantizing mul/div — the
    downstream math runs on values off by the combined scale factor (the
    loss still goes down, just slower, which is why only the trace catches
    it)."""
    qx = (x * 448.0).astype(jnp.float8_e4m3fn)
    qw = (w * 448.0).astype(jnp.float8_e4m3fn)
    y = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y + 1.0  # raw fp8 codes flow into the add


def fused_decode_unscaled_kv_step(q, k_codes, v_codes, k_scale, v_scale):
    """GL110 (the fused-decode shape of PR 17): the jaxpr model of
    ``fused_bgmv_paged_decode``'s quantized-KV contraction — scores off an
    fp8 K-page dot and the weighted sum over fp8 V-pages reach the output
    add with NEITHER ``k_scale`` nor ``v_scale`` applied.  The fused kernel
    dequantizes in-kernel (``kv_qmax`` scaling); this model drops it."""
    qk = (q * 448.0).astype(jnp.float8_e4m3fn)
    scores = jax.lax.dot_general(qk, k_codes, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    qs = (scores * 448.0).astype(jnp.float8_e4m3fn)
    out = jax.lax.dot_general(qs, v_codes, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    del k_scale, v_scale  # the planted bug: scales never touch the chain
    return out + 1.0


def fused_verify_unscaled_kv_step(q_tokens, k_codes, v_codes, k_scale, v_scale):
    """GL110 (the multi-token verify shape of PR 17): the verify window's
    k+1 queries attend over the same quantized pages — one dot per
    contraction, still no dequantizing mul before the residual add."""
    qk = (q_tokens * 448.0).astype(jnp.float8_e4m3fn)
    scores = jax.lax.dot_general(qk, k_codes, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    qs = (scores * 448.0).astype(jnp.float8_e4m3fn)
    out = jax.lax.dot_general(qs, v_codes, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    del k_scale, v_scale
    return out + q_tokens  # raw codes land in the residual stream


def flat_dcn_reduce_step(g):
    """GL108 (hint): a >= 1 MiB gradient psum over the JOINT ('dcn',
    'dp_shard') axes — the flat reduction whose cross-slice leg moves one
    full-size copy per intra-slice device over the slow DCN link.  The
    hierarchical decomposition (clean twin) reduce-scatters over ICI first
    so only the 1/p slab crosses dcn."""
    from jax.sharding import Mesh, PartitionSpec as P

    try:
        from jax import shard_map as _shard_map

        _no_check = {"check_vma": False}
    except ImportError:
        from jax.experimental.shard_map import shard_map as _shard_map

        _no_check = {"check_rep": False}

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dcn", "dp_shard"))

    def body(gl):
        return jax.lax.psum(gl[0], ("dcn", "dp_shard"))

    return _shard_map(body, mesh=mesh, in_specs=P(("dcn", "dp_shard")),
                      out_specs=P(None, None), **_no_check)(g)


def example_args():
    """Concrete example inputs for each planted function (tiny; tracing
    only reads shapes/dtypes)."""
    return {
        "wasted_donation_step": (jnp.ones((64, 64)), jnp.ones((64, 64))),
        "key_reuse_step": (jax.random.key(0), jnp.ones((8,))),
        "key_reuse_after_split_step": (jax.random.key(0), jnp.ones((8,))),
        "const_capture_step": (jnp.ones((600,)),),
        "transfer_in_trace_step": (jnp.ones((8,)),),
        "unsharded_output_step": (jax.ShapeDtypeStruct((1024, 1024), jnp.float32),),
        "collective_matmul_hint_step": (jnp.ones((8, 16)), jnp.ones((16, 4))),
        "collective_matmul_rs_hint_step": (jnp.ones((1, 8, 16)), jnp.ones((16, 4))),
        "unscaled_fp8_dot_step": (jnp.ones((8, 16)), jnp.ones((16, 4))),
        # q [H, D] / q_tokens [T, D] against P quantized pages of width D
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
        # per-device operand after the leading world-axis index: 520*520*4
        # ≈ 1.03 MiB — above the 1 MiB GL108 threshold
        "flat_dcn_reduce_step": (jax.ShapeDtypeStruct((4, 520, 520), jnp.float32),),
    }
