"""Long-context attention tests: flash (interpret), ring CP (both rotate
methods, zigzag), Ulysses SP — all against the native reference on the
8-device CPU mesh (reference parity role: CP/SP correctness, SURVEY §5
'long-context')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu.models.llama import native_attention
from accelerate_tpu.ops.flash_attention import flash_attention
from accelerate_tpu.parallel.context_parallel import (
    make_ring_attention,
    zigzag_shard,
    zigzag_unshard,
)
from accelerate_tpu.parallel.sequence_parallel import make_ulysses_attention
from accelerate_tpu.parallelism_config import ParallelismConfig


def _qkv(b=2, t=32, h=4, d=8, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.fixture
def cp_mesh():
    return ParallelismConfig(cp_size=8).build_device_mesh()


@pytest.fixture
def sp_mesh():
    return ParallelismConfig(sp_size=4, dp_shard_size=2).build_device_mesh()


def test_flash_matches_native_interpret():
    q, k, v = _qkv()
    for causal in (True, False):
        ref = native_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_grads_match_native():
    """dq AND dk/dv (all three from the one backward kernel) against the native reference."""
    q, k, v = _qkv()
    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True) ** 2)
    g = lambda q, k, v: jnp.sum(native_attention(q, k, v, causal=True) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}")


@pytest.mark.slow
def test_flash_non_divisible_seq_len():
    """Sequence lengths not divisible by the block size must still be exact
    (padded tile rows/cols are masked, not garbage): fwd + the bwd kernel."""
    rng = np.random.default_rng(3)
    B, T, H, D = 1, 12, 2, 8  # T=12 with block 8 -> padded second block
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    for causal in (True, False):
        ref = native_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True) ** 2)
    g = lambda q, k, v: jnp.sum(native_attention(q, k, v, causal=True) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gn):
        assert np.all(np.isfinite(np.asarray(a))), f"d{name} has NaN/inf"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}")


def test_flash_gqa():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 16, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 8)), jnp.float32)
    ref = native_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("rotate", ["allgather", "alltoall"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_native(cp_mesh, rotate, causal):
    q, k, v = _qkv(t=32)
    ref = native_attention(q, k, v, causal=causal)
    # zigzag layout: host-reorder, shard, attend, un-reorder
    qz = jnp.asarray(zigzag_shard(q, 8))
    kz = jnp.asarray(zigzag_shard(k, 8))
    vz = jnp.asarray(zigzag_shard(v, 8))
    spec = NamedSharding(cp_mesh, P(None, "cp", None, None))
    qz, kz, vz = jax.device_put(qz, spec), jax.device_put(kz, spec), jax.device_put(vz, spec)
    attn = make_ring_attention(cp_mesh, rotate_method=rotate, zigzag=True)
    out = attn(qz, kz, vz, causal=causal)
    out = zigzag_unshard(np.asarray(out), 8)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-4)


@pytest.mark.slow
def test_ring_attention_gqa(cp_mesh):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    ref = native_attention(q, k, v, causal=True)
    qz, kz, vz = (jnp.asarray(zigzag_shard(x, 8)) for x in (q, k, v))
    attn = make_ring_attention(cp_mesh, rotate_method="alltoall", zigzag=True)
    out = zigzag_unshard(np.asarray(attn(qz, kz, vz, causal=True)), 8)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-4)


@pytest.mark.slow
def test_ring_attention_differentiable(cp_mesh):
    q, k, v = _qkv(t=16)
    attn = make_ring_attention(cp_mesh, rotate_method="alltoall", zigzag=False)

    def f(q):
        return jnp.sum(attn(q, k, v, causal=True) ** 2)

    def g(q):
        return jnp.sum(native_attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)), np.asarray(jax.grad(g)(q)), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_native(sp_mesh, causal):
    q, k, v = _qkv(t=32, h=4)
    ref = native_attention(q, k, v, causal=causal)
    spec = NamedSharding(sp_mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    attn = make_ulysses_attention(sp_mesh)
    out = attn(qs, ks, vs, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_head_divisibility_error(sp_mesh):
    q, k, v = _qkv(t=32, h=3)
    attn = make_ulysses_attention(sp_mesh)
    with pytest.raises(ValueError, match="divisible"):
        attn(q, k, v)


def test_ulysses_in_jitted_train_step(sp_mesh):
    """Ulysses attention composes under jit + grad (the train-step path)."""
    q, k, v = _qkv(t=32, h=4)
    attn = make_ulysses_attention(sp_mesh)

    @jax.jit
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, causal=True) ** 2)

    g = jax.grad(loss)(q, k, v)
    assert g.shape == q.shape
    assert np.isfinite(np.asarray(g)).all()


def test_cross_rank_token_mean(sp_mesh):
    from jax import shard_map

    from accelerate_tpu.parallel.sequence_parallel import cross_rank_token_mean

    loss = jnp.arange(32.0).reshape(1, 32)
    mask = jnp.ones((1, 32))

    def body(loss, mask):
        return cross_rank_token_mean(loss, mask, ("sp",))

    f = shard_map(body, mesh=sp_mesh, in_specs=(P(None, "sp"), P(None, "sp")),
                  out_specs=P(), check_vma=False)
    out = float(f(loss, mask))
    assert out == pytest.approx(float(jnp.mean(loss)))


@pytest.mark.slow
def test_flash_gqa_grads_no_repeat():
    """GQA path: dk/dv come back at kv-head shape (group-summed in-kernel)."""
    rng = np.random.default_rng(5)
    B, T, H, Hkv, D = 2, 16, 8, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True) ** 2)
    g = lambda q, k, v: jnp.sum(native_attention(q, k, v, causal=True) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (B, T, Hkv, D)
    for name, a, b in zip("qkv", gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{name}")


@pytest.mark.slow
def test_flash_segment_ids_in_kernel():
    """Packed sequences run inside the fused kernel (no native fallback):
    cross-segment attention masked in fwd and all three grads."""
    rng = np.random.default_rng(6)
    B, T, H, Hkv, D = 2, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    segs = jnp.asarray(np.repeat([[0] * 6 + [1] * 10], B, axis=0), jnp.int32)
    for causal in (True, False):
        ref = native_attention(q, k, v, causal=causal, segment_ids=segs)
        out = flash_attention(q, k, v, causal=causal, segment_ids=segs, block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, segment_ids=segs, block_q=8, block_k=8, interpret=True) ** 2)
    g = lambda q, k, v: jnp.sum(native_attention(q, k, v, causal=True, segment_ids=segs) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{name}")


@pytest.mark.slow
@pytest.mark.parametrize("rotate", ["allgather", "alltoall"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_matches_native(cp_mesh, rotate, causal):
    """Ring attention with per-block flash kernels (position-masked causal,
    logsumexp combine) against the native reference."""
    q, k, v = _qkv(t=32)
    ref = native_attention(q, k, v, causal=causal)
    qz, kz, vz = (jnp.asarray(zigzag_shard(x, 8)) for x in (q, k, v))
    attn = make_ring_attention(cp_mesh, rotate_method=rotate, zigzag=True, use_flash=True)
    out = zigzag_unshard(np.asarray(attn(qz, kz, vz, causal=causal)), 8)
    np.testing.assert_allclose(out, np.asarray(ref), atol=2e-4)


@pytest.mark.slow
def test_flash_ring_differentiable(cp_mesh):
    """Gradients flow through the flash blocks AND the lse combine (the
    g_lse -> delta fold in the kernel backward)."""
    q, k, v = _qkv(t=16)
    attn = make_ring_attention(cp_mesh, rotate_method="alltoall", zigzag=False, use_flash=True)
    f = lambda q: jnp.sum(attn(q, k, v, causal=True) ** 2)
    g = lambda q: jnp.sum(native_attention(q, k, v, causal=True) ** 2)
    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)), np.asarray(jax.grad(g)(q)), atol=2e-4)


def test_flash_positions_and_lse():
    """Explicit positions drive the causal mask; return_lse matches a direct
    logsumexp of the masked scores."""
    rng = np.random.default_rng(7)
    B, T, H, D = 1, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    perm = np.asarray([1, 0, 3, 2, 5, 4, 7, 6, 9, 8, 11, 10, 13, 12, 15, 14])
    pos = jnp.asarray(perm[None, :], jnp.int32)
    out, lse = flash_attention(
        q, k, v, causal=True, positions=pos, return_lse=True,
        block_q=8, block_k=8, interpret=True,
    )
    # reference with an explicit position mask
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    mask = pos[0][:, None] >= pos[0][None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bhts,bshd->bthd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    ref_lse = jax.nn.logsumexp(s, -1).transpose(0, 2, 1)  # [B, T, H]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=1e-4)


def _dense_attention(q, k, v, *, causal, seg_q=None, seg_kv=None, pos_q=None, pos_kv=None):
    """``flash_attention``'s semantics, dense and in f32: causal by index from
    the top-left corner (``native_attention`` aligns T != S bottom-right) or
    by explicit position; returns (out [B, T, H, D], lse [B, T, H])."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d)
    mask = jnp.ones((b, t, s), bool)
    if causal:
        rows = jnp.arange(t)[None, :] if pos_q is None else pos_q
        cols = jnp.arange(s)[None, :] if pos_kv is None else pos_kv
        mask &= rows[:, :, None] >= cols[:, None, :]
    if seg_q is not None:
        mask &= seg_q[:, :, None] == seg_kv[:, None, :]
    scores = jnp.where(mask[:, None], scores, -1e30)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, -1), v)
    return out, jax.nn.logsumexp(scores, -1).transpose(0, 2, 1)


# (id, T, S, q heads, kv heads, causal, extras, dtype): blocks of 8, so every
# case walks several kv sub-blocks a q block and several q blocks a kv head
_ONE_PASS_CASES = [
    ("group1-causal", 24, 24, 2, 2, True, "", "float32"),
    ("group1-full", 24, 24, 2, 2, False, "", "float32"),
    ("group4-causal", 24, 24, 8, 2, True, "", "float32"),
    ("group4-full", 24, 24, 8, 2, False, "", "float32"),
    ("group7-causal", 16, 16, 7, 1, True, "", "float32"),
    ("group7-full", 16, 16, 7, 1, False, "", "float32"),
    ("segments-causal", 24, 24, 4, 2, True, "segments", "float32"),
    ("segments-full", 24, 24, 4, 2, False, "segments", "float32"),
    ("positions-lse", 16, 16, 4, 2, True, "positions", "float32"),
    ("positions-lse-ragged", 20, 20, 4, 1, True, "positions", "float32"),
    ("ragged-causal", 20, 20, 4, 2, True, "", "float32"),
    ("ragged-full", 12, 12, 2, 2, False, "", "float32"),
    ("ragged-segments", 20, 20, 7, 1, True, "segments", "float32"),
    ("cross-full", 16, 24, 4, 2, False, "", "float32"),
    ("cross-causal", 24, 16, 4, 2, True, "", "float32"),
    ("cross-ragged-segments", 12, 20, 4, 2, False, "segments", "float32"),
    ("bf16-group4-causal", 24, 24, 8, 2, True, "", "bfloat16"),
    ("bf16-group7-full", 16, 16, 7, 1, False, "", "bfloat16"),
    ("bf16-positions-lse", 16, 16, 4, 2, True, "positions", "bfloat16"),
    ("bf16-ragged-segments", 20, 20, 4, 2, True, "segments", "bfloat16"),
]


@pytest.mark.parametrize("t,s,h,hkv,causal,extra,dtype", [c[1:] for c in _ONE_PASS_CASES],
                         ids=[c[0] for c in _ONE_PASS_CASES])
def test_flash_one_pass_backward(t, s, h, hkv, causal, extra, dtype):
    """dq, dk AND dv of the one backward kernel, through ``jax.grad``: GQA
    groups 1/4/7, causal on/off, packed segments, explicit positions with a
    nonzero lse cotangent, T off the block, T != S, f32 and bf16 — against
    ``native_attention`` wherever it has the same semantics (self-attention
    shapes, no positions), else against the dense reference above."""
    rng = np.random.default_rng(35)
    b, d = 2, 8
    dtype = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    w_out = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    w_lse = jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32)
    kwargs, dense = {}, {}
    if extra == "segments":
        cut = lambda n: jnp.asarray(np.repeat([[0] * (n // 3) + [1] * (n - n // 3)], b, 0), jnp.int32)
        kwargs = dict(segment_ids=cut(t), kv_segment_ids=cut(s))
        dense = dict(seg_q=cut(t), seg_kv=cut(s))
    if extra == "positions":
        perm = lambda n: jnp.asarray(np.stack([rng.permutation(n) for _ in range(b)]), jnp.int32)
        kwargs = dict(positions=perm(t), kv_positions=perm(s))
        dense = dict(pos_q=kwargs["positions"], pos_kv=kwargs["kv_positions"])

    def flash_loss(q, k, v):
        out, lse = flash_attention(q, k, v, causal=causal, return_lse=True,
                                   block_q=8, block_k=8, interpret=True, **kwargs)
        loss = jnp.sum(out.astype(jnp.float32) * w_out)
        return loss + jnp.sum(lse * w_lse) if extra == "positions" else loss

    def reference_loss(q, k, v):
        if t == s and extra != "positions":
            out = native_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=causal,
                                   segment_ids=kwargs.get("segment_ids"))
            return jnp.sum(out * w_out)
        out, lse = _dense_attention(q, k, v, causal=causal, **dense)
        loss = jnp.sum(out * w_out)
        return loss + jnp.sum(lse * w_lse) if extra == "positions" else loss

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(reference_loss, argnums=(0, 1, 2))(q, k, v)
    atol = 1e-4 if dtype == jnp.float32 else 0.15
    for name, a, ref in zip("qkv", got, want):
        assert a.dtype == dtype and a.shape == ref.shape, f"d{name}"
        a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
        assert np.all(np.isfinite(a)), f"d{name} has NaN/inf"
        np.testing.assert_allclose(a, ref, atol=atol, rtol=0 if dtype == jnp.float32 else 0.03,
                                   err_msg=f"d{name}")


def test_flash_one_pass_dq_sums_kv_blocks_in_f32_and_casts_once():
    """dq of bf16 operands: per kv sub-block ``ds`` is rounded to bf16 (the
    matmul's operand), the products sum in f32 ACROSS the sub-blocks and the
    sum is cast once — equal to that blockwise reference to bf16's last bit,
    and not to one that rounds the running sum after every sub-block."""
    from accelerate_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(36)
    bh, t, d, blk = 2, 64, 16, 8
    bf16 = jnp.bfloat16
    q, k, v, g = (jnp.asarray(rng.normal(size=(bh, t, d)), bf16) for _ in range(4))
    none = jnp.zeros((bh, 1, t), jnp.int32)
    sm = 1.0 / np.sqrt(d)
    out, lse = fa._flash_fwd(q, k, v, none, none, none, none, True, sm, blk, blk, False, False, True)
    dq, _, _ = fa._flash_bwd(q, k, v, none, none, none, none, out, lse, g, None, True, sm,
                             blk, blk, False, False, True)
    assert dq.dtype == bf16

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
    idx = jnp.arange(t)

    def blockwise(round_each_block):
        acc = jnp.zeros((bh, t, d), jnp.float32)
        for start in range(0, t, blk):
            kb, vb = k[:, start:start + blk], v[:, start:start + blk]
            s = jnp.einsum("bsd,btd->bst", kb, q, preferred_element_type=jnp.float32) * sm
            p = jnp.where(idx[None, None, :] >= idx[None, start:start + blk, None],
                          jnp.exp(s - lse[:, None, :]), 0.0)
            dp = jnp.einsum("bsd,btd->bst", vb, g, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None, :]) * sm).astype(bf16)
            acc = acc + jnp.einsum("bst,bsd->btd", ds, kb, preferred_element_type=jnp.float32)
            if round_each_block:
                acc = acc.astype(bf16).astype(jnp.float32)
        return acc.astype(bf16)

    def ulps(a, b):  # distance in bf16 steps between two bf16 arrays
        bits = lambda x: np.asarray(x).view(np.int16).astype(np.int32)
        order = lambda x: np.where(bits(x) < 0, -(bits(x) & 0x7FFF), bits(x))
        return np.abs(order(a) - order(b))

    exact = ulps(dq, blockwise(round_each_block=False))
    assert exact.max() <= 1 and np.mean(exact == 0) > 0.99
    assert np.mean(ulps(dq, blockwise(round_each_block=True)) == 0) < 0.9


@pytest.mark.slow
@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_attention_gqa_no_repeat(cp_mesh, use_flash):
    """GQA KV shards travel the ring at kv-head width (no pre-repeat)."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    ref = native_attention(q, k, v, causal=True)
    qz, kz, vz = (jnp.asarray(zigzag_shard(x, 8)) for x in (q, k, v))
    attn = make_ring_attention(cp_mesh, rotate_method="alltoall", zigzag=True, use_flash=use_flash)
    out = zigzag_unshard(np.asarray(attn(qz, kz, vz, causal=True)), 8)
    np.testing.assert_allclose(out, np.asarray(ref), atol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("rotate", ["allgather", "alltoall"])
@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_attention_segment_ids(cp_mesh, rotate, use_flash):
    """Packed sequences under CP: segment ids rotate with KV; cross-segment
    attention masked identically to the unsharded native reference."""
    rng = np.random.default_rng(12)
    B, T, H, Hkv, D = 2, 32, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    segs = jnp.asarray(np.repeat([[0] * 10 + [1] * 14 + [2] * 8], B, axis=0), jnp.int32)
    for causal in (True, False):
        ref = native_attention(q, k, v, causal=causal, segment_ids=segs)
        qz, kz, vz = (jnp.asarray(zigzag_shard(x, 8)) for x in (q, k, v))
        segz = jnp.asarray(zigzag_shard(segs, 8)) if causal else segs
        attn = make_ring_attention(cp_mesh, rotate_method=rotate, zigzag=causal, use_flash=use_flash)
        out = zigzag_unshard(np.asarray(attn(qz if causal else q, kz if causal else k,
                                             vz if causal else v, causal=causal,
                                             segment_ids=segz)), 8) if causal else \
            np.asarray(attn(q, k, v, causal=causal, segment_ids=segs))
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-4,
                                   err_msg=f"causal={causal}")


@pytest.mark.slow
def test_ring_attention_segment_ids_differentiable(cp_mesh):
    """Grads flow through the segment-masked ring path (flash in-kernel)."""
    rng = np.random.default_rng(13)
    q, k, v = _qkv(t=16, seed=13)
    segs = jnp.asarray(np.repeat([[0] * 6 + [1] * 10], 2, axis=0), jnp.int32)
    attn = make_ring_attention(cp_mesh, rotate_method="alltoall", zigzag=False, use_flash=True)
    f = lambda q: jnp.sum(attn(q, k, v, causal=True, segment_ids=segs) ** 2)
    g = lambda q: jnp.sum(native_attention(q, k, v, causal=True, segment_ids=segs) ** 2)
    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)), np.asarray(jax.grad(g)(q)), atol=2e-4)


def test_flash_cross_segment_ids():
    """Distinct q/kv segment ids (the ring building block) against a masked
    reference with T != S."""
    rng = np.random.default_rng(14)
    B, T, S, H, D = 1, 8, 16, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    seg_q = jnp.asarray([[0] * 4 + [1] * 4], jnp.int32)
    seg_kv = jnp.asarray([[0] * 10 + [1] * 6], jnp.int32)
    out = flash_attention(q, k, v, causal=False, segment_ids=seg_q, kv_segment_ids=seg_kv,
                          block_q=8, block_k=8, interpret=True)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    mask = seg_q[0][:, None] == seg_kv[0][None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_segment_ids(sp_mesh, causal):
    """Packed sequences under SP: local segment ids all-gather to the full
    sequence each rank attends over."""
    rng = np.random.default_rng(21)
    B, T, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    segs = jnp.asarray(np.repeat([[0] * 10 + [1] * 14 + [2] * 8], B, axis=0), jnp.int32)
    ref = native_attention(q, k, v, causal=causal, segment_ids=segs)
    attn = make_ulysses_attention(sp_mesh)
    out = attn(q, k, v, causal=causal, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ulysses_gqa_no_repeat_when_divisible(sp_mesh):
    """GQA kv heads divisible by sp travel the all_to_alls at kv width."""
    rng = np.random.default_rng(22)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)  # 4 kv heads, sp=4
    v = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    ref = native_attention(q, k, v, causal=True)
    out = make_ulysses_attention(sp_mesh)(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ulysses_gqa_indivisible_falls_back(sp_mesh):
    """kv heads < sp: broadcast to q width (correctness preserved)."""
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)  # 2 kv heads, sp=4
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32)
    ref = native_attention(q, k, v, causal=True)
    out = make_ulysses_attention(sp_mesh)(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_default_block_sizes_heuristic():
    """Tiling heuristic: MXU-aligned, seq-clamped, VMEM-bounded."""
    from accelerate_tpu.ops.flash_attention import _VMEM_BUDGET_BYTES, default_block_sizes

    assert default_block_sizes(2048, 2048, 96) == (1024, 1024)  # measured sweet spot
    bq, bk = default_block_sizes(12, 12, 8)
    assert bq == 128 and bk == 128  # never below one MXU tile
    bq, bk = default_block_sizes(8192, 8192, 1024)  # giant head dim must shrink
    assert 4 * (2 * bq * 1024 + 2 * bk * 1024 + bq * bk) <= _VMEM_BUDGET_BYTES
    assert bq % 128 == 0 and bk % 128 == 0
    # the backward's tiles are its own: the sweep's winner at the train
    # cells' shapes, clamped to the lengths, never shrunk by the forward's
    # budget (the kernel states its VMEM from its plan)
    assert default_block_sizes(4096, 4096, 128) == (512, 1024)
    assert default_block_sizes(4096, 4096, 128, backward=True) == (512, 512)
    assert default_block_sizes(12, 300, 8, backward=True) == (128, 384)
    assert default_block_sizes(32768, 32768, 1024, backward=True) == (512, 512)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
def test_autotune_sweeps_one_pass_alone(backward):
    """The autotuner times the forward's or the backward's tiles apart and
    returns one of its candidates for that pass."""
    from accelerate_tpu.ops import flash_attention as fa

    cands = {(8, 8), (16, 8)}
    best = fa.autotune_block_sizes(1, 32, 2, 8, 1, dtype=jnp.float32, backward=backward,
                                   candidates=cands, iters=1)
    assert best in cands
    key = [k for k in fa._AUTOTUNE_CACHE if k[:4] == (1, 32, 2, 8) and k[7] == backward]
    assert len(key) == 1 and fa._AUTOTUNE_CACHE[key[0]] == best


def test_backward_vmem_plan_follows_the_shapes(monkeypatch):
    """What stays resident is a function of (S, D, itemsize) against the
    stated VMEM: two buffers, then one, then equal kv chunks — and the
    chunked walk (a dq partial a chunk, summed in f32) gives the same
    gradients as the whole sequence resident."""
    from accelerate_tpu.ops import flash_attention as fa

    plan = lambda s, d=128, itemsize=2: fa._bwd_vmem_plan(-(-s // 512), 512, 512, d, itemsize)
    assert plan(4096)[:2] == (8, 2) and plan(16384)[:2] == (32, 2)
    assert plan(32768)[:2] == (64, 1)
    blocks, buffers, _ = plan(65536)
    assert buffers == 1 and blocks < 128 and -(-128 // blocks) == 2
    assert all(plan(s, d, i)[2] <= fa._BWD_VMEM_BYTES
               for s in (4096, 32768, 131072) for d in (64, 128, 256) for i in (2, 4))

    q, k, v = _qkv(b=1, t=44, h=4, d=8, seed=9)
    k, v = k[:, :, :2], v[:, :, :2]
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8, interpret=True) ** 2)
    whole = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    step = fa._bwd_vmem_plan(1, 8, 8, 8, 4)[2] - 8 * 128 * 24
    monkeypatch.setattr(fa, "_BWD_VMEM_BYTES", step + 2 * 8 * 128 * 24)  # room for 2 of 6 sub-blocks
    assert fa._bwd_vmem_plan(6, 8, 8, 8, 4)[:2] == (2, 1)
    chunked = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", chunked, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_flash_inner_matches_native(sp_mesh, causal):
    """Ulysses with the flash kernel as the inner attention (the TPU path)."""
    from accelerate_tpu.parallel.sequence_parallel import make_ulysses_attention

    q, k, v = _qkv(t=32, h=4)
    ref = native_attention(q, k, v, causal=causal)
    inner = lambda q, k, v, causal: flash_attention(q, k, v, causal=causal, block_q=8, block_k=8, interpret=True)
    attn = make_ulysses_attention(sp_mesh, inner_attn=inner)
    spec = NamedSharding(sp_mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = attn(qs, ks, vs, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.slow
def test_model_level_ulysses_matches_native():
    """attn_implementation='ulysses' (the config-name entry added for sp×tp
    composition) produces native-attention logits under an active sp mesh —
    params are impl-independent, so one init serves both."""
    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM

    Accelerator(parallelism_config=ParallelismConfig(sp_size=4, dp_shard_size=2))
    rng_np = np.random.default_rng(0)
    tokens = jnp.asarray(rng_np.integers(0, 256, (2, 32)), jnp.int32)
    base = LlamaConfig.tiny(num_key_value_heads=4, dtype=jnp.float32)
    native_model = LlamaForCausalLM(base)
    params = native_model.init(jax.random.key(0), tokens[:, :8])
    ref = np.asarray(native_model.apply(params, tokens))
    uly = LlamaForCausalLM(
        LlamaConfig.tiny(attn_implementation="ulysses", num_key_value_heads=4,
                         dtype=jnp.float32)
    )
    out = np.asarray(uly.apply(params, tokens))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)


@pytest.mark.slow
def test_cp_composes_with_scanned_offload_ladder():
    """The multi-chip long-context claim (docs/long_context.md: ">=131k via
    cp=2 by the same per-shard ladder") requires ring CP to compose with the
    single-chip ladder itself: scan_layers + remat_policy="offload" (+ the
    hybrid boundary split).  Pin that the composed stack trains — loss
    decreases over steps — through the full Accelerator path on the CPU
    mesh (offload storage degrades to device memory there; the scan/remat/
    boundary-naming structure is identical)."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
    from accelerate_tpu.models.llama import stack_layer_params
    from accelerate_tpu.state import AcceleratorState, GradientState
    import optax

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(cp_size=2, dp_shard_size=4),
        mixed_precision="bf16",
    )
    cfg = LlamaConfig.tiny(
        attn_implementation="ring", remat=True, remat_policy="offload",
        scan_layers=True, boundary_offload_fraction=0.5, dtype=jnp.float32,
    )
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    seq = 32  # divisible by 2*cp (zigzag chunk pairs)
    tokens = rng.integers(0, cfg.vocab_size, (4, seq)).astype(np.int32)
    shift_labels = np.roll(tokens, -1, axis=1)
    shift_labels[:, -1] = -100
    unrolled = LlamaForCausalLM(
        LlamaConfig.tiny(attn_implementation="ring", dtype=jnp.float32))
    params = stack_layer_params(unrolled.init(jax.random.key(0), jnp.asarray(tokens[:, :8])))
    state = acc.create_train_state(params, optax.adamw(1e-3), apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
    losses = []
    for _ in range(4):
        with acc.maybe_context_parallel(
            buffers=[tokens, shift_labels], buffer_seq_dims=[1, 1]
        ) as (ids, labels):
            state, metrics = step(state, {"input_ids": ids, "shift_labels": labels})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_sp_composes_with_scanned_offload_ladder():
    """Ulysses SP variant of the composition pin above: sequence-sharded
    inputs through a scan_layers + offload-remat model (docs/long_context.md
    names `sp=2` as the other route past the single-chip ceiling)."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
    from accelerate_tpu.models.llama import stack_layer_params
    from accelerate_tpu.state import AcceleratorState, GradientState
    import optax

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    acc = Accelerator(
        parallelism_config=ParallelismConfig(sp_size=2, dp_shard_size=4),
        mixed_precision="bf16",
    )
    cfg = LlamaConfig.tiny(
        attn_implementation="ulysses", remat=True, remat_policy="offload",
        scan_layers=True, boundary_offload_fraction=0.5, dtype=jnp.float32,
    )
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    unrolled = LlamaForCausalLM(
        LlamaConfig.tiny(attn_implementation="ulysses", dtype=jnp.float32))
    params = stack_layer_params(unrolled.init(jax.random.key(0), jnp.asarray(tokens[:, :8])))
    state = acc.create_train_state(params, optax.adamw(1e-3), apply_fn=model.apply)
    step = acc.prepare_train_step(make_llama_loss_fn(model), max_grad_norm=1.0)
    spec = acc._default_batch_spec()(tokens)
    batch = {
        "input_ids": jax.device_put(jnp.asarray(tokens), NamedSharding(acc.mesh, spec)),
        "labels": jax.device_put(jnp.asarray(tokens), NamedSharding(acc.mesh, spec)),
    }
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# flash under a mesh: the per-shard wrap.  GSPMD cannot partition a Mosaic
# call, so `attn_implementation="flash"` goes manual over whatever axes the
# enclosing region still leaves Auto (tests/test_tpu_compile.py holds the TPU
# compiler's side of this; here: the regions it must nest in, on the CPU mesh)
# ---------------------------------------------------------------------------


def _shard_map_axes(jaxpr):
    """The manual-axes set of every shard_map in ``jaxpr``, outermost first."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            found.append(frozenset(eqn.params["manual_axes"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_shard_map_axes(sub))
    return found


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "segment_ids"])
def test_mesh_flash_runs_per_shard_and_equals_the_bare_kernel(segmented):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.ops.flash_attention import mesh_flash_attention

    acc = Accelerator(parallelism_config=ParallelismConfig(dp_shard_size=4, tp_size=2))
    q, k, v = _qkv(b=4)
    kw = dict(causal=True, block_q=8, block_k=8, interpret=True)
    if segmented:
        kw["segment_ids"] = jnp.asarray(np.repeat([[0, 1]], 16, axis=1).repeat(4, 0))
    # out and its three cotangents (the scalar loss itself sums across shards)
    both = lambda attn: lambda q, k, v: (attn(q, k, v, **kw), jax.grad(
        lambda q, k, v: jnp.sum(attn(q, k, v, **kw) ** 2), argnums=(0, 1, 2))(q, k, v))
    wrapped = jax.jit(both(mesh_flash_attention))
    for got, want in zip(jax.tree.leaves(wrapped(q, k, v)),
                         jax.tree.leaves(jax.jit(both(flash_attention))(q, k, v))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(_shard_map_axes(wrapped.trace(q, k, v).jaxpr.jaxpr)) == {
        frozenset(acc.mesh.axis_names)}


@pytest.mark.parametrize("outer,inner", [
    ({"pp"}, {"dcn", "dp_replicate", "dp_shard", "cp", "sp", "tp", "ep"}),
    (None, None),  # outer region fully manual: nothing left to wrap
], ids=["pipeline_stage", "fully_manual"])
def test_mesh_flash_nests_in_a_region_that_is_already_manual(outer, inner):
    """jax rejects a concrete-mesh shard_map inside a manual region: the wrap
    must read the CONTEXT mesh, take only the axes still Auto, and be the
    bare kernel where the region (PowerSGD / hierarchical grad sync) left
    none."""
    from accelerate_tpu import Accelerator
    from accelerate_tpu.ops.flash_attention import mesh_flash_attention

    acc = Accelerator(parallelism_config=ParallelismConfig(
        pp_size=2, dp_shard_size=2, tp_size=2))
    q, k, v = _qkv(b=4)
    kw = dict(causal=True, block_q=8, block_k=8, interpret=True)
    batch = P(None if outer else "dp_shard")
    region = jax.jit(jax.shard_map(
        lambda q, k, v: mesh_flash_attention(q, k, v, **kw), mesh=acc.mesh,
        in_specs=batch, out_specs=batch, check_vma=False,
        **({"axis_names": outer} if outer else {})))
    np.testing.assert_array_equal(
        np.asarray(region(q, k, v)), np.asarray(flash_attention(q, k, v, **kw)))
    nested = _shard_map_axes(region.trace(q, k, v).jaxpr.jaxpr)[1:]
    assert nested == ([frozenset(inner)] if inner else [])


@pytest.mark.parametrize("region", ["pipeline_stage", "powersgd", "hierarchical"])
def test_flash_model_inside_manual_regions_matches_native(region):
    """The supported combinations that trace the model inside a shard_map:
    a GPipe stage (manual over pp), and the compressed / hierarchical
    grad-sync steps (manual over every axis)."""
    import optax

    from accelerate_tpu import (
        Accelerator,
        FullyShardedDataParallelPlugin,
        GradSyncKwargs,
    )
    from accelerate_tpu.models import LlamaConfig, LlamaForCausalLM, make_llama_loss_fn
    from accelerate_tpu.parallel.pipeline_parallel import prepare_pipeline
    from accelerate_tpu.state import AcceleratorState, GradientState
    from accelerate_tpu.utils.dataclasses import ShardingStrategy

    models = {impl: LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=2, attn_implementation=impl, dtype=jnp.float32))
        for impl in ("flash", "native")}
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 16)), jnp.int32)
    params = models["native"].init(jax.random.key(0), ids[:, :8])

    if region == "pipeline_stage":
        acc = Accelerator(parallelism_config=ParallelismConfig(
            pp_size=2, dp_shard_size=2, tp_size=2))
        got = prepare_pipeline(models["flash"], params, acc.mesh, num_microbatches=4)(ids)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(models["native"].apply(params, ids)),
            atol=2e-4, rtol=2e-4)
        return

    ddp = FullyShardedDataParallelPlugin(sharding_strategy=ShardingStrategy.NO_SHARD)
    setup = {
        "powersgd": (ParallelismConfig(dp_shard_size=8),
                     GradSyncKwargs(compression="powersgd", rank=2)),
        "hierarchical": (ParallelismConfig(dcn_size=2, dp_shard_size=4),
                         GradSyncKwargs(hierarchical=True)),
    }[region]
    losses = {}
    for impl, model in models.items():
        AcceleratorState._reset_state(reset_partial_state=True)
        GradientState._reset_state()
        acc = Accelerator(parallelism_config=setup[0], fsdp_plugin=ddp,
                          kwargs_handlers=[setup[1]])
        state = acc.create_train_state(  # the step donates its state: a copy each
            jax.tree.map(jnp.copy, params), acc.prepare(optax.sgd(0.05)))
        step = acc.prepare_train_step(make_llama_loss_fn(model))
        losses[impl] = []
        for _ in range(2):
            state, metrics = step(state, {"input_ids": ids, "labels": ids})
            losses[impl].append(float(metrics["loss"]))
    assert losses["flash"][1] < losses["flash"][0]
    np.testing.assert_allclose(losses["flash"], losses["native"], atol=1e-4)
