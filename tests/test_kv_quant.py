"""Quantized KV pages (ISSUE 17 tentpole leg 2): int8/fp8 page codes with
per-(kv-head, page) running-amax scales.  Pins the acceptance contracts:

- greedy ``generate_paged`` under int8 KV stays within the pinned logit
  tolerance of the dense-cache reference and is BITWISE run-to-run
  deterministic;
- the capacity ladder delivers >= 1.9x tokens per HBM byte once
  ``page_size * head_dim`` amortizes the scales;
- quantize-on-write semantics: roundtrip error bounded by the page amax,
  running-amax rescale keeps one scale per page, an offset-0 write resets
  a recycled page's range;
- the knob surface (``ServingPlugin.kv_dtype`` + env default), the
  kv_dtype-seeded prefix-cache hashes, and the transfer handshake's
  dtype-mismatch rejection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import GenerationConfig, generate, generate_paged
from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM, init_paged_cache
from accelerate_tpu.ops.paged_cache import (
    KV_QUANT_QMAX,
    dequantize_kv_pages,
    paged_write_kv_quantized,
    resolve_kv_dtype,
)
from accelerate_tpu.serving import Request, ServingEngine, kv_pool_accounting
from accelerate_tpu.serving.paged_cache import kv_page_bytes
from accelerate_tpu.serving.prefix_cache import PrefixCache, block_hashes
from accelerate_tpu.serving.transfer import PagedKVTransport, page_bytes
from accelerate_tpu.utils.dataclasses import ServingPlugin


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _plugin(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("pages_per_slot", 8)
    kw.setdefault("num_pages", 24)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("decode_kernel", "native")
    return ServingPlugin(**kw)


# ---------------------------------------------------------------------------
# knob surface
# ---------------------------------------------------------------------------


def test_resolve_kv_dtype_normalization():
    for dense in (None, "", "bf16"):
        assert resolve_kv_dtype(dense) is None
    assert resolve_kv_dtype("int8") == "int8"
    assert resolve_kv_dtype("fp8") == "fp8"
    with pytest.raises(ValueError, match="kv_dtype"):
        resolve_kv_dtype("int4")


def test_serving_plugin_kv_dtype_knob(monkeypatch):
    assert _plugin().kv_dtype == "bf16"
    assert _plugin(kv_dtype="INT8").kv_dtype == "int8"  # case-folded
    monkeypatch.setenv("ACCELERATE_SERVE_KV_DTYPE", "fp8")
    assert _plugin().kv_dtype == "fp8"                  # env default
    assert _plugin(kv_dtype="bf16").kv_dtype == "bf16"  # explicit wins
    with pytest.raises(ValueError, match="kv_dtype"):
        _plugin(kv_dtype="int4")


def test_quantized_pool_layout():
    cfg = LlamaConfig.tiny()
    dense = init_paged_cache(cfg, 8, 4, 2, 4)
    quant = init_paged_cache(cfg, 8, 4, 2, 4, kv_dtype="int8")
    assert "k_scales" not in dense["layers"][0]
    layer = quant["layers"][0]
    assert layer["k_pages"].dtype == jnp.int8
    assert layer["k_scales"].shape == (cfg.num_key_value_heads, 8)
    assert layer["v_scales"].dtype == jnp.float32
    fp8 = init_paged_cache(cfg, 8, 4, 2, 4, kv_dtype="fp8")
    assert fp8["layers"][0]["v_pages"].dtype == jnp.float8_e4m3fn


# ---------------------------------------------------------------------------
# quantize-on-write semantics
# ---------------------------------------------------------------------------


def _empty_page_pool(hkv=2, num_pages=4, page=4, d=16, kv_dtype="int8"):
    pages = jnp.zeros((hkv, num_pages, page, d),
                      jnp.int8 if kv_dtype == "int8" else jnp.float8_e4m3fn)
    scales = jnp.zeros((hkv, num_pages), jnp.float32)
    return pages, scales


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_write_quantized_roundtrip(kv_dtype):
    """Write a full page, dequantize, and bound the error by the
    quantization step (amax / QMAX); the same call is bitwise
    reproducible (duplicate scatters all see the final amax)."""
    pages, scales = _empty_page_pool(kv_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(1, 4, 2, 16)), jnp.float32)
    ids = jnp.zeros((1, 4), jnp.int32)
    offs = jnp.arange(4, dtype=jnp.int32)[None]
    p1, s1 = paged_write_kv_quantized(pages, scales, vals, ids, offs, kv_dtype)
    p2, s2 = paged_write_kv_quantized(pages, scales, vals, ids, offs, kv_dtype)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    deq = dequantize_kv_pages(p1, s1, kv_dtype, jnp.float32)  # [Hkv,P,page,D]
    got = np.asarray(deq[:, 0]).transpose(1, 0, 2)            # [page,Hkv,D]
    want = np.asarray(vals[0])
    amax = np.abs(want).max(axis=(0, 2), keepdims=True)       # per kv-head
    # int8's step is uniform (amax/127); fp8 e4m3's is relative to the
    # element (3 mantissa bits -> <= 2^-3 round-to-nearest).  Allow 2
    # steps: the running-amax rescale pass can add one more rounding.
    step = amax / KV_QUANT_QMAX[kv_dtype]
    tol = 2.0 * np.maximum(step, np.abs(want) * 2.0 ** -3)
    assert np.max(np.abs(got - want) / tol) < 1.0


def test_paged_write_running_amax_and_offset0_reset():
    pages, scales = _empty_page_pool()
    small = jnp.full((1, 2, 2, 16), 0.1, jnp.float32)
    big = jnp.full((1, 1, 2, 16), 10.0, jnp.float32)
    pid = jnp.zeros((1, 2), jnp.int32)

    # open page 0 with small rows: scale is the small amax
    pages, scales = paged_write_kv_quantized(
        pages, scales, small, pid, jnp.asarray([[0, 1]], jnp.int32), "int8")
    assert np.allclose(np.asarray(scales[:, 0]), 0.1, rtol=1e-5)

    # a later big row grows the running amax; earlier rows rescale in place
    pages, scales = paged_write_kv_quantized(
        pages, scales, big, pid[:, :1], jnp.asarray([[2]], jnp.int32), "int8")
    assert np.allclose(np.asarray(scales[:, 0]), 10.0, rtol=1e-5)
    deq = np.asarray(dequantize_kv_pages(pages, scales, "int8", jnp.float32))
    step = 10.0 / 127.0
    assert np.max(np.abs(deq[:, 0, :2] - 0.1)) <= 2 * step
    assert np.max(np.abs(deq[:, 0, 2] - 10.0)) <= step

    # recycling the page: an offset-0 write resets the amax — the new
    # tenant never inherits the old 10.0 range
    pages, scales = paged_write_kv_quantized(
        pages, scales, small[:, :1], pid[:, :1],
        jnp.asarray([[0]], jnp.int32), "int8")
    assert np.allclose(np.asarray(scales[:, 0]), 0.1, rtol=1e-5)
    deq = np.asarray(dequantize_kv_pages(pages, scales, "int8", jnp.float32))
    assert np.max(np.abs(deq[:, 0, 0] - 0.1)) <= 2 * 0.1 / 127.0


# ---------------------------------------------------------------------------
# capacity ladder + accounting
# ---------------------------------------------------------------------------


def test_capacity_ladder_at_least_1p9x():
    """The acceptance floor: >= 1.9x token capacity per HBM byte once
    page_size * head_dim amortizes the per-page scales (tiny geometry:
    page 16 x D 16 -> 4096 dense bytes vs 2080 quantized = 1.969x)."""
    cfg = LlamaConfig.tiny()
    for kv_dtype in ("int8", "fp8"):
        acct = kv_pool_accounting(cfg, 64, 16, 2, kv_dtype)
        assert acct["kv_dtype"] == kv_dtype
        assert acct["capacity_vs_bf16"] >= 1.9
        want = (2 * cfg.num_hidden_layers * 16 * cfg.num_key_value_heads
                * cfg.head_dim
                + 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * 4)
        assert acct["bytes_per_page"] == want == kv_page_bytes(cfg, 16, 2, kv_dtype)
    # dense accounting is unchanged and carries no ladder keys
    dense = kv_pool_accounting(cfg, 64, 16, 2)
    assert "capacity_vs_bf16" not in dense
    # the transfer wire unit routes through the SAME formula (twin exactness
    # by construction)
    assert page_bytes(cfg, 16, 2, kv_dtype="int8") == kv_page_bytes(cfg, 16, 2, "int8")


# ---------------------------------------------------------------------------
# model-level parity (the pinned tolerance) + end-to-end determinism
# ---------------------------------------------------------------------------


def _paged_prefill_logits(model, params, ids, kv_dtype):
    page_size, slots, pps = 4, 1, 4
    pc = init_paged_cache(model.config, 8, page_size, slots, pps,
                          kv_dtype=kv_dtype or None)
    bt = jnp.arange(slots * pps, dtype=jnp.int32).reshape(slots, pps)
    keep = ("k_pages", "v_pages", "k_scales", "v_scales")
    layers = [{**{k: l[k] for k in keep if k in l}, "block_tables": bt}
              for l in pc["layers"]]
    n = ids.shape[1]
    lg, _ = model.apply(
        params, ids, positions=jnp.arange(n)[None],
        cache=layers, cache_write_mask=jnp.ones((1, n), bool),
    )
    return lg


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_quantized_logits_within_pinned_tolerance(tiny_model, kv_dtype):
    """Prefill through quantized pages reproduces the dense-cache logits
    within the pinned envelope — the fp32-reference tolerance the ISSUE
    acceptance names (measured ~0.4% relative on the tiny model; pinned
    at 5% of the logit range so real regressions, not quantization noise,
    trip it)."""
    model, params = tiny_model
    ids = jnp.asarray([[3, 17, 99, 4, 250, 7, 12, 63]], jnp.int32)
    ref = np.asarray(model.apply(params, ids), np.float32)
    got = np.asarray(_paged_prefill_logits(model, params, ids, kv_dtype), np.float32)
    scale = np.abs(ref).max()
    assert np.max(np.abs(got - ref)) < 0.05 * scale
    # and the quantized path really quantized (not silently dense)
    assert np.max(np.abs(got - ref)) > 0


def test_generate_paged_int8_deterministic_and_close_to_reference(tiny_model):
    """End-to-end acceptance: greedy paged decode over int8 KV pages is
    BITWISE run-to-run deterministic, and tracks the dense reference —
    the first emitted token of every row matches exactly (one decode step
    of quantization noise never flips the tiny model's argmax) and overall
    token agreement stays above the floor.  Exact full-sequence match is
    NOT the contract: a random-init model's near-uniform logits let one
    argmax flip cascade, which says nothing about the KV representation.
    """
    model, params = tiny_model
    prompts = [[3, 17, 99, 4, 250], [7, 12, 63], [5, 5, 9, 20, 77, 120, 8]]
    maxlen = max(len(p) for p in prompts)
    ids = jnp.asarray([p + [0] * (maxlen - len(p)) for p in prompts], jnp.int32)
    plens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    gcfg = GenerationConfig(max_new_tokens=12)
    ref = np.asarray(generate(model, params, ids, gcfg, prompt_lengths=plens))
    plug = _plugin(kv_dtype="int8")
    out1 = np.asarray(generate_paged(model, params, ids, gcfg,
                                     prompt_lengths=plens, serving_plugin=plug))
    out2 = np.asarray(generate_paged(model, params, ids, gcfg,
                                     prompt_lengths=plens, serving_plugin=plug))
    np.testing.assert_array_equal(out1, out2)   # bitwise run-to-run
    np.testing.assert_array_equal(out1[:, 0], ref[:, 0])
    assert (out1 == ref).mean() >= 0.5


# ---------------------------------------------------------------------------
# prefix-cache hash seeding + transfer handshake
# ---------------------------------------------------------------------------


def test_block_hashes_seeded_by_kv_dtype():
    """A quantized pool's page CONTENT is codes+scale, so its prefix hashes
    must never collide with a dense pool's (or another quant dtype's) for
    the same prompt — the kv_dtype seeds the chain root."""
    prompt = (3, 17, 99, 4, 250, 7, 12, 63)
    dense = block_hashes(prompt, 4)
    assert block_hashes(prompt, 4, kv_dtype="bf16") == dense  # bf16 == dense
    int8 = block_hashes(prompt, 4, kv_dtype="int8")
    fp8 = block_hashes(prompt, 4, kv_dtype="fp8")
    assert len({dense[0], int8[0], fp8[0]}) == 3
    # PrefixCache carries the seed so engine-internal hashing matches
    assert PrefixCache(4, kv_dtype="int8").block_hashes(prompt) == int8
    assert PrefixCache(4).block_hashes(prompt) == dense


def test_transport_rejects_kv_dtype_mismatch(tiny_model):
    model, params = tiny_model
    gcfg = GenerationConfig(max_new_tokens=4)
    src = ServingEngine(model, params, _plugin(kv_dtype="int8"), gcfg)
    dst = ServingEngine(model, params, _plugin(), gcfg)
    with pytest.raises(ValueError, match="KV page dtypes must match"):
        PagedKVTransport(src, dst)
    # matched quantized pair: constructs, and the wire unit is the
    # codes+scales page size (half the dense bytes and change)
    dst8 = ServingEngine(model, params, _plugin(kv_dtype="int8"), gcfg)
    t = PagedKVTransport(src, dst8)
    cfg = model.config
    assert t._page_bytes == kv_page_bytes(cfg, 4, 2, "int8") \
        < kv_page_bytes(cfg, 4, 2)
