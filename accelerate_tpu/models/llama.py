"""Llama-family decoder — the flagship model (BASELINE.json north star:
Llama-2-7B fine-tune; the reference exercises it via transformers + FSDP2,
its benchmarks/fsdp2 + examples/torch_native_parallelism).

TPU-first design notes:
- bf16 compute / fp32 master weights via the Accelerator policy; all matmuls
  shaped for the MXU (head_dim multiples of 128 recommended).
- Parameter paths (``q_proj/k_proj/v_proj/o_proj``, ``gate_proj/up_proj/
  down_proj``, ``embed_tokens``, ``lm_head``) line up with the TP rule table
  (parallel/sharding.py TRANSFORMER_TP_RULES), so tensor parallelism is pure
  sharding annotation.
- Attention implementation is pluggable: "native" (XLA fused softmax),
  "flash" (Pallas kernel, ops/flash_attention.py), "ring" (context-parallel
  shard_map kernel, parallel/context_parallel.py) — selected by config.
- ``remat`` wraps each block in ``jax.checkpoint`` (the activation-
  checkpointing analog, reference fsdp_utils.py:588).
- GQA (num_kv_heads < num_heads) supported throughout.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import paged_cache
from .layers import QuantizableDense


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    attn_implementation: str = "native"  # native | flash | ring | ulysses
    # explicit flash kernel tiling, pinned for BOTH passes (None = each pass
    # takes its own measured default, ops/flash_attention.default_block_sizes:
    # the forward's d>=128 clamp to block_q 512 dates from the two-kernel
    # backward that shared its tiles and overran the Mosaic scoped-VMEM limit
    # under remat; the one-pass backward runs (512, 512) and states its own
    # VMEM — sweep each pass with autotune_block_sizes before pinning)
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    remat: bool = False
    # remat granularity when remat=True: "full" recomputes everything
    # (minimum memory), "dots" saves matmul outputs (recompute only the cheap
    # elementwise ops — more memory, less recompute)
    remat_policy: str = "full"
    # lax.scan over the (homogeneous) layer stack instead of unrolling.
    # Param leaves gain a leading num_hidden_layers dim under "layers_scan".
    # This is what makes remat_policy="offload" actually pay: inside the
    # scan's sequential structure XLA transfers each boundary out of HBM
    # before the next iteration, where the unrolled stack's scheduler parks
    # ~5GiB of in-flight boundary buffers (the r2 131k blocker).  Also cuts
    # compile time at deep stacks (the body traces/compiles once).
    scan_layers: bool = False
    # layers per scan iteration: >1 offloads only every Nth boundary (the
    # blocks inside an iteration re-remat individually on backward), cutting
    # the pinned-host residual buffer by N.  Cost is quadratic in N: block
    # j's backward recomputes the chain 0..j from the iteration boundary,
    # i.e. (N-1)/2 extra forwards per block on average (measured: N=4 ran
    # 3x slower than N=1 at 112k) — use the smallest N that fits.  Must
    # divide num_hidden_layers.
    scan_block_size: int = 1
    # fraction of each offloaded boundary (along the sequence dim) that goes
    # to pinned host memory; the rest is SAVED IN DEVICE HBM.  <1.0 splits
    # the scan's stacked residual buffer between the two pools — the lever
    # when the HOST's pinned-allocation ceiling binds before device HBM does
    # (the measured situation at 131k on the bench rig: device 11.68 GiB
    # fits, 6.44 GiB pinned dies while 5.63 GiB runs — docs/long_context.md).
    # Only consulted by remat_policy="offload" under scan_layers.
    boundary_offload_fraction: float = 1.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.remat_policy not in ("full", "dots", "offload"):
            raise ValueError(
                f"remat_policy must be 'full', 'dots' or 'offload', got {self.remat_policy!r}"
            )
        if not 0.0 < self.boundary_offload_fraction <= 1.0:
            raise ValueError(
                f"boundary_offload_fraction={self.boundary_offload_fraction} "
                "must be in (0, 1] (1.0 = all boundaries pinned-host; smaller "
                "keeps the tail slice of each boundary in device HBM)"
            )
        if self.scan_block_size != 1:
            if not self.scan_layers:
                raise ValueError("scan_block_size > 1 requires scan_layers=True "
                                 "(the unrolled stack never consults it)")
            if self.scan_block_size < 1 or self.num_hidden_layers % self.scan_block_size:
                raise ValueError(
                    f"scan_block_size={self.scan_block_size} must divide "
                    f"num_hidden_layers={self.num_hidden_layers}"
                )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        """Test-scale config (toy fixture role, reference test_utils)."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama3_8b(cls, **kw):
        defaults = dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            rope_theta=500000.0, max_position_embeddings=8192,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama2_1b(cls, **kw):
        """~1.1B config (TinyLlama-style) — fits one v5e chip in bf16."""
        defaults = dict(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=22, num_attention_heads=32, num_key_value_heads=4,
            max_position_embeddings=2048,
        )
        defaults.update(kw)
        return cls(**defaults)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)
    return np.cos(freqs), np.sin(freqs)


def apply_rope(x, cos, sin, positions):
    """x: [B, T, H, D]; cos/sin: [max_len, D/2]; positions: [B, T]."""
    cos = cos[positions][:, :, None, :]  # [B, T, 1, D/2]
    sin = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def native_attention(q, k, v, *, causal: bool = True, segment_ids=None):
    """Reference-semantics attention, fp32 softmax, XLA-fused.

    q: [B, T, H, D]; k/v: [B, S, Hkv, D] (GQA broadcast here)."""
    b, t, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def get_attention_impl(name: str) -> Callable:
    if name == "native":
        return native_attention
    if name == "flash":
        from ..ops.flash_attention import mesh_flash_attention

        return mesh_flash_attention
    if name == "ring":
        from ..parallel.context_parallel import ring_attention

        return ring_attention
    if name == "ulysses":
        from ..parallel.sequence_parallel import ulysses_attention

        return ulysses_attention
    raise ValueError(f"unknown attention implementation {name!r}")


def _rows(x, tp_dim=None):
    """The layout the cache-free (training) path states for an activation:
    ``parallel/sharding.constrain_activation`` — rows over the batch axes,
    sequence over ``cp``/``sp``, ``tp_dim`` over ``tp`` — so that FSDP gathers
    weights and no activation crosses ``dp_shard``.  Not with the
    collective-matmul rings on: they hand their tensors over sequence- (or, at
    the Ulysses boundary, head-) sharded over the ring axis."""
    from ..ops.collective_matmul import collective_matmul_mode

    if collective_matmul_mode() != "off":
        return x
    from ..parallel.sharding import constrain_activation

    return constrain_activation(x, tp_dim)


def _as_is(x, tp_dim=None):
    """What the serving programs (``cache is not None``) get where the
    training path gets :func:`_rows`: no layout stated, their programs stay."""
    return x


# Sentinel position for unwritten / padding cache slots: larger than any real
# token position, so the causal comparison `kv_pos <= q_pos` excludes them.
CACHE_PAD_POSITION = np.int32(2**30)


def init_cache(config, batch_size: int, max_len: int, dtype=None):
    """Pre-allocated per-layer KV cache for autoregressive decoding.

    Each layer holds ``k``/``v`` [B, max_len, Hkv, D], per-slot global
    positions ``pos`` [B, max_len] (``CACHE_PAD_POSITION`` marks dead slots —
    the liveness mask is positional, so right-padded prompts and post-EOS
    slots are excluded the same way), and the scalar write ``index``.

    TPU-native analog of the engines' paged/contiguous KV caches the
    reference delegates generation to (big-model inference,
    reference big_modeling.py:513 + the reference's
    benchmarks/big_model_inference).
    """
    dtype = dtype or config.dtype
    hkv, d = config.num_key_value_heads, config.head_dim
    return [
        {
            "k": jnp.zeros((batch_size, max_len, hkv, d), dtype),
            "v": jnp.zeros((batch_size, max_len, hkv, d), dtype),
            "pos": jnp.full((batch_size, max_len), CACHE_PAD_POSITION, jnp.int32),
            "index": jnp.zeros((), jnp.int32),
        }
        for _ in range(config.num_hidden_layers)
    ]


def init_paged_cache(config, num_pages: int, page_size: int, num_slots: int,
                     pages_per_slot: int, dtype=None, kv_dtype=None):
    """Paged variant of :func:`init_cache` — the serving-core KV layout
    (vLLM PagedAttention discipline; see ``accelerate_tpu/serving/``).

    Instead of one dense ``[B, max_len]`` strip per sequence, K/V live in a
    **preallocated pool of fixed-size pages** shared by every sequence:

    - per layer: ``k_pages``/``v_pages`` ``[Hkv, num_pages, page_size, D]``
      (head-major so the Pallas paged-decode kernel's blocks keep a
      TPU-friendly ``(page_size, D)`` trailing tile);
    - ``block_tables`` ``[num_slots, pages_per_slot]`` int32 — slot *i*'s
      *j*-th logical page lives in physical page ``block_tables[i, j]``;
    - ``seq_lens`` ``[num_slots]`` int32 tokens written per slot (0 = dead);
    - ``free_stack``/``free_top`` — the device-side page allocator's free
      list (``ops/paged_cache.py`` pops/pushes it functionally, so the
      decode step stays jit- and donation-clean).

    Liveness is positional, like the dense cache: a kv index is visible to a
    query iff ``kv_index <= q_position``, and a slot's pages are only ever
    read up to its own ``seq_len`` — recycled pages never need zeroing.

    ``kv_dtype`` ``"int8"``/``"fp8"`` arms **quantized pages**: codes are
    stored at one byte per element and each layer additionally carries
    ``k_scales``/``v_scales`` ``[Hkv, num_pages]`` float32 — the per-(kv-head,
    page) running amax that is both the quantization scale and part of the
    page's content identity (the prefix cache folds the dtype into its hash
    chain, ``serving/prefix_cache.py``).  A scale of 0 marks a page with no
    quantized content yet; recycled pages are reset on their first
    (offset-0) write, so stale scales never leak across tenants.
    """
    dtype = dtype or config.dtype
    kv_dtype = paged_cache.resolve_kv_dtype(kv_dtype)
    hkv, d = config.num_key_value_heads, config.head_dim
    page_dtype = paged_cache.KV_QUANT_DTYPES[kv_dtype] if kv_dtype else dtype

    def layer():
        entry = {
            "k_pages": jnp.zeros((hkv, num_pages, page_size, d), page_dtype),
            "v_pages": jnp.zeros((hkv, num_pages, page_size, d), page_dtype),
        }
        if kv_dtype:
            entry["k_scales"] = jnp.zeros((hkv, num_pages), jnp.float32)
            entry["v_scales"] = jnp.zeros((hkv, num_pages), jnp.float32)
        return entry

    return paged_cache.init_paged_pools([layer() for _ in range(config.num_hidden_layers)],
                                        num_pages, num_slots, pages_per_slot)


def cached_attention(q, k_cache, v_cache, kv_positions, q_positions):
    """Decode-path attention against a pre-allocated KV cache.

    q: [B, T, H, D]; k_cache/v_cache: [B, S, Hkv, D]; kv_positions: [B, S]
    per-slot global positions (``CACHE_PAD_POSITION`` = dead slot);
    q_positions: [B, T].  The causal mask ``kv_pos <= q_pos`` doubles as the
    liveness mask.  Plain XLA einsum — at decode shapes (T=1..few) the op is
    HBM-bound on the cache read and fuses fine without the flash kernel.
    """
    b, t, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    if hkv != h:
        # grouped contraction keeps the cache read at kv-head width (no
        # materialized H-wide repeat in the decode loop's hot HBM path)
        g = h // hkv
        qg = q.reshape(b, t, hkv, g, d)
        scores = jnp.einsum("bthgd,bshd->bhgts", qg, k_cache).astype(jnp.float32) / np.sqrt(d)
        mask = kv_positions[:, None, None, None, :] <= q_positions[:, None, None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgts,bshd->bthgd", probs, v_cache)
        return out.reshape(b, t, h, d)
    scores = jnp.einsum("bthd,bshd->bhts", q, k_cache).astype(jnp.float32) / np.sqrt(d)
    mask = kv_positions[:, None, None, :] <= q_positions[:, None, :, None]  # [B,1,T,S]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v_cache)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None, cache_write_mask=None,
                 adapter_ids=None):
        cfg = self.config
        b, t = x.shape[:2]
        # Ulysses boundary as collective matmul: q/k/v fuse with all_to_all
        # #1 (ring all-gather->matmul over sp slices heads while gathering
        # the sequence) and o_proj with all_to_all #2 (ring matmul->reduce-
        # scatter back to sequence-sharded) — attention then runs with
        # heads pre-sharded.  Off (the default) or non-ulysses: the denses
        # ring over tp in their Megatron column/row roles.
        from ..ops.collective_matmul import ulysses_sp_boundary

        sp_boundary = (
            cfg.attn_implementation == "ulysses" and cache is None
            and ulysses_sp_boundary(cfg.num_attention_heads, cfg.num_key_value_heads, t)
        )
        ring_axis = "sp" if sp_boundary else "tp"
        dense = partial(QuantizableDense, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32)
        col = partial(dense, tp_mode="column", tp_axis=ring_axis)
        row = partial(dense, tp_mode="row", tp_axis=ring_axis)
        q = col(cfg.num_attention_heads * cfg.head_dim, name="q_proj")(x, adapter_ids)
        k = col(cfg.num_key_value_heads * cfg.head_dim, name="k_proj")(x, adapter_ids)
        v = col(cfg.num_key_value_heads * cfg.head_dim, name="v_proj")(x, adapter_ids)
        q = q.reshape(b, t, cfg.num_attention_heads, cfg.head_dim)
        k = k.reshape(b, t, cfg.num_key_value_heads, cfg.head_dim)
        v = v.reshape(b, t, cfg.num_key_value_heads, cfg.head_dim)
        if cache is None:
            # heads over tp: what mesh_flash_attention's qkv_spec asks for
            q, k, v = (_rows(a, tp_dim=2) for a in (q, k, v))

        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        if cache is not None and "k_pages" in cache:
            # paged serving path (serving/): write this chunk's K/V through
            # the block table, then attend ragged against the gathered pages.
            # Works for all three serving shapes — batched decode ([S, 1]),
            # a single sequence's chunked prefill ([1, C]), and the batched
            # speculative verify pass ([S, k+1]: multi-token paged append,
            # every lane's write routed through its own block-table column);
            # liveness stays the positional kv_pos <= q_pos comparison of
            # the dense path.
            page_size = cache["k_pages"].shape[2]
            pos_i32 = positions.astype(jnp.int32)
            # masked lanes (dead slots, prefill padding, rejected-draft
            # headroom past spec_len) may carry positions beyond the block
            # table — clamp the gather; the write itself is dropped below
            logical_page = jnp.clip(pos_i32 // page_size, 0,
                                    cache["block_tables"].shape[1] - 1)
            page_ids = jnp.take_along_axis(cache["block_tables"], logical_page, axis=1)
            if cache_write_mask is not None:
                # masked tokens (dead slots, prefill padding) write nowhere:
                # an out-of-bounds page id drops the scatter
                page_ids = jnp.where(cache_write_mask, page_ids,
                                     cache["k_pages"].shape[1])
            offsets = pos_i32 % page_size
            quantized = "k_scales" in cache
            if quantized:
                # int8/fp8 pages: quantize-on-write against the per-page
                # running amax; the kv dtype is recovered from the stored
                # code dtype so the trace stays argument-driven
                kv_dtype = ("int8" if cache["k_pages"].dtype == jnp.int8
                            else "fp8")
                k_pages, k_scales = paged_cache.paged_write_kv_quantized(
                    cache["k_pages"], cache["k_scales"], k, page_ids, offsets,
                    kv_dtype)
                v_pages, v_scales = paged_cache.paged_write_kv_quantized(
                    cache["v_pages"], cache["v_scales"], v, page_ids, offsets,
                    kv_dtype)
            else:
                kv_dtype, k_scales, v_scales = None, None, None
                k_pages = paged_cache.paged_write_kv(cache["k_pages"], k, page_ids, offsets)
                v_pages = paged_cache.paged_write_kv(cache["v_pages"], v, page_ids, offsets)
            if cfg.attn_implementation == "flash" and t == 1:
                # batched single-token decode: the Pallas paged kernel walks
                # each slot's pages through the block table (scalar-prefetch)
                # without materializing the gathered window
                from ..ops.flash_attention import paged_decode_attention

                out = paged_decode_attention(
                    q[:, 0], k_pages, v_pages, cache["block_tables"],
                    pos_i32[:, 0], k_scales=k_scales, v_scales=v_scales,
                )[:, None]
            elif cfg.attn_implementation == "flash" and t > 1:
                # multi-token paged attention (the speculative verify shape
                # [S, k+1] and chunked prefill [1, C]): the k+1-wide query
                # tile walks the same block-tables-as-scalar-prefetch grid
                from ..ops.flash_attention import paged_multitoken_attention

                out = paged_multitoken_attention(
                    q, k_pages, v_pages, cache["block_tables"], pos_i32,
                    k_scales=k_scales, v_scales=v_scales,
                )
            else:
                k_lin, v_lin, kv_pos = paged_cache.paged_gather_kv(
                    k_pages, v_pages, cache["block_tables"],
                    k_scales, v_scales, kv_dtype, cfg.dtype,
                )
                out = cached_attention(q, k_lin, v_lin, kv_pos, pos_i32)
            new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                         "block_tables": cache["block_tables"]}
            if quantized:
                new_cache["k_scales"] = k_scales
                new_cache["v_scales"] = v_scales
            out = out.reshape(b, t, cfg.num_attention_heads * cfg.head_dim)
            return row(cfg.hidden_size, name="o_proj")(out, adapter_ids), new_cache

        if cache is not None:
            # autoregressive path: write this chunk's K/V + positions at the
            # cache index, attend against the whole cache (the positional
            # comparison kv_pos <= q_pos masks dead slots and padding)
            idx = cache["index"]
            pos_write = positions.astype(jnp.int32)
            if cache_write_mask is not None:
                pos_write = jnp.where(cache_write_mask, pos_write, CACHE_PAD_POSITION)
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
            pos_cache = jax.lax.dynamic_update_slice(cache["pos"], pos_write, (0, idx))
            out = cached_attention(q, k_cache, v_cache, pos_cache, positions)
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache, "index": idx + t}
            out = out.reshape(b, t, cfg.num_attention_heads * cfg.head_dim)
            return row(cfg.hidden_size, name="o_proj")(out, adapter_ids), new_cache

        attn = get_attention_impl(cfg.attn_implementation)
        attn_kwargs = {}
        if cfg.attn_implementation == "flash" and cfg.flash_block_q is not None:
            attn_kwargs = {"block_q": cfg.flash_block_q,
                           "block_k": cfg.flash_block_k or cfg.flash_block_q}
        if sp_boundary:
            # q/k/v left the column rings head-sharded over sp at full
            # sequence; attention skips its entry/exit all_to_alls and the
            # o_proj row ring below scatters the sequence back
            attn_kwargs["heads_sharded"] = True
        out = attn(q, k, v, causal=True, segment_ids=segment_ids, **attn_kwargs)
        out = _rows(out.reshape(b, t, cfg.num_attention_heads * cfg.head_dim), tp_dim=-1)
        return row(cfg.hidden_size, name="o_proj")(out, adapter_ids)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, adapter_ids=None, rows: bool = False):
        """``rows``: the cache-free path's call — the MLP's width is stated
        over ``tp`` and its output in rows (:func:`_rows`)."""
        cfg = self.config
        pin = _rows if rows else _as_is
        dense = partial(QuantizableDense, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32)
        # Megatron roles for the collective-matmul ring over tp: gate/up
        # column-parallel (gather the sequence into the matmul), down
        # row-parallel (reduce-scatter the output back to sequence shards)
        gate = pin(dense(cfg.intermediate_size, name="gate_proj", tp_mode="column")(x, adapter_ids),
                   tp_dim=-1)
        up = pin(dense(cfg.intermediate_size, name="up_proj", tp_mode="column")(x, adapter_ids),
                 tp_dim=-1)
        return pin(dense(cfg.hidden_size, name="down_proj", tp_mode="row")(
            pin(nn.silu(gate) * up, tp_dim=-1), adapter_ids))


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None, cache_write_mask=None,
                 adapter_ids=None):
        cfg = self.config
        # the cache-free (training) path states its layout at the block's
        # boundaries; the serving programs keep theirs
        rows = cache is None
        pin = _rows if rows else _as_is
        x = pin(x)
        attn_in = pin(RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x))
        attn = LlamaAttention(cfg, name="self_attn")(attn_in, positions, segment_ids, cache,
                                                     cache_write_mask, adapter_ids)
        new_cache = None
        if cache is not None:
            attn, new_cache = attn
        h = pin(x + attn)
        out = pin(h + LlamaMLP(cfg, name="mlp")(
            pin(RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_attention_layernorm")(h)),
            adapter_ids, rows,
        ))
        if cache is not None:
            return out, new_cache
        return out


class _ScanBody(nn.Module):
    """One scan iteration over the homogeneous layer stack: carry is the
    hidden state, positions/segment_ids are broadcast.  The carry-in is
    tagged ``block_boundary`` so ``remat_policy="offload"`` can park the
    per-iteration residual in pinned host memory (under scan the stacked
    residual buffer itself lives host-side — the unrolled path's in-flight
    HBM pile-up cannot happen)."""

    config: Any
    block_cls: Any

    @nn.compact
    def __call__(self, x, positions, segment_ids):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        x = _rows(x)  # the carry, as an unrolled block's input
        frac = getattr(cfg, "boundary_offload_fraction", 1.0)
        if frac < 1.0 and cfg.remat and cfg.remat_policy == "offload":
            # hybrid boundary residency: the head slice of the sequence goes
            # to pinned host ("block_boundary", offloaded by the policy), the
            # tail slice stays in device HBM ("block_boundary_device", saved).
            # Slice sizes are static; align the split to 1024 tokens so the
            # D2H DMA stays on friendly tile boundaries (small sequences —
            # tests — align to 8 so the two-slice path is actually exercised).
            t = x.shape[1]
            align = 1024 if t >= 4096 else 8
            k = min(t, max(align, (int(t * frac) // align) * align))
            x_host = checkpoint_name(x[:, :k], "block_boundary")
            x_dev = checkpoint_name(x[:, k:], "block_boundary_device")
            x = jnp.concatenate([x_host, x_dev], axis=1) if k < t else x_host
        else:
            x = checkpoint_name(x, "block_boundary")
        bs = getattr(cfg, "scan_block_size", 1)
        if bs == 1:
            return self.block_cls(cfg, name="block")(x, positions, segment_ids), None
        # multi-block iteration: only the iteration boundary offloads; each
        # block re-remats individually on backward so the recompute peak
        # stays one block deep, honoring the configured remat granularity
        blk = self.block_cls
        if cfg.remat:
            policy = {
                "full": jax.checkpoint_policies.nothing_saveable,
                "offload": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[cfg.remat_policy]
            blk = nn.remat(blk, policy=policy)
        # NOTE (measured, r5): at long context XLA's latency-hiding
        # scheduler overlaps both blocks' recompute+backward live sets,
        # costing ~5 GB of device temps vs scan_block=1 at equal T.  An
        # inter-block optimization_barrier does NOT fix it (survives
        # tracing, no scheduling effect); compiling with
        # xla_tpu_enable_latency_hiding_scheduler=false does (temps return
        # to the sb=1 level — docs/long_context.md).
        for j in range(bs):
            x = blk(cfg, name=f"block_{j}")(x, positions, segment_ids)
        return x, None


class LMHead(nn.Module):
    """Vocab projection with params at ``lm_head/kernel`` (TP rule + ckpt
    path), computed in ``dtype`` with fp32 accumulation."""

    vocab_size: int
    dtype: Any

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        w = self.param(
            "kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.vocab_size), jnp.float32
        )
        w_c = w.astype(self.dtype)

        def head_dot(x):
            # fp32-accumulated vocab projection
            return jax.lax.dot_general(
                x, w_c, (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        if adapter_ids is not None and self.has_variable("lora", "a"):
            from ..ops.lora import lora_apply

            return lora_apply(
                x, head_dot(x), self.get_variable("lora", "a"),
                self.get_variable("lora", "b"), adapter_ids,
            )
        if x.ndim == 3:
            # column-parallel over tp (lm_head rule shards the vocab dim):
            # the ring gathers the sequence left tp-scattered by the last
            # block's row-parallel down_proj inside the head matmul
            from ..ops.collective_matmul import dense_collective_matmul

            y = dense_collective_matmul(
                x, w_c, "column", preferred_element_type=jnp.float32
            )
            if y is not None:
                return y
        return head_dot(x)


class LlamaForCausalLM(nn.Module):
    """Decoder LM head model.  ``__call__(input_ids) -> logits``.

    ``block_cls`` is the per-layer module — subclasses swap it to reuse the
    embed/decode/head skeleton (e.g. MixtralForCausalLM's sparse-MoE block).
    """

    config: LlamaConfig

    block_cls = LlamaBlock  # class attribute, not a dataclass field

    def init_paged_cache(self, num_pages: int, page_size: int, num_slots: int,
                         pages_per_slot: int, kv_dtype=None):
        """The serving engine's page pools for this family (the family
        protocol of ``serving/__init__.py``): K and V pages per layer."""
        return init_paged_cache(self.config, num_pages, page_size, num_slots, pages_per_slot,
                                kv_dtype=kv_dtype)

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, output_hidden: bool = False,
                 cache=None, cache_write_mask=None, adapter_ids=None):
        cfg = self.config
        if adapter_ids is not None and cfg.scan_layers:
            raise ValueError(
                "adapter_ids (multi-tenant LoRA) has no scan_layers path — "
                "the lora collection is per-layer; convert with "
                "unstack_layer_params + scan_layers=False (generation and "
                "the serving engine convert automatically)"
            )
        if positions is None:
            base = jnp.arange(input_ids.shape[1])
            if cache is not None:
                if "index" not in cache[0]:
                    raise ValueError(
                        "paged layer caches have no global write index — pass "
                        "explicit positions (the serving engine always does)"
                    )
                base = base + cache[0]["index"]
            positions = jnp.broadcast_to(base, input_ids.shape)
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32, name="embed_tokens"
        )
        x = embed(input_ids)
        if cache is None:
            x = _rows(x)
        block = type(self).block_cls
        offload_remat = False
        if cfg.remat and cache is None and cfg.remat_policy == "offload":
            from ..parallel.sharding import host_offload_supported

            offload_remat = host_offload_supported()
            if not offload_remat and not cfg.scan_layers:  # CPU mesh: full remat
                block = nn.remat(block, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat and cache is None and not cfg.scan_layers:
            policy = {
                "full": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[cfg.remat_policy]
            block = nn.remat(block, policy=policy)
        new_cache = [] if cache is not None else None
        if cfg.scan_layers and cache is not None:
            raise ValueError(
                "scan_layers=True has no cached-decode path (the KV cache is "
                "per-layer). generation.generate() converts automatically; "
                "for direct cached apply, convert once: "
                "params = unstack_layer_params(params) and rebuild the model "
                "with dataclasses.replace(cfg, scan_layers=False)."
            )
        if cfg.scan_layers and cache is None:
            # lax.scan over the stack: params stack under "layers_scan" with
            # a leading L dim (the sharding planner shifts TP rule dims for
            # this prefix).  With remat, the scan body is rematted with the
            # boundary-offload policy on TPU (MaxText-style: the stacked
            # boundary residuals live in pinned host memory) or
            # nothing_saveable/dots elsewhere.
            body = _ScanBody
            if cfg.remat:
                if offload_remat:
                    policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                        # "block_boundary_device" only exists when
                        # boundary_offload_fraction < 1 (hybrid residency)
                        names_which_can_be_saved=["block_boundary_device"],
                        names_which_can_be_offloaded=["block_boundary"],
                        offload_src="device", offload_dst="pinned_host",
                    )
                else:
                    policy = {
                        "full": jax.checkpoint_policies.nothing_saveable,
                        "offload": jax.checkpoint_policies.nothing_saveable,
                        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    }[cfg.remat_policy]
                body = nn.remat(body, policy=policy, prevent_cse=False)
            stack = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.num_hidden_layers // cfg.scan_block_size,
                in_axes=(nn.broadcast, nn.broadcast),
                metadata_params={nn.PARTITION_NAME: None},
            )
            x, _ = stack(cfg, block, name="layers_scan")(x, positions, segment_ids)
        elif offload_remat:
            # Activation offload (the ALST/Ulysses long-context enabler,
            # reference sequence_parallelism.md): one remat region over the
            # whole stack whose only saved values — the inter-block
            # activations — are offloaded to pinned host memory.  HBM holds
            # a couple of boundaries in flight instead of one per layer
            # (~6 GiB at 128k tokens); backward fetches them back over PCIe.
            from jax.ad_checkpoint import checkpoint_name

            # nested remat: the inner per-block remat keeps each block's
            # recomputed intermediates block-local during backward (without
            # it, XLA overlaps several layers' recomputes and the 1GiB MLP
            # intermediates stack up — measured OOM at 128k)
            inner = nn.remat(block, policy=jax.checkpoint_policies.nothing_saveable)

            def _stack(mdl, x, positions, segment_ids):
                for i in range(cfg.num_hidden_layers):
                    x = inner(cfg, name=f"layers_{i}")(x, positions, segment_ids)
                    x = checkpoint_name(x, "block_boundary")
                return x

            offload_policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["block_boundary"],
                offload_src="device", offload_dst="pinned_host",
            )
            x = nn.remat(_stack, policy=offload_policy)(self, x, positions, segment_ids)
        else:
            for i in range(cfg.num_hidden_layers):
                layer = block(cfg, name=f"layers_{i}")
                if cache is not None:
                    x, layer_cache = layer(x, positions, segment_ids, cache[i], cache_write_mask,
                                           adapter_ids)
                    new_cache.append(layer_cache)
                elif adapter_ids is not None:
                    # positional through any remat wrapper (kwargs and
                    # jax.checkpoint static handling don't always mix)
                    x = layer(x, positions, segment_ids, None, None, adapter_ids)
                else:
                    x = layer(x, positions, segment_ids)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        if cache is None:
            x = _rows(x)  # what the fused CE (or the head) is handed
        if output_hidden:
            # pre-head states for the fused linear+CE loss path (the vocab
            # projection happens inside the loss, chunked over the vocab)
            return (x, new_cache) if cache is not None else x
        # Head matmul in compute dtype with fp32 accumulation: an fp32 matmul
        # runs at a fraction of MXU rate, and with vocab-sized output this is
        # ~10% of the model's FLOPs — bf16 operands + preferred_element_type
        # keeps fp32 logits at native MXU speed.
        if cfg.tie_word_embeddings:
            head_w = embed.embedding.astype(cfg.dtype)  # [V, H]
            contract = (((x.ndim - 1,), (1,)), ((), ()))
            logits = jax.lax.dot_general(x, head_w, contract, preferred_element_type=jnp.float32)
        else:
            logits = LMHead(cfg.vocab_size, cfg.dtype, name="lm_head")(x, adapter_ids)
        return (logits, new_cache) if cache is not None else logits


def causal_lm_loss(logits, labels, ignore_index: int = -100, shifted: bool = False):
    """Shifted next-token cross-entropy (matches transformers CausalLM loss).

    Formulated as ``logsumexp - label_logit`` so the [B, T, V] log-softmax
    tensor is never materialized (one reduction pass over the vocab axis
    instead of a full fp32 logp array — vocab-sized HBM traffic halved).

    ``shifted=True`` means ``labels`` are already next-token aligned with
    ``logits`` position-by-position — REQUIRED under context parallelism,
    where the sequence is zigzag-sharded and "the next position" is not the
    next array index (reference context_parallelism.md:113-121: shift labels
    *before* sharding, pass as ``shift_labels``).
    """
    if shifted:
        logits = logits.astype(jnp.float32)
    else:
        logits = logits[:, :-1].astype(jnp.float32)
        labels = labels[:, 1:]
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logit
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def make_llama_loss_fn(model: LlamaForCausalLM, fused_vocab_chunks: Optional[int] = None):
    """Loss factory.  With ``fused_vocab_chunks`` set, the vocab projection
    moves inside a chunked fused linear+CE (ops/fused_xent.py) so the
    [B, T, V] logits tensor is never materialized — the activation-memory
    headroom this frees typically pays for a cheaper remat policy.  The
    chunks are that many pieces of the SEQUENCE, each against the whole
    vocabulary a device holds: one loop builds a chunk's logits once and,
    under ``jax.grad``, its ``dh`` rows and its term of ``dw`` from them in
    the same pass (three matmuls a chunk; the backward pass only scales).
    Under a mesh whose ``tp`` axis is wider than one and divides the
    vocabulary (the TP plan shards ``lm_head/kernel`` and the tied embedding
    along it), each ``tp`` shard reduces its own slice of the vocabulary:
    three [N / chunks] fp32 vectors a chunk and one fp32 [N, H] ``dh`` cross
    ``tp``, never the logits; any other mesh runs the bare loop under GSPMD.
    Either way the head is gathered over the FSDP axes once and ``dw``
    reduced over them once, after the loop."""
    if fused_vocab_chunks is None:
        def loss_fn(params, batch):
            logits = model.apply(params, batch["input_ids"], segment_ids=batch.get("segment_ids"))
            if "shift_labels" in batch:  # pre-shifted (the CP contract)
                return causal_lm_loss(logits, batch["shift_labels"], shifted=True)
            return causal_lm_loss(logits, batch["labels"])

        return loss_fn

    from ..ops.fused_xent import fused_causal_lm_loss

    cfg = model.config

    def fused_loss_fn(params, batch):
        hidden = model.apply(
            params, batch["input_ids"], segment_ids=batch.get("segment_ids"), output_hidden=True
        )
        inner = params.get("params", params)
        if cfg.tie_word_embeddings:
            weight = inner["embed_tokens"]["embedding"].astype(cfg.dtype)  # [V, H]
            vocab_major = True
        else:
            weight = inner["lm_head"]["kernel"].astype(cfg.dtype)  # [H, V]
            vocab_major = False
        shifted = "shift_labels" in batch  # pre-shifted (the CP contract)
        return fused_causal_lm_loss(
            hidden, weight, batch["shift_labels"] if shifted else batch["labels"],
            vocab_major=vocab_major, num_chunks=fused_vocab_chunks, shifted=shifted,
        )

    return fused_loss_fn


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


_LAYER_KEY = r"layers_(\d+)"


def stack_layer_params(params, scan_block_size: int = 1):
    """Convert unrolled per-layer params (``layers_0..layers_{L-1}``) to the
    ``scan_layers=True`` layout: ``layers_scan/block/...`` with a leading L
    dim (or ``layers_scan/block_j/...`` with a leading L/bs dim when
    ``scan_block_size=bs>1`` — global layer i maps to iteration i//bs, slot
    i%bs).  Accepts the tree with or without the flax ``params`` wrapper;
    checkpoints saved in either layout load into either model via this pair
    (reference parity: to-fsdp2-style state-dict converters)."""
    import re

    if "params" in params and isinstance(params["params"], dict):
        return {**params, "params": stack_layer_params(params["params"], scan_block_size)}
    layer_keys = sorted(
        (k for k in params if re.fullmatch(_LAYER_KEY, k)),
        key=lambda k: int(k.rsplit("_", 1)[1]),
    )
    if not layer_keys:
        return params
    bs = scan_block_size
    if len(layer_keys) % bs:
        raise ValueError(f"{len(layer_keys)} layers not divisible by scan_block_size={bs}")
    out = {k: v for k, v in params.items() if not re.fullmatch(_LAYER_KEY, k)}
    if bs == 1:
        out["layers_scan"] = {
            "block": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[params[k] for k in layer_keys]
            )
        }
    else:
        out["layers_scan"] = {
            f"block_{j}": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[params[k] for k in layer_keys[j::bs]]
            )
            for j in range(bs)
        }
    return out


def unstack_layer_params(params):
    """Inverse of :func:`stack_layer_params` (block size inferred from the
    stacked layout)."""
    if "params" in params and isinstance(params["params"], dict):
        return {**params, "params": unstack_layer_params(params["params"])}
    if "layers_scan" not in params:
        return params
    scan = params["layers_scan"]
    out = {k: v for k, v in params.items() if k != "layers_scan"}
    if "block" in scan:
        stacked = scan["block"]
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        for i in range(n):
            out[f"layers_{i}"] = jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
        return out
    bs = len(scan)
    n_iter = jax.tree_util.tree_leaves(scan["block_0"])[0].shape[0]
    for it in range(n_iter):
        for j in range(bs):
            out[f"layers_{it * bs + j}"] = jax.tree_util.tree_map(
                lambda x, it=it: x[it], scan[f"block_{j}"]
            )
    return out


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token ≈ 6*N + 12*L*H*D*T attention term (PaLM appendix
    formula).  The benchmark counts FLOPs with its own copy
    (``perfbench/rooflines/train_step.py``: the yardstick does not import
    the program)."""
    n_params = (
        cfg.vocab_size * cfg.hidden_size * (1 if cfg.tie_word_embeddings else 2)
        + cfg.num_hidden_layers * (
            cfg.hidden_size * cfg.head_dim * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads)
            + cfg.num_attention_heads * cfg.head_dim * cfg.hidden_size
            + 3 * cfg.hidden_size * cfg.intermediate_size
            + 2 * cfg.hidden_size
        )
        + cfg.hidden_size
    )
    attn_flops = 12 * cfg.num_hidden_layers * cfg.num_attention_heads * cfg.head_dim * seq_len
    return 6 * n_params + attn_flops
