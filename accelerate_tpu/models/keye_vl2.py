"""Keye-VL-2.0's language model: a decoder whose every layer is learned
sparse attention (an indexer picks ``sparse_topk`` keys per token,
``ops/sparse_attention.py``) followed by a dropless mixture of experts
(sorted dispatch + grouped matmul, ``parallel/expert_parallel.py``).

It shares ``RMSNorm``, ``LMHead`` and the serving engine with
``models/llama.py`` and differs in four ways the engine has to know about:

- ``head_dim`` is a field of the configuration (32 x 128 != hidden 2048);
- rotary angles are computed from the positions (no table of
  ``max_position_embeddings`` rows baked into a program), per frequency pair
  from the temporal, height or width position (``mrope_section``; positions
  ``[3, B, T]``; a text token has the three equal: plain RoPE);
- a layer keeps THREE page pools, ``k_pages`` / ``v_pages``
  ``[P, page, Hkv * D]`` and ``index_pages`` ``[P, page, 128]`` (the indexer's
  one key per token in the row's first ``Di`` lanes), page-major with a token's row contiguous, all addressed
  by the engine's one block table (:meth:`KeyeVL2ForCausalLM.init_paged_cache`);
- a paged call returns ``(logits, layers, counters)``: the int32 vector
  :attr:`KeyeVL2ForCausalLM.tick_counters` lays out, which the engine fetches
  with the tick's tokens.

Serving only: there is no loss for the indexer here, so no training path.
The vision tower is not built; what it asks of the language model — three
position axes and embeddings handed in instead of token ids — is supported
on the cache-free path (``inputs_embeds``, positions ``[3, B, T]``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import page_walk as pw
from ..ops import sparse_attention as sa
from ..ops.paged_cache import init_paged_pools, page_writer
from ..parallel.expert_parallel import grouped_ffn, route_dropless
from .layers import Float32Dense, Float32Out, apply_rotary, bias_free_proj, rotary_angles
from .llama import LMHead, RMSNorm


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """The language model's keys of the published ``config.json`` (the
    ``sa_config`` group flattened: ``indexer_*``, ``sparse_topk``,
    ``q_chunk_size`` / ``kv_chunk_size``, which are the source's tiling of
    the indexer scores and change no result)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    mrope_section: tuple = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    sparse_topk: int = 2048
    q_chunk_size: int = 512
    kv_chunk_size: int = 512
    tie_word_embeddings: bool = False
    # the global ids of the experts this program holds (None = all): data of
    # the layer, so that a chip's share routes over every expert and computes
    # its own experts' part
    experts_held: Optional[tuple] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise NotImplementedError("every layer is an expert layer here "
                                      "(decoder_sparse_step 1, mlp_only_layers [])")
        if self.indexer_num_kv_heads != 1:
            raise NotImplementedError("the indexer keeps one key head per token")
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(f"mrope_section {self.mrope_section} must cover head_dim / 2 "
                             f"= {self.head_dim // 2} frequency pairs")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")

    @property
    def held(self) -> tuple:
        return tuple(range(self.num_experts)) if self.experts_held is None \
            else tuple(self.experts_held)

    @classmethod
    def tiny(cls, **kw):
        """Test scale: 16 experts x 4 a token, ``sparse_topk`` 16."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, moe_intermediate_size=32, num_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512, mrope_section=(4, 6, 6),
            indexer_num_heads=4, indexer_head_dim=16, sparse_topk=16,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def keye_vl2_30b_a3b(cls, **kw):
        """https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B (config.json)."""
        return cls(**kw)


class KeyeVL2Indexer(nn.Module):
    """``qI [B, T, J, Di]``, ``w [B, T, J]`` and ``kI [B, T, Di]`` of the
    layer's normed input, float32: rotary on all ``Di`` dims at the model's
    theta and the text (temporal) position, no norm, no bias."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, text_positions):
        cfg = self.config
        b, t = x.shape[:2]
        j, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        proj = lambda n, name: Float32Dense(n, name=name)(x)
        ang = rotary_angles(text_positions, di, cfg.rope_theta)
        q_idx = apply_rotary(proj(j * di, "q_proj").reshape(b, t, j, di), ang)
        k_idx = apply_rotary(proj(di, "k_proj").reshape(b, t, 1, di), ang)[:, :, 0]
        return q_idx, proj(j, "weights_proj"), k_idx


class KeyeVL2Attention(nn.Module):
    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x32, positions, cache=None, cache_write_mask=None):
        """``x32``: the layer's normed input in float32 (the indexer reads it
        unrounded; the attention projections read it in ``dtype``)."""
        cfg = self.config
        b, t = x32.shape[:2]
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        text_positions = positions if positions.ndim == 2 else positions[0]
        x = x32.astype(cfg.dtype)
        q = bias_free_proj(h * d, cfg, "q_proj")(x).reshape(b, t, h, d)
        k = bias_free_proj(hkv * d, cfg, "k_proj")(x).reshape(b, t, hkv, d)
        v = bias_free_proj(hkv * d, cfg, "v_proj")(x).reshape(b, t, hkv, d)
        # per-head RMSNorm of q and k before the rotary (assumed: the
        # lineage's practice; the published config has no key for it)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        ang = rotary_angles(positions, d, cfg.rope_theta, cfg.mrope_section)
        q = apply_rotary(q, ang).astype(cfg.dtype)
        k = apply_rotary(k, ang).astype(cfg.dtype)
        with jax.named_scope("sparse_index"):
            q_idx, w_idx, k_idx = KeyeVL2Indexer(cfg, name="indexer")(x32, text_positions)
        o_proj = Float32Out(cfg.hidden_size, cfg.dtype, name="o_proj")

        if cache is None:
            out, selected = sa.dense_selected_attention(
                q, k, v, q_idx, w_idx, k_idx, text_positions, cfg.sparse_topk)
            self.sow("intermediates", "selected", selected)
            return o_proj(out.reshape(b, t, h * d)), None, None

        if positions.ndim != 2:
            raise NotImplementedError("the paged cache is addressed by one position per token")
        if b > 1 and t > 1:
            raise NotImplementedError("a paged call is a decode step [S, 1] or one prefill "
                                      "chunk [1, C]")
        page = cache["k_pages"].shape[1]
        pos = positions.astype(jnp.int32)
        live = jnp.ones((b, t), bool) if cache_write_mask is None else cache_write_mask
        tables = cache["block_tables"]
        with jax.named_scope("paged_write_kv"):
            write = page_writer(tables, pos, live, page)
            k_pages = write(cache["k_pages"], k.reshape(b, t, hkv * d))
            v_pages = write(cache["v_pages"], v.reshape(b, t, hkv * d))
            lanes = cache["index_pages"].shape[2]
            index_pages = write(cache["index_pages"],
                                jnp.pad(k_idx, ((0, 0), (0, 0), (0, lanes - k_idx.shape[2]))))
        q_pos = jnp.where(live, pos, -1)
        kv_len = jnp.max(q_pos) + 1
        bp = max(pw.block_pages_for(b, t, h, page),
                 pw.block_pages_for(b, t, cfg.indexer_num_heads, page))
        padded = pw.pad_block_tables(tables, bp)
        keys, threshold, ties = sa.index_keys(q_idx, w_idx, index_pages, padded, q_pos,
                                              cfg.sparse_topk, kv_len)
        out = sa.paged_selected_attention(q, k_pages, v_pages, padded, keys, threshold, ties,
                                          kv_len)
        counts = None
        if t == 1:      # decode: keys attended / keys visible, over the live slots
            counts = jnp.stack([jnp.sum(jnp.minimum(q_pos + 1, cfg.sparse_topk), dtype=jnp.int32),
                                jnp.sum(q_pos + 1, dtype=jnp.int32)])
        new_cache = {"k_pages": k_pages, "v_pages": v_pages, "index_pages": index_pages,
                     "block_tables": tables}
        return o_proj(out.reshape(b, t, h * d)), new_cache, counts


class KeyeVL2SparseMoE(nn.Module):
    """Router over all experts (float32 logits), the ``num_experts_per_tok``
    largest, gates renormalised over them, every routed token computed."""

    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x32, token_mask=None):
        """``x32``: the normed input in float32 (the router reads it
        unrounded; the experts read it in ``dtype``)."""
        cfg = self.config
        b, t, hid = x32.shape
        held, f = cfg.held, cfg.moe_intermediate_size
        logits = Float32Dense(cfg.num_experts, name="gate")(x32)
        x = x32.astype(cfg.dtype)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        experts = lambda name, shape: self.param(name, init, shape, jnp.float32).astype(cfg.dtype)
        w_gate = experts("experts_gate_proj", (len(held), hid, f))
        w_up = experts("experts_up_proj", (len(held), hid, f))
        w_down = experts("experts_down_proj", (len(held), f, hid))
        routing = route_dropless(
            logits.reshape(b * t, -1), cfg.num_experts_per_tok, held,
            normalize=cfg.norm_topk_prob,
            token_mask=None if token_mask is None else token_mask.reshape(-1))
        self.sow("intermediates", "experts", routing.experts)
        y = grouped_ffn(x.reshape(b * t, hid), routing, w_gate, w_up, w_down)
        return y.reshape(b, t, hid), routing.tokens_per_expert


class KeyeVL2Block(nn.Module):
    config: KeyeVL2Config

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_write_mask=None):
        cfg = self.config
        n = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="input_layernorm")(x)
        attn, new_cache, counts = KeyeVL2Attention(cfg, name="self_attn")(
            n, positions, cache, cache_write_mask)
        h = x + attn
        n = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="post_attention_layernorm")(h)
        moe, per_expert = KeyeVL2SparseMoE(cfg, name="mlp")(n, cache_write_mask)
        return h + moe, new_cache, counts, per_expert


class KeyeVL2ForCausalLM(nn.Module):
    """``__call__(input_ids | inputs_embeds, positions [B, T] or [3, B, T])``
    -> logits; with ``cache`` (the engine's per-layer page views) ->
    ``(logits, layers, counters)``."""

    config: KeyeVL2Config

    # what the serving engine cannot do for this family yet: it refuses at
    # construction rather than run without the indexer's pages
    serving_refuses = {
        "adapters": "LoRA adapters (ops/lora.py knows no expert or indexer projection)",
        "kv_dtype": "int8/fp8 KV pages (no quantized write for index_pages)",
        "speculate": "speculative verify (a [S, k+1] paged call selects nothing yet)",
        "prefix_cache": "prefix-cache hashing (index_pages are not part of a page's identity)",
        "hold_finished": "page transfer (serving/transfer.py moves k_pages and v_pages only)",
    }

    # a prefill chunk is written a page at a time (ops/paged_cache.write_chunk_pages)
    prefill_writes_whole_pages = True

    @property
    def tick_counters(self) -> tuple:
        """(name, length) of the int32 counters a paged call returns, in order."""
        return (("expert_tokens", self.config.num_experts), ("moe_experts_hit_sum", 1),
                ("moe_ticks", 1), ("sparse_selected_sum", 1), ("sparse_visible_sum", 1))

    def init_paged_cache(self, num_pages: int, page_size: int, num_slots: int,
                         pages_per_slot: int, kv_dtype=None):
        """This family's kind of per-token state: K and V pages and the
        indexer's key pages, one block table for all three."""
        if kv_dtype in ("int8", "fp8"):
            raise NotImplementedError(self.serving_refuses["kv_dtype"])

        cfg = self.config
        hkv, d = cfg.num_key_value_heads, cfg.head_dim
        layer = lambda: {
            "k_pages": jnp.zeros((num_pages, page_size, hkv * d), cfg.dtype),
            "v_pages": jnp.zeros((num_pages, page_size, hkv * d), cfg.dtype),
            # a row is whole 128-lane tiles: [.., Di = 64] would be padded to
            # the same bytes in HBM and relaid around every write
            "index_pages": jnp.zeros((num_pages, page_size, -(-cfg.indexer_head_dim // 128) * 128),
                                     cfg.dtype),
        }
        counters = sum(n for _, n in self.tick_counters)
        return init_paged_pools([layer() for _ in range(cfg.num_hidden_layers)], num_pages,
                                num_slots, pages_per_slot,
                                tick_counters=jnp.zeros((counters,), jnp.int32))

    @nn.compact
    def __call__(self, input_ids=None, positions=None, inputs_embeds=None, cache=None,
                 cache_write_mask=None, output_hidden: bool = False):
        cfg = self.config
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="embed_tokens")
        if (input_ids is None) == (inputs_embeds is None):
            raise ValueError("pass exactly one of input_ids and inputs_embeds")
        x = embed(input_ids) if inputs_embeds is None else inputs_embeds.astype(cfg.dtype)
        x = x.astype(jnp.float32)              # the residual stream (see layers.Float32Out)
        if positions is None:
            if cache is not None:
                raise ValueError("a paged call needs explicit positions")
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        new_layers, per_expert, decode_counts = [], [], []
        for i in range(cfg.num_hidden_layers):
            x, layer_cache, counts, experts = KeyeVL2Block(cfg, name=f"layers_{i}")(
                x, positions, None if cache is None else cache[i], cache_write_mask)
            new_layers.append(layer_cache)
            per_expert.append(experts)
            if counts is not None:       # a decode step: [experts hit, 1 (a layer-tick), selected, visible]
                hit = jnp.sum(experts > 0, dtype=jnp.int32)
                decode_counts.append(jnp.concatenate([hit[None], jnp.ones((1,), jnp.int32), counts]))
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        out = x if output_hidden else LMHead(cfg.vocab_size, cfg.dtype, name="lm_head")(x)
        if cache is None:
            return out
        return out, new_layers, jnp.concatenate(
            [sum(per_expert), sum(decode_counts) if decode_counts else jnp.zeros((4,), jnp.int32)])
