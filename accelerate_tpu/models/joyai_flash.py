"""JoyAI-LLM-Flash's language model (``model_type: joyai_llm_flash``; every key
of its config is one of DeepSeek-V3's): multi-head LATENT attention in every
layer, a dense first layer, then sigmoid-routed experts beside a shared expert.

One layer's attention, with ``x`` the pre-normed input of the token at ``t``
and head ``h``::

    c_q = rms_norm(W_qa x) [q_lora_rank];  [qn_h ; qr_h] = W_qb,h c_q [nope ; rope];  qr_h <- rope(qr_h, t)
    [c ; kr] = W_kva x [kv_lora_rank ; rope];  c <- rms_norm(c);  kr <- rope(kr, t)   (ONE kr for all heads)
    [kn_h,s ; v_h,s] = W_kvb,h c_s [nope ; v];   W_kvb,h = [W_UK,h ; W_UV,h]
    score_h(t, s) = (qn_h,t . kn_h,s + qr_h,t . kr_s) / sqrt(nope + rope),  s <= t
    o_h,t = sum_s softmax_s(score_h(t, .)) v_h,s;   attn_t = W_o [o_1 .. o_H]

What a token leaves behind is its LATENT row ``[c ; kr]`` (512 + 64 values,
shared by every head), never per-head keys and values.  Two algebraically
equal walks read it, chosen by the call's shape and by nothing else:

- a decode step ``[S, 1]`` runs the ABSORBED form: ``qa_h = W_UK,h^T qn_h``
  [kv_lora_rank], ``score = (qa_h . c_s + qr_h . kr_s) * scale``, ``u_h =
  sum_s p_s c_s``, ``o_h = W_UV,h u_h`` — the row is the key (whole) and the
  value (its first ``kv_lora_rank`` values); no key or value head is built.
  The walk is ONE Pallas kernel a layer, ``latent_decode``
  (``ops/page_walk.latent_decode_attention``): each slot reads its
  own pages once, up to its own length, and nothing is gathered;
- a prefill chunk ``[1, C]`` runs the EXPANDED form: each gathered block of
  latent rows is up-projected by ``W_kvb`` of the held heads to per-head keys
  and values, then scored (the chunk's own rows, written first, included),
  through ``ops/page_walk.paged_masked_attention`` — the XLA walk the
  kernel is tested against.

The pool is ONE array a layer, ``[P, page, 640]`` at the published sizes: a
row is ``[c ; kr ; zeros]`` up to whole 128-lane tiles (a 576-wide row takes
the same bytes in HBM and is relaid around every write).

``rope_interleave``: the published rotary pairs dims ``(2i, 2i + 1)``.  The
program rotates halves (``(i, i + rope / 2)``) over weights whose rotary
columns are DE-INTERLEAVED (even dims first): the same scores, since ``qr``
and ``kr`` are permuted alike.  ``deinterleave_rope`` is that permutation;
``models/hf_interop.load_hf_joyai_flash`` and the benchmark's family adapter
apply it to ``q_b_proj`` and ``kv_a_proj_with_mqa``.

The MLPs, the block, the float32 residual stream and the
next-token-prediction wrapper are ``models/k_exaone.py``'s, by import.

**A share is configuration** (as in ``models/k_exaone.py``): ``experts_held``
(global ids), ``attention_heads_held`` and ``vocab_held`` (counts).  The model
builds exactly the held weights (``q_b_proj`` ``[q_lora_rank, heads_held x
192]``, ``kv_b_proj`` ``[kv_lora_rank, heads_held x 256]``, ``o_proj``,
experts, ``lm_head``); ``q_a_proj``, ``kv_a_proj_with_mqa``, both latent
norms, the router, the shared expert and the dense MLP are whole on every
chip.  There is no KV-head count to hold: a latent has no head axis to
divide, so every chip of a tensor-parallel group keeps the whole latent cache.

Serving and cache-free forwards only: there is no training path here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import page_walk as pw
from ..ops import window_attention as wa
from ..ops.paged_cache import init_paged_pools, page_writer
from .k_exaone import KExaoneBlock, KExaoneMTP
from .layers import Float32Out, apply_rotary, bias_free_proj, rotary_angles
from .llama import LMHead, RMSNorm


@dataclasses.dataclass(frozen=True)
class JoyAIFlashConfig:
    """The published ``config.json``'s keys, and what of a layer is held here."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    # the share (None = all): global ids of the experts, counts of the rest
    experts_held: Optional[tuple] = None
    attention_heads_held: Optional[int] = None
    vocab_held: Optional[int] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError("group-limited routing (n_group / topk_group > 1)")
        if self.rope_scaling is not None:
            raise NotImplementedError("rope_scaling (YaRN's factors and its softmax scale)")
        if self.moe_layer_freq != 1:
            raise NotImplementedError("every layer past first_k_dense_replace is sparse")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is pairs of dims")

    # the names models/k_exaone.py's MLPs and block read
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def num_shared_experts(self) -> int:
        return self.n_shared_experts

    @property
    def held(self) -> tuple:
        return tuple(range(self.n_routed_experts)) if self.experts_held is None \
            else tuple(self.experts_held)

    @property
    def heads(self) -> int:
        return self.attention_heads_held or self.num_attention_heads

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def mlps(self) -> tuple:
        return tuple("dense" if i < self.first_k_dense_replace else "sparse"
                     for i in range(self.num_hidden_layers))

    @property
    def latent_row(self) -> int:
        """Values a token leaves in a layer's pool: ``[c ; kr]``, padded with
        zeros to whole 128-lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @classmethod
    def tiny(cls, **kw):
        """Test scale: 4 heads of 16 + 8 (values 16) over a latent of 32, 16 experts x 4 a token."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def joyai_llm_flash(cls, **kw):
        """https://huggingface.co/jdopensource/JoyAI-LLM-Flash (config.json)."""
        return cls(**kw)


def deinterleave_rope(width: int) -> np.ndarray:
    """Column order that turns the published rotary pairing ``(2i, 2i + 1)``
    into the halves ``(i, i + width / 2)`` this program rotates: index ``j``
    of the result is the published dim it takes."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def deinterleave_rope_columns(kernel, head_width: int, rope: int):
    """``kernel`` [in, heads x head_width] with the last ``rope`` columns of
    every head in the order this program rotates (``q_b_proj``; ``kv_a_proj_with_mqa``
    is one "head" as wide as the kernel)."""
    cols = np.arange(kernel.shape[1]).reshape(-1, head_width)
    cols[:, -rope:] = cols[:, -rope:][:, deinterleave_rope(rope)]
    return kernel[:, cols.reshape(-1)]


class JoyAIFlashAttention(nn.Module):
    config: JoyAIFlashConfig
    kind: str = "latent_attention"

    @nn.compact
    def __call__(self, x32, positions, cache=None, cache_write_mask=None):
        """``x32``: the layer's normed input (float32).  Returns ``(W_o of the
        HELD heads' attention [B, T, H] float32, the layer's new state, int32
        [3]: keys visible to the live queries and rows of the pages the kernel
        read (a decode step), cached rows up-projected (a prefill chunk))``."""
        cfg = self.config
        b, t = x32.shape[:2]
        h, r = cfg.heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scale = 1.0 / (dn + dr) ** 0.5
        x = x32.astype(cfg.dtype)
        with jax.named_scope("latent_project"):
            c_q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_a_layernorm")(
                bias_free_proj(cfg.q_lora_rank, cfg, "q_a_proj")(x))
            q = bias_free_proj(h * (dn + dr), cfg, "q_b_proj")(c_q).reshape(b, t, h, dn + dr)
            kva = bias_free_proj(r + dr, cfg, "kv_a_proj_with_mqa")(x)
            c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_a_layernorm")(kva[..., :r])
            ang = rotary_angles(positions, dr, cfg.rope_theta)
            qn, qr = q[..., :dn], apply_rotary(q[..., dn:], ang).astype(cfg.dtype)
            kr = apply_rotary(kva[:, :, None, r:], ang)[:, :, 0].astype(cfg.dtype)
            w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(), (r, h * (dn + dv)),
                               jnp.float32).astype(cfg.dtype).reshape(r, h, dn + dv)
        o_proj = Float32Out(cfg.hidden_size, cfg.dtype, name="o_proj")

        def expanded(rows):
            """Latent rows [B, S, >= r + dr] -> the held heads' keys [B, S, H, dn + dr]
            and values [B, S, H, dv]."""
            kv = jnp.einsum("bsr,rhd->bshd", rows[..., :r], w_kvb)
            k_rot = jnp.broadcast_to(rows[:, :, None, r:r + dr], kv.shape[:3] + (dr,))
            return jnp.concatenate([kv[..., :dn], k_rot], axis=-1), kv[..., dn:]

        if cache is None:
            with jax.named_scope("latent_prefill"):
                k, v = expanded(jnp.concatenate([c, kr], axis=-1))
                seen = positions[:, None, :] <= positions[:, :, None]
                qh = jnp.concatenate([qn, qr], axis=-1)[:, :, :, None]
                out = wa.masked_attention(qh, k, v, seen, scale)[:, :, :, 0]
            return o_proj(out.reshape(b, t, h * dv).astype(cfg.dtype)), None, None

        if b > 1 and t > 1:
            raise NotImplementedError("a paged call is a decode step [S, 1] or one prefill "
                                      "chunk [1, C]")
        pos = positions.astype(jnp.int32)
        live = jnp.ones((b, t), bool) if cache_write_mask is None else cache_write_mask
        q_pos = jnp.where(live, pos, -1)
        kv_len = jnp.max(q_pos) + 1 if t > 1 else None     # where a chunk's walk ends
        tables, pool = cache["block_tables"], cache["latent_pages"]
        page, row = pool.shape[1:]
        with jax.named_scope("paged_write_kv"):
            pad = jnp.zeros((b, t, row - r - dr), cfg.dtype)
            pool = page_writer(tables, pos, live, page)(pool, jnp.concatenate([c, kr, pad], -1))
        zero = jnp.zeros((), jnp.int32)
        if t == 1:      # absorbed: the row is the key, whole, and in its first r values the value
            with jax.named_scope("latent_project"):
                qa = jnp.einsum("bthd,rhd->bthr", qn, w_kvb[..., :dn]).astype(cfg.dtype)
            with jax.named_scope("latent_attend"):
                u = pw.latent_decode_attention(qa[:, 0], qr[:, 0], pool, tables, q_pos[:, 0],
                                               scale=scale)[:, None]
            with jax.named_scope("latent_project"):
                out = jnp.einsum("bthr,rhd->bthd", u, w_kvb[..., dn:]).astype(cfg.dtype)
            walked = jnp.sum((q_pos + page) // page, dtype=jnp.int32) * page  # each slot's own pages
            counts = jnp.stack([jnp.sum(q_pos + 1, dtype=jnp.int32), walked, zero])
        else:           # expanded: each gathered block up-projected to the held heads' keys, values
            bp = pw.block_pages_for(b, t, h, page)
            padded = pw.pad_block_tables(tables, bp)
            walked = b * jnp.minimum((kv_len + bp * page - 1) // (bp * page),
                                     padded.shape[1] // bp) * (bp * page)
            with jax.named_scope("latent_prefill"):
                out = pw.paged_masked_attention(
                    jnp.concatenate([qn, qr], axis=-1), pool, None, padded, kv_len,
                    pw.causal_mask(q_pos), scale=scale, value_width=dv, expand=expanded)
            counts = jnp.stack([zero, zero, walked.astype(jnp.int32)])
        return o_proj(out.reshape(b, t, h * dv)), {"latent_pages": pool}, counts


class JoyAIFlashForCausalLM(nn.Module):
    """``__call__(input_ids, positions [B, T])`` -> logits over the vocabulary
    rows held; with ``output_mtp`` -> ``(logits, mtp_logits [B, T - 1, V])``;
    with ``cache`` (the engine's per-layer views) -> ``(logits, layers,
    counters)``."""

    config: JoyAIFlashConfig

    serving_refuses = {
        "adapters": "LoRA adapters (ops/lora.py knows no latent or expert projection)",
        "kv_dtype": "int8/fp8 KV state (no quantized write for a latent row, whose c and kr "
                    "parts want scales of their own)",
        "speculate": "speculative decode (no draft provider reads the served model's own hidden "
                     "state, which the next-token-prediction module needs, and a [S, k+1] paged "
                     "call is neither the absorbed nor the expanded walk)",
        "hold_finished": "page transfer (serving/transfer.py moves k_pages and v_pages only, "
                         "not latent_pages)",
    }

    prefill_writes_whole_pages = True

    @property
    def tick_counters(self) -> tuple:
        """(name, length) of the int32 counters a paged call returns, in order.
        ``expert_tokens`` (rows routed to each HELD expert) counts every
        program; ``latent_expanded_sum`` (cached rows a chunk's walk gathered
        and up-projected, summed over the layers) counts prefill chunks; the
        rest count decode steps only: held experts with a row summed over the
        sparse layers, sparse layer-steps, rows routed to held experts and rows
        the grouped matmuls were fed, keys visible to the live queries and rows
        of the pages the ``latent_decode`` kernel read for them (each live
        slot's own whole pages: ``(position // page + 1) * page``), both
        summed over the slots and the layers."""
        return (("expert_tokens", len(self.config.held)), ("moe_experts_hit_sum", 1),
                ("moe_ticks", 1), ("moe_rows_held", 1), ("moe_rows_computed", 1),
                ("latent_visible_sum", 1), ("latent_walked_sum", 1), ("latent_expanded_sum", 1))

    def init_paged_cache(self, num_pages: int, page_size: int, num_slots: int,
                         pages_per_slot: int, kv_dtype=None):
        """One pool of latent rows a layer under the engine's block table."""
        if kv_dtype in ("int8", "fp8"):
            raise NotImplementedError(self.serving_refuses["kv_dtype"])
        cfg = self.config
        layer = lambda: {"latent_pages": jnp.zeros((num_pages, page_size, cfg.latent_row),
                                                   cfg.dtype)}
        counters = sum(n for _, n in self.tick_counters)
        return init_paged_pools([layer() for _ in range(cfg.num_hidden_layers)], num_pages,
                                num_slots, pages_per_slot,
                                tick_counters=jnp.zeros((counters,), jnp.int32))

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_write_mask=None,
                 output_mtp: bool = False):
        cfg = self.config
        embed = nn.Embed(cfg.vocab, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="embed_tokens")
        x = embed(input_ids).astype(jnp.float32)       # the residual stream is float32
        if positions is None:
            if cache is not None:
                raise ValueError("a paged call needs explicit positions")
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        states, rows, fed, latent = [], [], [], jnp.zeros((3,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            x, state, counts, moe = KExaoneBlock(
                cfg, "latent_attention", cfg.mlps[i], JoyAIFlashAttention, name=f"layers_{i}")(
                    x, positions, None if cache is None else cache[i], cache_write_mask)
            states.append(state)
            if moe is not None:
                rows.append(moe[0])
                fed.append(moe[1])
            if counts is not None:
                latent = latent + counts
        norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")
        head = LMHead(cfg.vocab, cfg.dtype, name="lm_head")
        logits = head(norm(x))
        if cache is not None:
            rows = jnp.stack(rows) if rows else jnp.zeros((0, len(cfg.held)), jnp.int32)
            steps = jnp.stack([jnp.sum(rows > 0), rows.shape[0], jnp.sum(rows),
                               sum(fed, jnp.zeros((), jnp.int32))]).astype(jnp.int32)
            if x.shape[1] > 1:      # a prefill chunk counts its experts' rows and its walk only
                steps = jnp.zeros_like(steps)
            return logits, states, jnp.concatenate([jnp.sum(rows, axis=0), steps, latent])
        if not output_mtp:
            return logits
        if not cfg.num_nextn_predict_layers:
            raise ValueError("this configuration has no next-token-prediction module")
        nxt = embed(input_ids[:, 1:]).astype(jnp.float32)
        y = KExaoneMTP(cfg, "latent_attention", JoyAIFlashAttention, name="mtp")(
            x[:, :-1], nxt, positions[:, :-1])
        return logits, head(norm(y))
