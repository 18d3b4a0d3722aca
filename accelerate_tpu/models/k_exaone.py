"""K-EXAONE's language model (``model_type: exaone_moe``): a decoder that mixes
two kinds of attention layer and two kinds of MLP in one stack.

- ``layer_types``: ``sliding_attention`` layers attend the last
  ``sliding_window`` tokens, with rotary; ``full_attention`` layers attend the
  whole context, with NO rotary (EXAONE 4.0's hybrid rule).  Per-head RMSNorm
  of q and k in both.
- ``mlp_layer_types``: ``dense`` layers are one SwiGLU of ``intermediate_size``;
  ``sparse`` layers are ``num_experts`` routed SwiGLUs of
  ``moe_intermediate_size`` (sigmoid scores, the ``num_experts_per_tok`` of
  largest ``score + bias`` chosen, gates the unbiased scores renormalised over
  the chosen and scaled by ``routed_scaling_factor``; no capacity, no drop:
  ``parallel/expert_parallel.py``) beside ``num_shared_experts`` shared ones
  that every token visits.
- ``num_nextn_predict_layers``: a next-token-prediction module over the
  model's own hidden state (``W_p [norm(h_t); norm(Emb(x_{t+1}))]``, one
  full-attention sparse block, the shared final norm and head), on the
  cache-free path only.

**A share is configuration.**  ``experts_held`` (global ids),
``attention_heads_held`` / ``key_value_heads_held`` and ``vocab_held`` (counts)
say what of a layer THIS program holds when several chips share each layer
(tensor-parallel heads and vocabulary, expert-parallel experts): the model
builds exactly those weights (``q_proj`` ``[H, heads_held * D]``, experts
``[len(experts_held), ...]``, ``lm_head`` ``[H, vocab_held]``), the router
keeps every expert's output, gates are normalised over all the chosen, and
what absent heads and experts would add is left out: a partial result, with
no collective and nothing in its place on one chip.

**Two kinds of per-token state in one cache** (the family protocol of
``serving/__init__.py``).  A ``full_attention`` layer keeps K and V in pages
``[P, page, Hkv * D]`` under the engine's block table; a decode step reads
each slot's own pages once, to the slot's own length, inside one Pallas call a
layer (``paged_walk_decode``, ``ops/page_walk.py``), a prefill chunk
walks blocks of gathered pages (``ops/sparse_attention.py``, plain XLA).  A
``sliding_attention`` layer keeps, per SLOT, a ring of ``sliding_window`` rows
(``ops/window_attention.py``) outside the allocator: its bytes do not grow
with the context, and a slot is handed on without being cleared.  The engine
knows neither kind: a layer's view carries the block-table rows and the slot
ids, and each kind reads what it needs.

Serving and cache-free forwards only: there is no training path here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import page_walk as pw
from ..ops import window_attention as wa
from ..ops.paged_cache import init_paged_pools, page_writer
from ..parallel.expert_parallel import grouped_ffn, held_rows_fed, route_dropless
from .layers import Float32Dense, Float32Out, apply_rotary, bias_free_proj, rotary_angles
from .llama import LMHead, RMSNorm

_PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class KExaoneConfig:
    """The published ``config.json``'s keys, and what of a layer is held here."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[tuple] = None            # default: LLLG repeated
    mlp_layer_types: Optional[tuple] = None        # default: first_k_dense_replace dense, then sparse
    first_k_dense_replace: int = 1
    sliding_window: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    # the share (None = all): global ids of the experts, counts of the rest
    experts_held: Optional[tuple] = None
    attention_heads_held: Optional[int] = None
    key_value_heads_held: Optional[int] = None
    vocab_held: Optional[int] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError("group-limited routing (n_group / topk_group > 1)")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r}")
        kinds, mlps = self.kinds, self.mlps
        if len(kinds) < self.num_hidden_layers or len(mlps) < self.num_hidden_layers:
            raise ValueError("layer_types / mlp_layer_types are shorter than num_hidden_layers")
        if set(kinds) - {"sliding_attention", "full_attention"} or set(mlps) - {"dense", "sparse"}:
            raise ValueError(f"unknown layer kinds in {sorted(set(kinds) | set(mlps))}")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads held do not group over {self.kv_heads} KV heads")

    @property
    def kinds(self) -> tuple:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(_PERIOD[i % 4] for i in range(self.num_hidden_layers))

    @property
    def mlps(self) -> tuple:
        if self.mlp_layer_types is not None:
            return tuple(self.mlp_layer_types)
        return tuple("dense" if i < self.first_k_dense_replace else "sparse"
                     for i in range(self.num_hidden_layers))

    @property
    def held(self) -> tuple:
        return tuple(range(self.num_experts)) if self.experts_held is None \
            else tuple(self.experts_held)

    @property
    def heads(self) -> int:
        return self.attention_heads_held or self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.key_value_heads_held or self.num_key_value_heads

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @classmethod
    def tiny(cls, **kw):
        """Test scale: window 8, 16 experts x 4 a token, LLLG twice over."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            sliding_window=8, num_experts=16, num_experts_per_tok=4, max_position_embeddings=512,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def k_exaone_236b_a23b(cls, **kw):
        """https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B (config.json)."""
        return cls(**kw)


class KExaoneAttention(nn.Module):
    config: KExaoneConfig
    kind: str

    @nn.compact
    def __call__(self, x32, positions, cache=None, cache_write_mask=None):
        """``x32``: the layer's normed input (float32).  Returns ``(W_o of the
        HELD heads' attention [B, T, H] float32, the layer's new state, of a
        decode step the keys visible to its live queries — and beside them,
        from a full-attention layer, the rows of the pages read for them, int32
        [2] — or None)``.

        A full-attention layer's decode step ``[S, 1]`` is the
        ``paged_walk_decode`` Pallas kernel (``ops/page_walk.py``: each
        slot's own K and V pages once, to its own length); its prefill chunk
        ``[1, C]`` walks blocks of gathered pages
        (``ops/page_walk.paged_causal_attention``, plain XLA)."""
        cfg = self.config
        b, t = x32.shape[:2]
        h, hkv, d, window = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.sliding_window
        sliding = self.kind == "sliding_attention"
        x = x32.astype(cfg.dtype)
        q = bias_free_proj(h * d, cfg, "q_proj")(x).reshape(b, t, h, d)
        k = bias_free_proj(hkv * d, cfg, "k_proj")(x).reshape(b, t, hkv, d)
        v = bias_free_proj(hkv * d, cfg, "v_proj")(x).reshape(b, t, hkv, d)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        if sliding:            # rotary on window layers only
            ang = rotary_angles(positions, d, cfg.rope_theta)
            q = apply_rotary(q, ang).astype(cfg.dtype)
            k = apply_rotary(k, ang).astype(cfg.dtype)
        o_proj = Float32Out(cfg.hidden_size, cfg.dtype, name="o_proj")

        if cache is None:
            with jax.named_scope("window_attend" if sliding else "global_attend"):
                seen = positions[:, None, :] <= positions[:, :, None]
                if sliding:
                    seen &= positions[:, None, :] > positions[:, :, None] - window
                qg = q.reshape(b, t, hkv, h // hkv, d)
                out = wa.masked_attention(qg, k, v, seen, 1.0 / d ** 0.5)
            return o_proj(out.reshape(b, t, h * d).astype(cfg.dtype)), None, None

        if b > 1 and t > 1:
            raise NotImplementedError("a paged call is a decode step [S, 1] or one prefill "
                                      "chunk [1, C]")
        pos = positions.astype(jnp.int32)
        live = jnp.ones((b, t), bool) if cache_write_mask is None else cache_write_mask
        q_pos = jnp.where(live, pos, -1)
        flat = lambda a: a.reshape(b, t, hkv * d)
        if sliding:
            slots, old = cache["slots"], (cache["k_ring"], cache["v_ring"])

            def written():
                with jax.named_scope("paged_write_kv"):
                    write = wa.ring_writer(slots, pos, live, old[0].shape[1])
                    return write(old[0], flat(k)), write(old[1], flat(v))

            if t == 1:      # a decode step lands on the ring, then reads it
                k_ring, v_ring = written()
                out = wa.ring_decode_attention(q, k_ring, v_ring, slots, q_pos, window)
            else:           # a chunk reads the ring as the chunks before it left it, then lands
                out = wa.ring_chunk_attention(q, k, v, *old, slots[0], q_pos, window)
                k_ring, v_ring = written()
            state = {"k_ring": k_ring, "v_ring": v_ring}
            seen = jnp.sum(jnp.minimum(q_pos + 1, window), dtype=jnp.int32)
        else:
            tables, page = cache["block_tables"], cache["k_pages"].shape[1]
            with jax.named_scope("paged_write_kv"):
                write = page_writer(tables, pos, live, page)
                k_pages, v_pages = write(cache["k_pages"], flat(k)), write(cache["v_pages"], flat(v))
            if t == 1:      # each slot's own pages once, to its own length: one kernel
                with jax.named_scope("global_attend"):
                    out = pw.paged_walk_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                                         q_pos[:, 0])[:, None]
            else:           # a chunk's rows against blocks of gathered pages
                padded = pw.pad_block_tables(tables, pw.block_pages_for(b, t, h, page))
                out = pw.paged_causal_attention(q, k_pages, v_pages, padded, q_pos,
                                                jnp.max(q_pos) + 1)
            state = {"k_pages": k_pages, "v_pages": v_pages}
            walked = jnp.sum((q_pos + page) // page, dtype=jnp.int32) * page  # each slot's own pages
            seen = jnp.stack([jnp.sum(q_pos + 1, dtype=jnp.int32), walked])
        return o_proj(out.reshape(b, t, h * d)), state, (seen if t == 1 else None)


class KExaoneMLP(nn.Module):
    """One SwiGLU: bf16 operands, the down projection's float32 accumulator
    handed on.  The dense layers' MLP and the shared expert."""

    config: Any
    width: int

    @nn.compact
    def __call__(self, x32):
        cfg = self.config
        x = x32.astype(cfg.dtype)
        hidden = nn.silu(bias_free_proj(self.width, cfg, "gate_proj")(x)) \
            * bias_free_proj(self.width, cfg, "up_proj")(x)
        return Float32Out(cfg.hidden_size, cfg.dtype, name="down_proj")(hidden)


class KExaoneSparseMoE(nn.Module):
    """The shared expert over every token, plus the HELD routed experts' part:
    router over all ``num_experts`` (float32), selection by ``score + bias``,
    gates from the unbiased scores."""

    config: Any

    @nn.compact
    def __call__(self, x32, token_mask=None):
        cfg = self.config
        b, t, hid = x32.shape
        held, f = cfg.held, cfg.moe_intermediate_size
        logits = Float32Dense(cfg.num_experts, name="gate")(x32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros, (cfg.num_experts,),
                          jnp.float32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        experts = lambda name, shape: self.param(name, init, shape, jnp.float32).astype(cfg.dtype)
        w_gate = experts("experts_gate_proj", (len(held), hid, f))
        w_up = experts("experts_up_proj", (len(held), hid, f))
        w_down = experts("experts_down_proj", (len(held), f, hid))
        routing = route_dropless(
            logits.reshape(b * t, -1), cfg.num_experts_per_tok, held,
            normalize=cfg.norm_topk_prob, scoring=cfg.scoring_func, select_bias=bias,
            gate_scale=cfg.routed_scaling_factor,
            token_mask=None if token_mask is None else token_mask.reshape(-1))
        self.sow("intermediates", "experts", routing.experts)
        y = grouped_ffn(x32.astype(cfg.dtype).reshape(b * t, hid), routing, w_gate, w_up,
                        w_down).reshape(b, t, hid)
        if cfg.num_shared_experts:
            with jax.named_scope("moe_shared"):
                y = y + KExaoneMLP(cfg, cfg.num_shared_experts * f, name="shared_experts")(x32)
        return y, routing.group_sizes, held_rows_fed(routing)


class KExaoneBlock(nn.Module):
    """Pre-norm block: ``h = x + attn(norm(x))``, ``y = h + mlp(norm(h))``.
    ``attention``: the module class ``(config, kind)`` of the attention
    (another family's, e.g. ``models/joyai_flash.py``'s latent attention,
    whose configuration then answers to the names the MLPs here read)."""

    config: Any
    kind: str
    mlp: str
    attention: Any = KExaoneAttention

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_write_mask=None):
        cfg = self.config
        n = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="input_layernorm")(x)
        attn, state, seen = self.attention(cfg, self.kind, name="self_attn")(
            n, positions, cache, cache_write_mask)
        h = x + attn
        n = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="post_attention_layernorm")(h)
        if self.mlp == "dense":
            return h + KExaoneMLP(cfg, cfg.intermediate_size, name="mlp")(n), state, seen, None
        moe, per_expert, computed = KExaoneSparseMoE(cfg, name="mlp")(n, cache_write_mask)
        return h + moe, state, seen, (per_expert, computed)


class KExaoneMTP(nn.Module):
    """The next-token-prediction module (assumed DeepSeek-V3's form): from the
    model's hidden state at ``t`` and the embedding of token ``t + 1``, one
    full-attention sparse block whose output, through the model's own final
    norm and head, predicts token ``t + 2``."""

    config: Any
    kind: str = "full_attention"
    attention: Any = KExaoneAttention

    @nn.compact
    def __call__(self, hidden, next_embeds, positions):
        cfg = self.config
        joined = jnp.concatenate(
            [RMSNorm(cfg.rms_norm_eps, jnp.float32, name="hnorm")(hidden),
             RMSNorm(cfg.rms_norm_eps, jnp.float32, name="enorm")(next_embeds)], axis=-1)
        x = Float32Out(cfg.hidden_size, cfg.dtype, name="eh_proj")(joined)
        return KExaoneBlock(cfg, self.kind, "sparse", self.attention, name="block")(x, positions)[0]


class KExaoneForCausalLM(nn.Module):
    """``__call__(input_ids, positions [B, T])`` -> logits over the vocabulary
    rows held; with ``output_mtp`` -> ``(logits, mtp_logits [B, T - 1, V])``;
    with ``cache`` (the engine's per-layer views) -> ``(logits, layers,
    counters)``."""

    config: KExaoneConfig

    serving_refuses = {
        "adapters": "LoRA adapters (ops/lora.py knows no expert projection)",
        "kv_dtype": "int8/fp8 KV state (no quantized write for a window layer's ring)",
        "speculate": "speculative decode (no draft provider reads the served model's own hidden "
                     "state, which the next-token-prediction module needs, and a [S, k+1] paged "
                     "call has no ring rollback)",
        "prefix_cache": "prefix-cache hashing (a window layer's ring at a hit boundary is not "
                        "part of a page's identity and would have to be rebuilt)",
        "hold_finished": "page transfer (serving/transfer.py moves k_pages and v_pages only, "
                         "not a slot's rings)",
    }

    prefill_writes_whole_pages = True

    @property
    def tick_counters(self) -> tuple:
        """(name, length) of the int32 counters a paged call returns, in order.
        ``expert_tokens`` (rows routed to each HELD expert) counts every
        program; the rest count decode steps only: held experts with a row
        summed over the sparse layers, sparse layer-steps, rows routed to held
        experts and rows the grouped matmuls were fed, keys visible to the
        live queries in the full-attention layers and rows of the pages the
        ``paged_walk_decode`` kernel read for them (each live slot's own whole
        pages: ``(position // page + 1) * page``), keys visible in the window
        layers; the last three summed over the slots and the layers."""
        return (("expert_tokens", len(self.config.held)), ("moe_experts_hit_sum", 1),
                ("moe_ticks", 1), ("moe_rows_held", 1), ("moe_rows_computed", 1),
                ("global_visible_sum", 1), ("global_walked_sum", 1), ("window_visible_sum", 1))

    def _counters(self, per_expert, fed, seen, decode: bool):
        """The vector ``tick_counters`` lays out, from the layers' parts."""
        held = jnp.zeros((0, len(self.config.held)), jnp.int32)
        rows = jnp.stack(per_expert) if per_expert else held           # [sparse layers, E held]
        total = lambda parts, shape=(): sum(parts, jnp.zeros(shape, jnp.int32))
        steps = jnp.stack([jnp.sum(rows > 0), rows.shape[0], jnp.sum(rows), total(fed),
                           *total(seen["full_attention"], (2,)), total(seen["sliding_attention"])])
        return jnp.concatenate([jnp.sum(rows, axis=0),
                                steps.astype(jnp.int32) if decode else jnp.zeros_like(steps, jnp.int32)])

    def init_paged_cache(self, num_pages: int, page_size: int, num_slots: int,
                         pages_per_slot: int, kv_dtype=None):
        """Pages for the full-attention layers, a ring per slot for the window
        layers: the second is a slot-addressed kind of layer state."""
        if kv_dtype in ("int8", "fp8"):
            raise NotImplementedError(self.serving_refuses["kv_dtype"])
        cfg = self.config
        row = cfg.kv_heads * cfg.head_dim

        def layer(kind):
            if kind == "sliding_attention":
                ring = lambda: jnp.zeros((num_slots, cfg.sliding_window, row), cfg.dtype)
                return {"k_ring": ring(), "v_ring": ring()}
            pages = lambda: jnp.zeros((num_pages, page_size, row), cfg.dtype)
            return {"k_pages": pages(), "v_pages": pages()}

        counters = sum(n for _, n in self.tick_counters)
        return init_paged_pools([layer(k) for k in cfg.kinds[:cfg.num_hidden_layers]], num_pages,
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
        states, per_expert, fed, seen = [], [], [], {"full_attention": [], "sliding_attention": []}
        for i in range(cfg.num_hidden_layers):
            x, state, keys, moe = KExaoneBlock(cfg, cfg.kinds[i], cfg.mlps[i], name=f"layers_{i}")(
                x, positions, None if cache is None else cache[i], cache_write_mask)
            states.append(state)
            if moe is not None:
                per_expert.append(moe[0])
                fed.append(moe[1])
            if keys is not None:        # a decode step
                seen[cfg.kinds[i]].append(keys)
        norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")
        head = LMHead(cfg.vocab, cfg.dtype, name="lm_head")
        logits = head(norm(x))
        if cache is not None:
            return logits, states, self._counters(per_expert, fed, seen, decode=x.shape[1] == 1)
        if not output_mtp:
            return logits
        if not cfg.num_nextn_predict_layers:
            raise ValueError("this configuration has no next-token-prediction module")
        nxt = embed(input_ids[:, 1:]).astype(jnp.float32)
        y = KExaoneMTP(cfg, name="mtp")(x[:, :-1], nxt, positions[:, :-1])
        return logits, head(norm(y))
