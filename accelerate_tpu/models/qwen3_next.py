"""Qwen3-Next's language model (``model_type: qwen3_next``): three Gated
DeltaNet layers (linear attention: a recurrent state a sequence, not keys and
values a token) to every gated softmax-attention layer, and in every layer 512
softmax-routed experts, ten a token, beside a shared expert behind a sigmoid
gate of its own.

With ``rms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred: the
two block norms, the final norm, ``q_norm`` and ``k_norm``), layer ``i``::

    h = x + Mixer_i(rms(x; w1));    y = h + MoE(rms(h; w2))
    Mixer_i: full attention where (i + 1) % full_attention_interval == 0, Gated DeltaNet otherwise

- **Gated DeltaNet** (``linear_attn``): ``[q; k; v; z] = W_qkvz n``, laid out
  a key head's group after another (``[q 128; k 128; v 2 x 128; z 2 x 128]``
  where two value heads read each key head), ``[b; a] = W_ba n`` likewise;
  ``[q; k; v] <- silu(conv4([q; k; v]))``, depthwise and causal over the last
  four positions; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q <- l2norm(q) / sqrt(Dk)``, ``k <- l2norm(k)``; the gated
  delta rule (``ops/gated_delta.py``); out: ``W_o [rmsnorm(o; w) * silu(z)]``
  with a plain weight of ``Dv`` shared by the heads.
- **Gated attention** (``self_attn``): ``[q_h; gate_h] = W_q,h n``; per-head
  zero-centred RMS norm of q and k; rotary on the first ``partial_rotary_factor``
  of a head's dims (halves paired); causal softmax of ``q . k / sqrt(D)``;
  ``W_o [attn_h * sigmoid(gate_h)]``.
- **MoE** (``mlp``): ``p = softmax(W_r n)`` over all experts (float32),
  ``T = top-k(p)``, ``g_e = p_e / sum_T p``; ``sum_{e in T} g_e E_e(n) +
  sigmoid(w_s . n) E_shared(n)``; no capacity, no drop
  (``parallel/expert_parallel.py``).

**A share is configuration**, as in ``models/k_exaone.py``: ``experts_held``
(global ids) and counts of the attention heads, the KV heads, the Gated
DeltaNet key and value heads and the vocabulary rows held here.  The model
builds exactly those weights; the router, the shared expert and its gate are
whole on every chip; what absent heads and experts would add is left out.

**Three kinds of layer state in one cache** (the family protocol of
``serving/__init__.py``).  A full-attention layer keeps K and V in pages
``[P, page, Hkv * D]`` under the engine's block table (a decode step walks
each slot's own pages in the ``paged_walk_decode`` kernel, a prefill chunk
blocks of gathered pages).  A Gated DeltaNet layer keeps, per SLOT, its
recurrent ``state`` ``[Hv, Dk, Dv]`` in float32 and the ``conv`` window, the
last three rows of ``[q; k; v]`` before the convolution: bytes that do not
grow with the context.  That state is a sum over the whole past, so nothing
read later can mask a stale one out, and the engine clears nothing.  The
discipline is held HERE: **a call starts from zero wherever its first live
position is 0 and from the slot's stored state otherwise; a lane whose
``cache_write_mask`` is off leaves its state and its window as they were;
padded positions of a prefill bucket change nothing; what a chunk stores is
the state behind its last LIVE position and the window's last three live
rows.**  Eviction re-admits from position 0, so recompute rebuilds the state;
what would need a snapshot of it (a prefix-cache hit, a speculative rollback,
a page transfer) is refused by name.

Serving and cache-free forwards only: there is no training path here, and the
checkpoint's multi-token-prediction module is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import gated_delta as gd
from ..ops import page_walk as pw
from ..ops import window_attention as wa
from ..ops.paged_cache import init_paged_pools, page_writer
from ..parallel.expert_parallel import grouped_ffn, held_rows_fed, route_dropless
from .k_exaone import KExaoneMLP
from .layers import Float32Dense, Float32Out, apply_rotary, bias_free_proj, rotary_angles
from .llama import LMHead


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json``'s keys, and what of a layer is held here."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    # the share (None = all): global ids of the experts, counts of the rest
    experts_held: Optional[tuple] = None
    attention_heads_held: Optional[int] = None
    key_value_heads_held: Optional[int] = None
    linear_key_heads_held: Optional[int] = None
    linear_value_heads_held: Optional[int] = None
    vocab_held: Optional[int] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.decoder_sparse_step != 1 or tuple(self.mlp_only_layers):
            raise NotImplementedError("every layer is sparse (decoder_sparse_step 1, no mlp_only_layers)")
        if self.rope_scaling is not None:
            raise NotImplementedError("rope_scaling")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads held do not group over {self.kv_heads} KV heads")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError(f"{self.linear_value_heads} value heads held do not group over "
                             f"{self.linear_key_heads} key heads")
        if self.rotary_dim % 2:
            raise ValueError("the rotary part of a head is pairs of dims")

    @property
    def kinds(self) -> tuple:
        return tuple("full_attention" if (i + 1) % self.full_attention_interval == 0
                     else "linear_attention" for i in range(self.num_hidden_layers))

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
    def linear_key_heads(self) -> int:
        return self.linear_key_heads_held or self.linear_num_key_heads

    @property
    def linear_value_heads(self) -> int:
        return self.linear_value_heads_held or self.linear_num_value_heads

    @property
    def vocab(self) -> int:
        return self.vocab_held or self.vocab_size

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        """``[q; k; v]`` of the held heads: what the convolution runs over."""
        return 2 * self.linear_key_heads * self.linear_key_head_dim \
            + self.linear_value_heads * self.linear_value_head_dim

    @classmethod
    def tiny(cls, **kw):
        """Test scale: LLLG twice over, 4 key heads x 2 value heads of 16, 16 experts x 4 a token."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=4, linear_num_value_heads=8, num_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def qwen3_next_80b_a3b(cls, **kw):
        """https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct (config.json)."""
        return cls(**kw)


class ZeroCentredRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``, float32 inside."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (normed * (1.0 + weight)).astype(self.dtype)


def _paged_call(b: int, t: int):
    if b > 1 and t > 1:
        raise NotImplementedError("a paged call is a decode step [S, 1] or one prefill chunk [1, C]")


class Qwen3NextGatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x32, positions, cache=None, cache_write_mask=None):
        """``x32``: the layer's normed input (float32).  Returns ``(W_o of the
        HELD heads' output [B, T, H] float32, the layer's new state, int32 [2]:
        states updated by a decode step and states started from zero — or
        None)``."""
        cfg = self.config
        b, t = x32.shape[:2]
        kh, vh = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv, taps = cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim
        r = vh // kh                                        # value heads that read one key head
        x = x32.astype(cfg.dtype)
        with jax.named_scope("linear_project"):
            # a key head's group after another: [q dk; k dk; v r x dv; z r x dv], [b r; a r]
            qkvz = Float32Out(kh * (2 * dk + 2 * r * dv), cfg.dtype, name="in_proj_qkvz")(x)
            ba = Float32Out(kh * 2 * r, cfg.dtype, name="in_proj_ba")(x).reshape(b, t, kh, 2 * r)
            qkvz = qkvz.reshape(b, t, kh, 2 * dk + 2 * r * dv)
            mixed = jnp.concatenate(
                [qkvz[..., :dk].reshape(b, t, kh * dk), qkvz[..., dk:2 * dk].reshape(b, t, kh * dk),
                 qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, t, vh * dv)], axis=-1)
            z = qkvz[..., 2 * dk + r * dv:].reshape(b, t, vh, dv)
            conv_w = self.param("conv1d", nn.initializers.lecun_normal(),
                                (taps, cfg.conv_channels), jnp.float32)
            a_log = self.param("A_log", nn.initializers.zeros, (vh,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (vh,), jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, vh))
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., r:].reshape(b, t, vh) + dt_bias)
        weight = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)
        out_proj = Float32Out(cfg.hidden_size, cfg.dtype, name="out_proj")

        def heads_of(conved):
            """silu of the convolution's output -> q, k (normed, a key head's
            serving its value heads) [.., Hv, Dk] and v [.., Hv, Dv]."""
            y = jax.nn.silu(conved)
            lead = y.shape[:-1]
            q = gd.l2norm(y[..., :kh * dk].reshape(lead + (kh, dk))) * dk ** -0.5
            k = gd.l2norm(y[..., kh * dk:2 * kh * dk].reshape(lead + (kh, dk)))
            return (jnp.repeat(q, r, axis=-2), jnp.repeat(k, r, axis=-2),
                    y[..., 2 * kh * dk:].reshape(lead + (vh, dv)))

        def gated_out(o):
            normed = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                                       + cfg.rms_norm_eps) * weight
            return out_proj((normed * jax.nn.silu(z)).reshape(b, t, vh * dv).astype(cfg.dtype))

        if cache is None:       # every row a whole sequence from position 0
            zero_window = jnp.zeros((taps - 1, cfg.conv_channels), jnp.float32)
            zero_state = jnp.zeros((vh, dk, dv), jnp.float32)

            def one_row(mixed_row, g_row, beta_row):
                with jax.named_scope("linear_project"):
                    y, _ = gd.causal_conv_chunk(mixed_row, zero_window, conv_w, t)
                return gd.gated_delta_chunk(*heads_of(y), g_row, beta_row, zero_state)[0]

            return gated_out(jax.vmap(one_row)(mixed, g, beta)), None, None

        _paged_call(b, t)
        pos = positions.astype(jnp.int32)
        live = jnp.ones((b, t), bool) if cache_write_mask is None else cache_write_mask
        slots, state, window = cache["slots"], cache["state"], cache["conv"]
        if t == 1:              # one token a slot: the state read, corrected and written in place
            live, fresh = live[:, 0], live[:, 0] & (pos[:, 0] == 0)
            with jax.named_scope("linear_project"):
                old = window[slots]
                y, new = gd.causal_conv_step(mixed[:, 0], jnp.where(fresh[:, None, None], 0, old),
                                             conv_w)
                window = window.at[jnp.where(live, slots, window.shape[0])].set(new, mode="drop")
                q, k, v = heads_of(y)
            o, state = gd.gated_delta_step(q, k, v, g[:, 0], beta[:, 0], state, slots, live, fresh)
            counts = jnp.stack([jnp.sum(live), jnp.sum(fresh)]).astype(jnp.int32)
            return gated_out(o[:, None]), {"state": state, "conv": window}, counts
        # one chunk of one sequence: the chunked form from the slot's state, or from zero
        slot, fresh = slots[0], pos[0, 0] == 0
        length = jnp.sum(live[0].astype(jnp.int32))
        with jax.named_scope("linear_project"):
            at = (slot, 0, 0)
            old = jax.lax.dynamic_slice(window, at, (1,) + window.shape[1:])[0]
            y, new = gd.causal_conv_chunk(mixed[0], jnp.where(fresh, 0, old), conv_w, length)
            window = jax.lax.dynamic_update_slice(
                window, jnp.where(length > 0, new, old)[None], at)
            q, k, v = heads_of(y)
        at = (slot, 0, 0, 0)
        kept = jax.lax.dynamic_slice(state, at, (1,) + state.shape[1:])[0]
        o, s = gd.gated_delta_chunk(q, k, v, jnp.where(live[0, :, None], g[0], 0.0),
                                    jnp.where(live[0, :, None], beta[0], 0.0),
                                    jnp.where(fresh, 0.0, kept))
        state = jax.lax.dynamic_update_slice(state, jnp.where(length > 0, s, kept)[None], at)
        counts = jnp.stack([0, (fresh & (length > 0)).astype(jnp.int32)]).astype(jnp.int32)
        return gated_out(o[None]), {"state": state, "conv": window}, counts


class Qwen3NextAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x32, positions, cache=None, cache_write_mask=None):
        """``x32``: the layer's normed input (float32).  Returns ``(W_o of the
        HELD heads' gated attention [B, T, H] float32, the layer's new state,
        of a decode step int32 [2]: the keys visible to its live queries and
        the rows of the pages read for them — or None)``."""
        cfg = self.config
        b, t = x32.shape[:2]
        h, hkv, d, rot = cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.rotary_dim
        x = x32.astype(cfg.dtype)
        qg = bias_free_proj(h * 2 * d, cfg, "q_proj")(x).reshape(b, t, h, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = bias_free_proj(hkv * d, cfg, "k_proj")(x).reshape(b, t, hkv, d)
        v = bias_free_proj(hkv * d, cfg, "v_proj")(x).reshape(b, t, hkv, d)
        q = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        ang = rotary_angles(positions, rot, cfg.rope_theta)
        turn = lambda a: jnp.concatenate(
            [apply_rotary(a[..., :rot], ang).astype(cfg.dtype), a[..., rot:]], axis=-1)
        q, k = turn(q), turn(k)
        o_proj = Float32Out(cfg.hidden_size, cfg.dtype, name="o_proj")
        gated = lambda out: o_proj((out.reshape(b, t, h, d) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).reshape(b, t, h * d).astype(cfg.dtype))

        if cache is None:
            with jax.named_scope("global_attend"):
                seen = positions[:, None, :] <= positions[:, :, None]
                out = wa.masked_attention(q.reshape(b, t, hkv, h // hkv, d), k, v, seen,
                                          1.0 / d ** 0.5)
            return gated(out), None, None

        _paged_call(b, t)
        pos = positions.astype(jnp.int32)
        live = jnp.ones((b, t), bool) if cache_write_mask is None else cache_write_mask
        q_pos = jnp.where(live, pos, -1)
        tables, page = cache["block_tables"], cache["k_pages"].shape[1]
        flat = lambda a: a.reshape(b, t, hkv * d)
        with jax.named_scope("paged_write_kv"):
            write = page_writer(tables, pos, live, page)
            k_pages, v_pages = write(cache["k_pages"], flat(k)), write(cache["v_pages"], flat(v))
        state = {"k_pages": k_pages, "v_pages": v_pages}
        if t > 1:               # a chunk's rows against blocks of gathered pages
            padded = pw.pad_block_tables(tables, pw.block_pages_for(b, t, h, page))
            out = pw.paged_causal_attention(q, k_pages, v_pages, padded, q_pos, jnp.max(q_pos) + 1)
            return gated(out), state, None
        with jax.named_scope("global_attend"):      # each slot's own pages once: one kernel
            out = pw.paged_walk_decode_attention(q[:, 0], k_pages, v_pages, tables, q_pos[:, 0])
        walked = jnp.sum((q_pos + page) // page, dtype=jnp.int32) * page
        return gated(out[:, None]), state, jnp.stack([jnp.sum(q_pos + 1, dtype=jnp.int32), walked])


class Qwen3NextSparseMoE(nn.Module):
    """The gated shared expert over every token, plus the HELD routed experts'
    part: router over all ``num_experts`` (float32), softmax, top-k, gates
    renormalised over the chosen."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x32, token_mask=None):
        cfg = self.config
        b, t, hid = x32.shape
        held, f = cfg.held, cfg.moe_intermediate_size
        logits = Float32Dense(cfg.num_experts, name="gate")(x32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        experts = lambda name, shape: self.param(name, init, shape, jnp.float32).astype(cfg.dtype)
        w_gate = experts("experts_gate_proj", (len(held), hid, f))
        w_up = experts("experts_up_proj", (len(held), hid, f))
        w_down = experts("experts_down_proj", (len(held), f, hid))
        routing = route_dropless(
            logits.reshape(b * t, -1), cfg.num_experts_per_tok, held, normalize=cfg.norm_topk_prob,
            scoring="softmax", token_mask=None if token_mask is None else token_mask.reshape(-1))
        self.sow("intermediates", "experts", routing.experts)
        y = grouped_ffn(x32.astype(cfg.dtype).reshape(b * t, hid), routing, w_gate, w_up,
                        w_down).reshape(b, t, hid)
        with jax.named_scope("moe_shared"):
            shared = KExaoneMLP(cfg, cfg.shared_expert_intermediate_size, name="shared_expert")(x32)
            y = y + jax.nn.sigmoid(Float32Dense(1, name="shared_expert_gate")(x32)) * shared
        return y, routing.group_sizes, held_rows_fed(routing)


class Qwen3NextBlock(nn.Module):
    """Pre-norm block: ``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``."""

    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_write_mask=None):
        cfg = self.config
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, jnp.float32, name=name)
        mixer = Qwen3NextAttention(cfg, name="self_attn") if self.kind == "full_attention" \
            else Qwen3NextGatedDeltaNet(cfg, name="linear_attn")
        mixed, state, counts = mixer(norm("input_layernorm")(x), positions, cache, cache_write_mask)
        h = x + mixed
        moe, per_expert, computed = Qwen3NextSparseMoE(cfg, name="mlp")(
            norm("post_attention_layernorm")(h), cache_write_mask)
        return h + moe, state, counts, (per_expert, computed)


class Qwen3NextForCausalLM(nn.Module):
    """``__call__(input_ids, positions [B, T])`` -> logits over the vocabulary
    rows held; with ``cache`` (the engine's per-layer views) -> ``(logits,
    layers, counters)``."""

    config: Qwen3NextConfig

    serving_refuses = {
        "adapters": "LoRA adapters (ops/lora.py knows no expert or fused linear-attention projection)",
        "kv_dtype": "int8/fp8 KV state (no quantized form of a recurrent state, which is a sum "
                    "over the whole context)",
        "speculate": "speculative decode (a [S, k+1] paged call would have to roll the recurrent "
                     "state back to the accepted position, and no snapshot of it is kept)",
        "prefix_cache": "prefix-cache hashing (a hit would have to start from the recurrent state "
                        "at the hit boundary, and no snapshot of it is kept with the pages)",
        "hold_finished": "page transfer (serving/transfer.py moves k_pages and v_pages only, "
                         "not a slot's recurrent state and conv window)",
    }

    prefill_writes_whole_pages = True

    @property
    def tick_counters(self) -> tuple:
        """(name, length) of the int32 counters a paged call returns, in order.
        ``expert_tokens`` (rows routed to each HELD expert) and
        ``linear_resets`` (slot-layer recurrent states started from zero: a
        prefill chunk at position 0, a Gated DeltaNet layer) count every
        program; the rest count decode steps only: held experts with a row
        summed over the layers, layer-steps, rows routed to held experts and
        rows the grouped matmuls were fed, keys visible to the live queries in
        the full-attention layers and rows of the pages the
        ``paged_walk_decode`` kernel read for them, and ``linear_steps``
        (slot-layer recurrent states a decode step updated)."""
        return (("expert_tokens", len(self.config.held)), ("moe_experts_hit_sum", 1),
                ("moe_ticks", 1), ("moe_rows_held", 1), ("moe_rows_computed", 1),
                ("global_visible_sum", 1), ("global_walked_sum", 1), ("linear_steps", 1),
                ("linear_resets", 1))

    def init_paged_cache(self, num_pages: int, page_size: int, num_slots: int,
                         pages_per_slot: int, kv_dtype=None):
        """Pages for the full-attention layers; a recurrent state (float32)
        and a conv window per slot for the Gated DeltaNet layers: the second
        is a slot-addressed kind of layer state."""
        if kv_dtype in ("int8", "fp8"):
            raise NotImplementedError(self.serving_refuses["kv_dtype"])
        cfg = self.config

        def layer(kind):
            if kind == "full_attention":
                pages = lambda: jnp.zeros((num_pages, page_size, cfg.kv_heads * cfg.head_dim),
                                          cfg.dtype)
                return {"k_pages": pages(), "v_pages": pages()}
            return {"state": jnp.zeros((num_slots, cfg.linear_value_heads, cfg.linear_key_head_dim,
                                        cfg.linear_value_head_dim), jnp.float32),
                    "conv": jnp.zeros((num_slots, cfg.linear_conv_kernel_dim - 1,
                                       cfg.conv_channels), cfg.dtype)}

        counters = sum(n for _, n in self.tick_counters)
        return init_paged_pools([layer(k) for k in cfg.kinds], num_pages, num_slots,
                                pages_per_slot, tick_counters=jnp.zeros((counters,), jnp.int32))

    @nn.compact
    def __call__(self, input_ids, positions=None, cache=None, cache_write_mask=None):
        cfg = self.config
        embed = nn.Embed(cfg.vocab, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="embed_tokens")
        x = embed(input_ids).astype(jnp.float32)       # the residual stream is float32
        if positions is None:
            if cache is not None:
                raise ValueError("a paged call needs explicit positions")
            positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        states, rows, fed = [], [], []
        counts = {"full_attention": jnp.zeros((2,), jnp.int32),
                  "linear_attention": jnp.zeros((2,), jnp.int32)}
        for i, kind in enumerate(cfg.kinds):
            x, state, seen, (per_expert, computed) = Qwen3NextBlock(cfg, kind, name=f"layers_{i}")(
                x, positions, None if cache is None else cache[i], cache_write_mask)
            states.append(state)
            rows.append(per_expert)
            fed.append(computed)
            if seen is not None:
                counts[kind] = counts[kind] + seen
        logits = LMHead(cfg.vocab, cfg.dtype, name="lm_head")(
            ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x))
        if cache is None:
            return logits
        rows = jnp.stack(rows)                                          # [layers, E held]
        steps = jnp.stack([jnp.sum(rows > 0), rows.shape[0], jnp.sum(rows), sum(fed),
                           *counts["full_attention"], counts["linear_attention"][0]])
        if x.shape[1] > 1:      # a prefill chunk counts its experts' rows and its resets only
            steps = jnp.zeros_like(steps)
        return logits, states, jnp.concatenate(
            [jnp.sum(rows, axis=0), steps.astype(jnp.int32), counts["linear_attention"][1:]])
