"""Olmo-Hybrid's decoder (``model_type: olmo_hybrid``): three Gated DeltaNet
layers (linear attention: one matrix a value head and sequence, whatever the
context's length) to every full softmax-attention layer, a dense SwiGLU MLP in
each, and the OLMo 2 block order: the norm sits on a sublayer's OUTPUT.

With ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, layer ``i`` of kind
``layer_types[i]``::

    h = x + rms(Mixer_i(x); w1);    y = h + rms(MLP(h); w2)
    MLP(h) = W_down (silu(W_gate h) * (W_up h));    logits = W_head rms(y_last; w_f)

- **full_attention** (``self_attn``): ``q = rms(W_q x; w_q)``, ``k = rms(W_k x;
  w_k)`` over ALL of a projection's channels, then split into heads; ``v = W_v
  x``; NO rotary (``rope_theta: null``: position comes from the linear layers);
  causal softmax of ``q . k / sqrt(D)`` through ``ops/flash_attention.py``;
  ``W_o``.
- **linear_attention** (``linear_attn``, the published Gated DeltaNet layer
  with separate projections): ``q = W_q x``, ``k = W_k x``, ``v = W_v x``, ``z
  = W_g x``, ``b = W_b x``, ``a = W_a x``; ``q, k, v <- silu(conv(.))``,
  depthwise, causal over ``linear_conv_kernel_dim`` positions, zeros before
  position 0; ``q <- l2norm(q) / sqrt(Dk)``, ``k <- l2norm(k)``; ``beta = 2
  sigmoid(b)`` (``linear_allow_neg_eigval``: a transition ``I - beta k k^T``
  may have a negative eigenvalue; ``sigmoid(b)`` when false), ``g = -exp(A_log)
  softplus(a + dt_bias)``; the gated delta rule from a zero state at position 0
  (``ops/gated_delta.gated_delta_chunk``: the chunked form, differentiable
  with a backward pass that keeps one state a block); out: ``W_o [rms(o; w_o)
  * silu(z)]`` with ``w_o`` of ``Dv`` shared by the heads.  What stands around
  the rule runs as two passes over the rows (``ops/delta_mixer.py``), each one
  Pallas kernel forward and one backward with its gradient by hand:
  ``conv_silu_l2norm`` takes the q, k and v projections' float32 outputs
  through conv, silu, the L2 norms and q's scale in one launch (each array
  read and written where it lies), ``gated_rmsnorm`` makes ``rms(o; w_o) *
  silu(z)`` and its one cast; each keeps only its inputs for the backward,
  which ``remat`` makes again anyway.

Precision: weights and the large matmuls' operands in ``dtype`` (bf16); the
residual stream, the norms, the conv, the L2 norms, ``beta``, ``g`` and the
whole rule in float32.

This is the TRAINING path (cache-free, every row one whole document from
position 0: ``segment_ids`` are refused, a packed row would need the recurrent
state and the conv window reset at each boundary).  Serving it needs the
family protocol of ``serving/__init__.py`` over this file (the per-slot state
of ``models/qwen3_next.py``); ``docs/supported_models.md`` says so.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import delta_mixer
from ..ops import gated_delta as gd
from ..ops.flash_attention import mesh_flash_attention
from .k_exaone import KExaoneMLP
from .layers import Float32Out, bias_free_proj
from .llama import LMHead, RMSNorm, _rows, make_llama_loss_fn

KINDS = ("linear_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """The published ``config.json``'s keys (``rope_parameters.rope_theta`` as
    ``rope_theta``), ``remat`` as ``LlamaConfig`` carries it, and ``dtype``."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: Optional[tuple] = None       # None: (linear x 3, full) repeated
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: Optional[float] = None
    tie_word_embeddings: bool = False
    remat: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.rope_theta is not None:
            raise NotImplementedError("the full-attention layers carry no rotary (rope_theta: null)")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")
        if len(self.kinds) != self.num_hidden_layers or set(self.kinds) - set(KINDS):
            raise ValueError(f"layer_types names {self.num_hidden_layers} layers, each one of {KINDS}")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise NotImplementedError("a key head serves the value head of its own index (30 of each)")
        if self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads divide the hidden size, KV heads the heads")

    @property
    def kinds(self) -> tuple:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple("full_attention" if (i + 1) % 4 == 0 else "linear_attention"
                     for i in range(self.num_hidden_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **kw):
        """Test scale: one period, 4 key and value heads of 8 x 16 (``Dk != Dv``)."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=4,
            num_attention_heads=2, num_key_value_heads=2, linear_num_key_heads=4,
            linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
            max_position_embeddings=512,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def olmo_hybrid_7b(cls, **kw):
        """https://huggingface.co/allenai/Olmo-Hybrid-7B (config.json)."""
        return cls(**kw)


class OlmoHybridAttention(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x32):
        cfg = self.config
        b, t = x32.shape[:2]
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        x = x32.astype(cfg.dtype)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(Float32Out(h * d, cfg.dtype, name="q_proj")(x))
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(Float32Out(hkv * d, cfg.dtype, name="k_proj")(x))
        v = bias_free_proj(hkv * d, cfg, "v_proj")(x)
        q, k, v = (_rows(a.reshape(b, t, -1, d), tp_dim=2) for a in (q, k, v))
        out = mesh_flash_attention(q, k, v, causal=True)
        return Float32Out(cfg.hidden_size, cfg.dtype, name="o_proj")(
            _rows(out.reshape(b, t, h * d), tp_dim=-1))


class _Scale(nn.Module):
    """A norm's weight under the name ``RMSNorm`` gives it (``<name>/scale``)."""

    @nn.compact
    def __call__(self, width):
        return self.param("scale", nn.initializers.ones, (width,), jnp.float32)


class OlmoHybridGatedDeltaNet(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x32):
        cfg = self.config
        b, t = x32.shape[:2]
        kh, vh = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, taps = cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim
        x = x32.astype(cfg.dtype)
        proj = lambda width, name: Float32Out(width, cfg.dtype, name=name)
        vector = lambda name: self.param(name, nn.initializers.zeros, (vh,), jnp.float32).astype(jnp.float32)
        with jax.named_scope("linear_project"):
            q, k, v = proj(kh * dk, "q_proj")(x), proj(kh * dk, "k_proj")(x), proj(vh * dv, "v_proj")(x)
            z = proj(vh * dv, "g_proj")(x)
            beta = jax.nn.sigmoid(proj(vh, "b_proj")(x)) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
            g = -jnp.exp(vector("A_log")) * jax.nn.softplus(proj(vh, "a_proj")(x) + vector("dt_bias"))
        conv = lambda a, name: self.param(name, nn.initializers.lecun_normal(), (taps, a.shape[-1]), jnp.float32)
        with jax.named_scope("linear_conv"):
            # one pass: the conv from zeros before position 0, silu, q's and k's L2 norms and q's ``Dk^-0.5``
            q, k, v = delta_mixer.conv_silu_l2norm(
                q, k, v, conv(q, "q_conv1d"), conv(k, "k_conv1d"), conv(v, "v_conv1d"), kh)
        o, _ = gd.gated_delta_chunk(q.reshape(b, t, kh, dk), k.reshape(b, t, kh, dk), v.reshape(b, t, vh, dv),
                                    g, beta, jnp.zeros((b, vh, dk, dv), jnp.float32))
        with jax.named_scope("linear_out"):
            weight = _Scale(name="o_norm")(dv)                                  # over Dv, one weight for all heads
            gated = delta_mixer.gated_rmsnorm(o.reshape(b, t, vh * dv), z, weight, cfg.rms_norm_eps, cfg.dtype)
        with jax.named_scope("linear_project"):
            return proj(cfg.hidden_size, "o_proj")(gated)


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        """``x``: the residual stream [B, T, H], float32."""
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, jnp.float32, name=name)
        x = _rows(x)
        if self.kind == "full_attention":
            mixed = OlmoHybridAttention(cfg, name="self_attn")(x)
        else:
            mixed = OlmoHybridGatedDeltaNet(cfg, name="linear_attn")(x)
        h = _rows(x + norm("post_attention_layernorm")(mixed))
        mlp = KExaoneMLP(cfg, cfg.intermediate_size, name="mlp")(h)
        return _rows(h + norm("post_feedforward_layernorm")(mlp))


class OlmoHybridForCausalLM(nn.Module):
    """``__call__(input_ids) -> logits``, or the final-normed states with
    ``output_hidden`` (what the fused CE is handed)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, segment_ids=None, output_hidden: bool = False):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError(
                "packed rows: the recurrent state and the conv window are not reset at a document's "
                "boundary; one document a row")
        x = _rows(nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
                           param_dtype=jnp.float32, name="embed_tokens")(input_ids))
        block = OlmoHybridBlock
        if cfg.remat:
            # all of a block is made again in the backward pass but the rule's triangular inverse (``T`` and
            # ``A``, 126 MB a layer at 8,192 tokens: what the rule's backward keeps anyway, a third of its forward)
            block = nn.remat(block, policy=jax.checkpoint_policies.save_only_these_names(gd.KEPT_ACROSS_REMAT))
        for i, kind in enumerate(cfg.kinds):
            x = block(cfg, kind, name=f"layers_{i}")(x)
        x = _rows(RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x))
        if output_hidden:
            return x
        return LMHead(cfg.vocab_size, cfg.dtype, name="lm_head")(x)


def make_olmo_hybrid_loss_fn(model: OlmoHybridForCausalLM, fused_vocab_chunks: Optional[int] = None):
    """The next-token loss of ``batch["labels"]``; with ``fused_vocab_chunks``
    the head moves inside the chunked linear + CE of ``ops/fused_xent.py``.
    ``make_llama_loss_fn``'s contract, word for word: this model answers the
    same call (``output_hidden``, an untied ``lm_head/kernel``)."""
    return make_llama_loss_fn(model, fused_vocab_chunks)
