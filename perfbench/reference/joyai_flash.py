"""Plain reference for the ``joyai_flash`` model family (JoyAI-LLM-Flash's
language model, ``model_type: joyai_llm_flash``: DeepSeek-V3's layer), given
the SAME share of each layer as the program: straight ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``, the EXPANDED equations, no
cache, no absorption, no kernels, nothing imported from ``accelerate_tpu``.
The expert layer, the SwiGLUs, the tie rule and the controls' rounding are
``reference/k_exaone.py``'s, by import (the same arithmetic: sigmoid scores,
top-8 of score + bias, gates 2.5 s_e / sum s, a shared expert).

One layer ``l`` (pre-norm, eps 1e-6), ``n = rms_norm(x)``, head ``h`` of the HELD heads::

    h = x + Attn_l(n);   y = h + MLP_l(rms_norm(h));   final rms_norm, untied head
    c_q = rms_norm(W_qa n) [1536];   [qn_h ; qr_h] = W_qb,h c_q [128 ; 64];   qr_h <- rope(qr_h, t)
    [c ; kr] = W_kva n [512 ; 64];   c <- rms_norm(c);   kr <- rope(kr, t)        (one kr for all heads)
    [kn_h,s ; v_h,s] = W_kvb,h c_s [128 ; 128]
    score_h(t, s) = (qn_h,t . kn_h,s + qr_h,t . kr_s) / sqrt(192),  s <= t
    o_t = W_o concat_h sum_s softmax_s(score_h(t, .)) v_h,s
    rope: theta 32e6 over the 64 dims, the PUBLISHED pairing (2i, 2i + 1) (``rope_interleave``); no YaRN
    dense MLP (layer 0):  W_down (silu(W_gate n) * W_up n), width 7,168
    sparse MLP (layers >= 1):  s = sigmoid(W_r n) [256];  T = top8(s + b);  g_e = 2.5 s_e / sum_{j in T} s_j
        MLP(n) = E_shared(n) + sum_{e in T, e held here} g_e E_e(n)      (SwiGLUs of width 768)
    MTP (cache-free tests only; weights ``mtp.*``): h'_t = W_p [rms_norm(h_t); rms_norm(Emb(x_{t+1}))],
        one such layer (sparse MLP), the model's final norm and head: logits for x_{t+2}

**The share.**  The configuration's top-level ``num_attention_heads``,
``n_routed_experts`` and ``vocab_size`` are what is HELD (rank 0 of eight
chips that share each layer); ``published`` has the model's own counts and
``share.experts_held`` the global ids.  ``W_qb``, ``W_kvb`` and ``W_o`` are the
held heads' columns and rows; ``W_qa``, ``W_kva``, both latent norms, the
router (256 outputs), the shared expert and the dense MLP are whole.

Weights are the benchmark's own (``perfbench/weights.py``), a flat dict, every
matrix ``[in, out]`` in the PUBLISHED column order (rotary dims interleaved):
``embed [V,H]``, ``layers.<i>.{attn_norm [H], q_a [H,1536], q_a_norm [1536],
q_b [1536, heads x 192], kv_a [H,576], kv_a_norm [512], kv_b [512, heads x
256], o [heads x 128, H], mlp_norm [H]}``, then the MLP leaves as
``reference/k_exaone.py`` names them; ``final_norm [H]``, ``head [H,V]``.

``forward_logits`` returns an object, not an array: ``logits[row, span]`` runs
that row up to ``span.stop`` (``RowLogits``).  Positions whose routing is a
TIE (``assumed.tie_margin``) read flat, as in ``reference/k_exaone.py``.

``quant`` is the CONTROL (``"int8"`` / ``"fp8"``: both operands of every dense
matmul).  The names of ``FAULTS`` in its place plant ONE fault in a float32
forward (``prove.py --control rope,attn_scale,...``).

How the reference blocks its work: as ``reference/k_exaone.py`` (one length a
call, 512 queries at a time against the whole row's expanded keys, SwiGLUs
2,048 columns at a time, routed experts one at a time).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import k_exaone as kx
from perfbench.reference.k_exaone import (Q_BLOCK, ROW_ROUND, TIE_MARGIN, angles, cut_length,
                                          dense, rms_norm)

ATTN_KEYS = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o", "mlp_norm")
FAULTS = {
    "rope": "the rotary part of the score dropped (qn . kn alone)",
    "attn_scale": "scores scaled by 1/sqrt(576), the row's width, in place of 1/sqrt(192)",
    "kv_norm": "kv_a_layernorm skipped: the latent is stored and up-projected unnormed",
    "kr_raw": "kr kept BEFORE its rotary (queries are rotated, the stored keys are not)",
    "value_slice": "the value read 64 values off in the row [c ; kr]: its LAST 512 values in place "
                   "of its first 512 (the nearest computable reading of 'the value read from the "
                   "whole row': W_UV takes 512 inputs)",
    "blind": "a query sees the keys of its own prefill chunk only (assumed.prefill_chunk "
             "positions): the expanded walk blind to the rows cached before the chunk",
    "expert": "held expert 7 adds nothing, as a grouped matmul that loses one group would",
    "bias": "the selection ignores the bias b (top-8 of the scores alone)",
    "gate_scale": "gate scale 1.0 in place of routed_scaling_factor 2.5",
    "shared": "the shared expert adds nothing",
    "share": "rows routed to ABSENT experts are multiplied by held experts' weights (expert e by "
             "held e % 32), as a grouped matmul that does not stop at the held rows would",
}
NO_FAULT = np.zeros((len(FAULTS),), bool)
# this family's MLP faults under the names reference/k_exaone.py's expert layer reads
_KX_NAME = {"gate_scale": "scale"}


def split_control(quant):
    """A control's name -> (the precision of the matmuls, the planted faults' flags)."""
    if quant in FAULTS:
        return None, np.arange(len(FAULTS)) == list(FAULTS).index(quant)
    return quant, NO_FAULT


def _kx_flags(flags):
    """The flags ``reference/k_exaone.py``'s expert layer takes, from this family's."""
    mine = dict(zip(FAULTS, np.asarray(flags)))
    named = {_KX_NAME.get(k, k): v for k, v in mine.items()}
    return np.asarray([bool(named.get(k, False)) for k in kx.FAULTS])


def rope_pairs(x, ang):
    """x [T, heads, D], angles [T, D/2]; the published pairing: dims (2i, 2i + 1)."""
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def held_experts(cfg) -> tuple:
    share = cfg.get("share") or {}
    return tuple(share.get("experts_held", range(cfg["n_routed_experts"])))


def cfg_key(cfg):
    assumed = cfg.get("assumed") or {}
    return (("heads", cfg["num_attention_heads"]), ("q_rank", cfg["q_lora_rank"]),
            ("kv_rank", cfg["kv_lora_rank"]), ("nope", cfg["qk_nope_head_dim"]),
            ("rope", cfg["qk_rope_head_dim"]), ("v", cfg["v_head_dim"]),
            ("eps", cfg["rms_norm_eps"]), ("theta", float(cfg["rope_theta"])),
            ("experts", cfg.get("published", cfg)["n_routed_experts"]),
            ("per_tok", cfg["num_experts_per_tok"]), ("norm_topk", bool(cfg["norm_topk_prob"])),
            ("scale", float(cfg["routed_scaling_factor"])), ("scoring", cfg["scoring_func"]),
            ("held", held_experts(cfg)), ("tie", float(assumed.get("tie_margin", TIE_MARGIN))),
            ("chunk", int(assumed.get("prefill_chunk", 2048))))


@kx._highest
def _keys(x, lw, ang, flags, *, c, quant):
    """Of the whole row: kn [T, H, 128], the one rotated kr [T, 64], v [T, H, 128]."""
    fault = dict(zip(FAULTS, flags))
    h, r, dn, dr, dv = c["heads"], c["kv_rank"], c["nope"], c["rope"], c["v"]
    n = rms_norm(x, lw["attn_norm"], c["eps"])
    kva = dense(n, lw["kv_a"], quant)
    lat = jnp.where(fault["kv_norm"], kva[:, :r], rms_norm(kva[:, :r], lw["kv_a_norm"], c["eps"]))
    kr = jnp.where(fault["kr_raw"], kva[:, r:], rope_pairs(kva[:, None, r:], ang)[:, 0])
    kv = dense(lat, lw["kv_b"], quant).reshape(-1, h, dn + dv)
    off = jnp.concatenate([lat, kr], axis=-1)[:, dr:dr + r]         # the row read 64 values off
    w_uv = lw["kv_b"].reshape(r, h, dn + dv)[..., dn:]
    v = jnp.where(fault["value_slice"], jnp.einsum("sr,rhd->shd", off, w_uv), kv[..., dn:])
    return kv[..., :dn], kr, v


@kx._highest
def _attend_block(i, x, lw, ang, kn, kr, v, flags, *, c, quant):
    """``W_o`` applied to the held heads' attention of queries [i*Bq, (i+1)*Bq)
    of one row over the whole row's keys, causal."""
    fault = dict(zip(FAULTS, flags))
    h, r, dn, dr, dv = c["heads"], c["kv_rank"], c["nope"], c["rope"], c["v"]
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=0)
    n = rms_norm(cut(x), lw["attn_norm"], c["eps"])
    c_q = rms_norm(dense(n, lw["q_a"], quant), lw["q_a_norm"], c["eps"])
    q = dense(c_q, lw["q_b"], quant).reshape(bq, h, dn + dr)
    qn, qr = q[..., :dn], rope_pairs(q[..., dn:], cut(ang))
    s = jnp.einsum("thd,shd->hts", qn, kn) \
        + jnp.where(fault["rope"], 0.0, jnp.einsum("thd,sd->hts", qr, kr))
    s = s * jnp.where(fault["attn_scale"], 1.0 / np.sqrt(r + dr), 1.0 / np.sqrt(dn + dr))
    at, s_pos = i * bq + jnp.arange(bq)[:, None], jnp.arange(t)[None, :]
    seen = (s_pos <= at) & ((s_pos >= at // c["chunk"] * c["chunk"]) | ~fault["blind"])
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", p, v).reshape(bq, h * dv)
    return dense(out, lw["o"], quant)


def layer(weights, prefix, x, ang, need, key, quant, flags, sparse: bool):
    """One decoder layer over one row x [T, H]; positions at or past ``need``
    are left unfinished (nothing before them reads them).  Returns the row
    and its tokens whose routing here is a tie (none in a dense layer)."""
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    lw = {k: jnp.asarray(weights[f"{prefix}.{k}"], jnp.float32) for k in ATTN_KEYS}
    kn, kr, v = _keys(x, lw, ang, flags, key=key, quant=quant)
    blocks = [_attend_block(b, x, lw, ang, kn, kr, v, flags, key=key, quant=quant)
              for b in range(-(-need // bq))]
    attn = jnp.concatenate(blocks)
    attn = jnp.pad(attn, ((0, t - attn.shape[0]), (0, 0)))
    del kn, kr, v, blocks
    h, n = kx._post_attention(x, attn, lw["mlp_norm"], key=key, quant=quant)
    if sparse:
        moe, tie = kx.sparse_mlp(weights, prefix, n, jnp.arange(t) < need, key, quant,
                                 _kx_flags(flags))
        return h + moe, tie
    w = lambda name: weights[f"{prefix}.{name}"]
    return (h + kx.swiglu(n, w("mlp_gate"), w("mlp_up"), w("mlp_down"), key, quant),
            jnp.zeros((t,), bool))


def row_hidden(weights, cfg, layers, ids, need=None, quant=None, length=None, ties=False):
    """Hidden states before the final norm of ONE row ``ids`` [T], finished
    up to ``need`` and run at ``length`` positions (default: ``need``
    rounded up, ``cut_length``); returns [length, H], with ``ties`` also
    the positions [length] whose routing is a tie in some sparse layer."""
    quant, flags = split_control(quant)
    key = cfg_key(cfg)
    c = dict(key)
    ids = np.asarray(ids)
    need = ids.shape[0] if need is None else need
    t = cut_length(need) if length is None else length
    ids = np.pad(ids, (0, max(0, t - ids.shape[0])))[:t]
    x = jnp.asarray(weights["embed"][jnp.asarray(ids)], jnp.float32)
    ang = angles(np.arange(t), c["rope"], c["theta"])
    tied = jnp.zeros((t,), bool)
    for i in range(layers):
        x, tie = layer(weights, f"layers.{i}", x, ang, need, key, quant, flags,
                       sparse=i >= cfg["first_k_dense_replace"])
        tied |= tie
    return (x, tied) if ties else x


def _logits(weights, cfg, x, quant):
    return kx._head(x, jnp.asarray(weights["final_norm"], jnp.float32),
                    jnp.asarray(weights["head"], jnp.float32), key=cfg_key(cfg),
                    quant=split_control(quant)[0])


def row_logits(weights, cfg, layers, ids, quant=None):
    """float32 logits [T, V] of one whole row."""
    return _logits(weights, cfg, row_hidden(weights, cfg, layers, ids, quant=quant)[:len(ids)],
                   quant)


def mtp_logits(weights, cfg, layers, ids, quant=None):
    """The next-token-prediction module's logits [T - 1, V] (position ``t``
    predicts token ``t + 2``), from the weights ``mtp.*``."""
    quant_, flags = split_control(quant)
    key = cfg_key(cfg)
    c = dict(key)
    t = len(ids) - 1
    hidden = row_hidden(weights, cfg, layers, ids, quant=quant)[:t]
    f32 = lambda name: jnp.asarray(weights[name], jnp.float32)
    nxt = jnp.asarray(weights["embed"][jnp.asarray(np.asarray(ids)[1:])], jnp.float32)
    with jax.default_matmul_precision("highest"):
        joined = jnp.concatenate([rms_norm(hidden, f32("mtp.hnorm"), c["eps"]),
                                  rms_norm(nxt, f32("mtp.enorm"), c["eps"])], axis=-1)
        x = dense(joined, f32("mtp.proj"), quant_)
    pad = -t % min(Q_BLOCK, t)
    x = jnp.pad(x, ((0, pad), (0, 0)))
    ang = angles(np.arange(t + pad), c["rope"], c["theta"])
    y, _ = layer(weights, "mtp", x, ang, t, key, quant_, flags, sparse=True)
    return _logits(weights, cfg, y[:t], quant)


class RowLogits(kx.RowLogits):
    """``logits[row, span]`` -> float32 [len(span), V]: THIS family's forward
    of ``ids[row]`` up to ``span.stop``, then the norm and the head on ``span``
    (``reference/k_exaone.RowLogits``'s rules and state: tied positions read
    flat in the sound forward only; one length and one head shape a call)."""

    def __getitem__(self, at):
        row, span = at
        start, stop, _ = span.indices(self.ids.shape[1])
        x, tied = row_hidden(self.weights, self.cfg, self.layers, self.ids[row], need=stop,
                             quant=self.quant, length=max(self.length, cut_length(stop)), ties=True)
        block = -(-(stop - start) // ROW_ROUND) * ROW_ROUND
        picked = jnp.pad(x[start:stop], ((0, block - (stop - start)), (0, 0)))
        logits = _logits(self.weights, self.cfg, picked, self.quant)[:stop - start]
        if self.quant is not None:      # a control or a planted fault is judged everywhere
            return logits
        self.tied += int(jnp.sum(tied[start:stop]))
        return jnp.where(tied[start:stop, None], 0.0, logits)


def forward_logits(weights, cfg, layers, ids, quant=None):
    """ids [B, T] int32 -> an object indexed ``[row, slice]`` (see ``RowLogits``)."""
    return RowLogits(weights, cfg, layers, ids, quant)
