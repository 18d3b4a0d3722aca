"""Plain reference for the ``qwen3_next`` model family (Qwen3-Next-80B-A3B's
language model, ``model_type: qwen3_next``), given the SAME share of each layer
as the program: straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the TOKEN recurrence of the gated
delta rule by ``lax.scan``, no chunked form, no cache, no kernels, nothing
imported from ``accelerate_tpu``.  The SwiGLUs, one expert's rows, the head,
the controls' rounding and the lazy ``RowLogits`` are ``reference/k_exaone.py``'s,
by import; the router is this family's own (softmax over 512, ten a token).

With ``rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)`` (zero-centred), layer ``i``::

    h = x + Mixer_i(rms(x; w1));   y = h + MoE(rms(h; w2));   final rms, untied head
    Mixer_i: gated attention where (i + 1) % 4 == 0, Gated DeltaNet otherwise

    Gated DeltaNet, n = rms(x; w1), key head j of the HELD ones, its value heads 2j, 2j + 1:
        [q_j 128; k_j 128; v_2j, v_2j+1 2 x 128; z_2j, z_2j+1 2 x 128] = W_qkvz,j n;   [b 2; a 2] = W_ba,j n
        [q; k; v] <- silu(sum_{tap 0..3} w_tap * [q; k; v]_{t-3+tap}), depthwise, zeros before the row
        beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias);   q <- l2norm(q) / sqrt(128);   k <- l2norm(k)
        S <- exp(g_t) S;   S <- S + k_t (beta_t (v_t - S^T k_t))^T;   o_t = S^T q_t        (S [128 x 128] a value head)
        out = W_o [rmsnorm_128(o_t; w, plain) * silu(z_t)]
    gated attention, head h of the HELD ones on the held KV head:
        [q_h 256; gate_h 256] = W_q,h n;   q_h <- rms_256(q_h), k <- rms_256(k);   rotary (theta 1e7, halves
        paired) on dims 0-63;   o = W_o [softmax_s(q_h . k_s / 16, s <= t) v_s * sigmoid(gate_h)]
    MoE: p = softmax_512(W_r n);  T = top10(p);  g_e = p_e / sum_T p
        MoE(n) = sigmoid(w_s . n) E_shared(n) + sum_{e in T, e held here} g_e E_e(n)     (SwiGLUs of width 512)

**The share.**  The configuration's top-level ``num_attention_heads``,
``num_key_value_heads``, ``linear_num_key_heads``, ``linear_num_value_heads``,
``num_experts`` and ``vocab_size`` are what is HELD (rank 0 of four chips that
share each layer); ``published`` has the model's own counts and
``share.experts_held`` the global ids.  The router scores all 512 experts and
normalises over all ten chosen; only the held experts' part is added.

Weights are the benchmark's own (``perfbench/weights.py``), a flat dict, every
matrix ``[in, out]``: ``embed [V,H]``; ``layers.<i>.{attn_norm, mlp_norm [H]}``;
a Gated DeltaNet layer's ``qkvz [H, heads x 768]``, ``ba [H, heads x 4]`` (a key
head's group after another), ``conv [4, C]`` (tap 3 meets the current row; the
channels ``[q; k; v]``), ``A_log, dt_bias [Hv]``, ``gdn_norm [128]``, ``gdn_out
[Hv x 128, H]``; an attention layer's ``q [H, heads x 512]``, ``k, v [H, 256]``,
``o [heads x 256, H]``, ``q_norm, k_norm [256]``; ``router [H, 512]``, ``gate,
up [E_held,H,F]``, ``down [E_held,F,H]``, ``shared_gate, shared_up [H,F]``,
``shared_down [F,H]``, ``shared_sigmoid [H,1]``; ``final_norm [H]``, ``head
[H,V]``.  The zero-centred norms' leaves are the ``w`` of ``1 + w``; ``A_log``
is its leaf plus ``assumed.weight_scales.A_log_mean`` (the benchmark's leaves
are zero-mean normals; the configuration file says why the mean).

``forward_logits`` returns an object, not an array: ``logits[row, span]`` runs
that row up to ``span.stop`` (``reference/k_exaone.RowLogits``'s rules).
Positions whose routing is a TIE — the tenth and the eleventh router LOGIT
within ``assumed.tie_margin`` of each other and either expert held here —
read flat in the sound forward and are not judged.

``quant`` is the CONTROL (``"int8"`` / ``"fp8"``: both operands of every dense
matmul).  The names of ``FAULTS`` in its place plant ONE fault in a float32
forward (``prove.py --control stale,carry,...``).

How the reference blocks its work: as ``reference/k_exaone.py`` (one length a
call, 512 queries at a time against the whole row's keys, SwiGLUs 2,048
columns at a time, routed experts one at a time); a Gated DeltaNet layer's
projections run over the whole row and its recurrence is ONE scan of the
row's positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import k_exaone as kx
from perfbench.reference.k_exaone import (Q_BLOCK, ROW_ROUND, TIE_MARGIN, angles, cut_length,
                                          dense, rms_norm, rope)

FAULTS = {
    "stale": "a slot's recurrent state is not zeroed at position 0: the row starts from the state "
             "its own tokens left behind (what a finished tenant of a slot leaves)",
    "carry": "the recurrent state is not handed from chunk to chunk: zero at every multiple of "
             "assumed.prefill_chunk positions",
    "conv_carry": "the conv window restarts each chunk: taps that reach before a multiple of "
                  "assumed.prefill_chunk read zeros",
    "decay": "g = 0: the state never decays",
    "beta": "beta = 1: every write at full strength",
    "l2": "q and k not L2-normed (q still scaled by 1/sqrt(128))",
    "out_gate": "the Gated DeltaNet output not gated: rmsnorm(o) alone, no silu(z)",
    "attn_gate": "the attention output not gated: no sigmoid(gate_h)",
    "rope_all": "rotary over all 256 dims of a head in place of the first 64",
    "norm_plain": "the zero-centred norms applied as w in place of 1 + w",
    "shared_gate": "the shared expert added ungated (no sigmoid(w_s . n))",
    "expert": "held expert 7 adds nothing, as a grouped matmul that loses one group would",
    "share": "rows routed to ABSENT experts are multiplied by held experts' weights (expert e by "
             "held e % 128), as a grouped matmul that does not stop at the held rows would",
    "softmax": "the gates of the chosen not renormalised (p_e in place of p_e / sum_T p)",
    "state_bf16": "the recurrent state rounded to bfloat16 after every token: a state kept in a "
                  "lower precision than the float32 the configuration file states",
}
NO_FAULT = np.zeros((len(FAULTS),), bool)
ATTN_KEYS = ("q", "k", "v", "o", "q_norm", "k_norm")
GDN_KEYS = ("qkvz", "ba", "conv", "A_log", "dt_bias", "gdn_norm", "gdn_out")


def split_control(quant):
    """A control's name -> (the precision of the matmuls, the planted faults' flags)."""
    if quant in FAULTS:
        return None, np.arange(len(FAULTS)) == list(FAULTS).index(quant)
    return quant, NO_FAULT


def held_experts(cfg) -> tuple:
    share = cfg.get("share") or {}
    return tuple(share.get("experts_held", range(cfg["num_experts"])))


def cfg_key(cfg):
    assumed = cfg.get("assumed") or {}
    return (("heads", cfg["num_attention_heads"]), ("kv_heads", cfg["num_key_value_heads"]),
            ("head_dim", cfg["head_dim"]),
            ("rotary", int(cfg["head_dim"] * cfg["partial_rotary_factor"])),
            ("key_heads", cfg["linear_num_key_heads"]), ("value_heads", cfg["linear_num_value_heads"]),
            ("dk", cfg["linear_key_head_dim"]), ("dv", cfg["linear_value_head_dim"]),
            ("taps", cfg["linear_conv_kernel_dim"]), ("eps", cfg["rms_norm_eps"]),
            ("theta", float(cfg["rope_theta"])),
            ("experts", cfg.get("published", cfg)["num_experts"]),
            ("per_tok", cfg["num_experts_per_tok"]), ("norm_topk", bool(cfg["norm_topk_prob"])),
            ("held", held_experts(cfg)), ("tie", float(assumed.get("tie_margin", TIE_MARGIN))),
            ("chunk", int(assumed.get("prefill_chunk", 2048))),
            ("a_log_mean", float((assumed.get("weight_scales") or {}).get("A_log_mean", 0.0))))


def _centred(w, flags):
    """The factor of a zero-centred norm: ``1 + w`` (``w`` alone under ``norm_plain``)."""
    return jnp.where(dict(zip(FAULTS, flags))["norm_plain"], w, 1.0 + w)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


# -- Gated DeltaNet -----------------------------------------------------------------


@kx._highest
def _delta_inputs(x, norm, lw, flags, *, c, quant):
    """Of the whole row: q, k [T, Hv, 128] (normed, a key head's serving its
    value heads), v, z [T, Hv, 128], g, beta [T, Hv]."""
    fault = dict(zip(FAULTS, flags))
    kh, vh, dk, dv, taps = c["key_heads"], c["value_heads"], c["dk"], c["dv"], c["taps"]
    r, t = vh // kh, x.shape[0]
    n = rms_norm(x, _centred(norm, flags), c["eps"])
    qkvz = dense(n, lw["qkvz"], quant).reshape(t, kh, 2 * dk + 2 * r * dv)
    ba = dense(n, lw["ba"], quant).reshape(t, kh, 2 * r)
    mixed = jnp.concatenate([qkvz[..., :dk].reshape(t, -1), qkvz[..., dk:2 * dk].reshape(t, -1),
                             qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, -1)], axis=-1)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, vh, dv)
    before = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    into_chunk = jnp.arange(t) % c["chunk"]
    y = 0.0
    for tap in range(taps):         # tap reaches taps - 1 - tap positions back
        reach = taps - 1 - tap
        cut = fault["conv_carry"] & (into_chunk < reach)
        y = y + jnp.where(cut[:, None], 0.0, before[tap:tap + t]) * lw["conv"][tap]
    y = jax.nn.silu(y)
    q, k = y[:, :kh * dk].reshape(t, kh, dk), y[:, kh * dk:2 * kh * dk].reshape(t, kh, dk)
    q = jnp.where(fault["l2"], q, l2norm(q)) / np.sqrt(dk)
    k = jnp.where(fault["l2"], k, l2norm(k))
    v = y[:, 2 * kh * dk:].reshape(t, vh, dv)
    beta = jnp.where(fault["beta"], 1.0, jax.nn.sigmoid(ba[..., :r].reshape(t, vh)))
    g = -jnp.exp(lw["A_log"] + c["a_log_mean"]) * jax.nn.softplus(ba[..., r:].reshape(t, vh)
                                                                  + lw["dt_bias"])
    g = jnp.where(fault["decay"], 0.0, g)
    return jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, z, g, beta


@kx._highest
def _delta_scan(s0, q, k, v, g, beta, flags, *, c, quant):
    """The token recurrence over the row: ``(o [T, Hv, 128], the last state)``."""
    fault = dict(zip(FAULTS, flags))
    restart = fault["carry"] & (jnp.arange(q.shape[0]) % c["chunk"] == 0)

    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t, zero = xs
        s = jnp.where(zero, 0.0, s) * jnp.exp(g_t)[:, None, None]
        written = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * written[:, None, :]
        s = jnp.where(fault["state_bf16"], s.astype(jnp.bfloat16).astype(jnp.float32), s)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s, o = jax.lax.scan(token, s0, (q, k, v, g, beta, restart))
    return o, s


@kx._highest
def _delta_out(o, z, lw, flags, *, c, quant):
    gate = jnp.where(dict(zip(FAULTS, flags))["out_gate"], 1.0, jax.nn.silu(z))
    normed = rms_norm(o, lw["gdn_norm"], c["eps"]) * gate
    return dense(normed.reshape(o.shape[0], -1), lw["gdn_out"], quant)


def delta_net(x, norm, lw, key, quant, flags):
    """One Gated DeltaNet mixer over one row x [T, H] -> [T, H]."""
    c = dict(key)
    q, k, v, z, g, beta = _delta_inputs(x, norm, lw, flags, key=key, quant=quant)
    s0 = jnp.zeros((c["value_heads"], c["dk"], c["dv"]), jnp.float32)
    if dict(zip(FAULTS, np.asarray(flags)))["stale"]:       # what the row itself would leave behind
        s0 = _delta_scan(s0, q, k, v, g, beta, flags, key=key, quant=quant)[1]
    o, _ = _delta_scan(s0, q, k, v, g, beta, flags, key=key, quant=quant)
    return _delta_out(o, z, lw, flags, key=key, quant=quant)


# -- gated attention ----------------------------------------------------------------


def _rotated(x, ang_part, ang_all, c, fault):
    """Rotary on the first ``rotary`` dims (on all of them under ``rope_all``)."""
    part = jnp.concatenate([rope(x[..., :c["rotary"]], ang_part), x[..., c["rotary"]:]], axis=-1)
    return jnp.where(fault["rope_all"], rope(x, ang_all), part)


@kx._highest
def _keys(x, norm, lw, ang_part, ang_all, flags, *, c, quant):
    """k (normed, rotated) and v of the row: [T, Hkv, D]."""
    fault = dict(zip(FAULTS, flags))
    hkv, d = c["kv_heads"], c["head_dim"]
    n = rms_norm(x, _centred(norm, flags), c["eps"])
    k = rms_norm(dense(n, lw["k"], quant).reshape(-1, hkv, d), _centred(lw["k_norm"], flags), c["eps"])
    return _rotated(k, ang_part, ang_all, c, fault), dense(n, lw["v"], quant).reshape(-1, hkv, d)


@kx._highest
def _attend_block(i, x, norm, lw, ang_part, ang_all, k, v, flags, *, c, quant):
    """``W_o`` applied to the held heads' gated attention of queries
    [i*Bq, (i+1)*Bq) of one row over the whole row's keys, causal."""
    fault = dict(zip(FAULTS, flags))
    hq, hkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=0)
    n = rms_norm(cut(x), _centred(norm, flags), c["eps"])
    qg = dense(n, lw["q"], quant).reshape(bq, hq, 2 * d)
    q = rms_norm(qg[..., :d], _centred(lw["q_norm"], flags), c["eps"])
    q = _rotated(q, cut(ang_part), cut(ang_all), c, fault)
    at, s_pos = i * bq + jnp.arange(bq)[:, None], jnp.arange(t)[None, :]
    s = jnp.einsum("thgd,shd->hgts", q.reshape(bq, hkv, hq // hkv, d), k) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where((s_pos <= at)[None, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hgts,shd->thgd", p, v).reshape(bq, hq, d)
    out = out * jnp.where(fault["attn_gate"], 1.0, jax.nn.sigmoid(qg[..., d:]))
    return dense(out.reshape(bq, hq * d), lw["o"], quant)


def attention(x, norm, lw, need, key, quant, flags):
    """One gated-attention mixer over one row x [T, H] -> [T, H], finished up to ``need``."""
    c = dict(key)
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    ang_part = angles(np.arange(t), c["rotary"], c["theta"])
    ang_all = angles(np.arange(t), c["head_dim"], c["theta"])
    k, v = _keys(x, norm, lw, ang_part, ang_all, flags, key=key, quant=quant)
    blocks = [_attend_block(b, x, norm, lw, ang_part, ang_all, k, v, flags, key=key, quant=quant)
              for b in range(-(-need // bq))]
    attn = jnp.concatenate(blocks)
    return jnp.pad(attn, ((0, t - attn.shape[0]), (0, 0)))


# -- the experts --------------------------------------------------------------------


@kx._highest
def _route(n, live, router, flags, *, c, quant):
    """Each token's experts (global ids) and gates, the most live tokens any
    one held expert was routed, and the tokens whose choice is a tie."""
    fault = dict(zip(FAULTS, flags))
    logits = dense(n, router, quant)
    p = jax.nn.softmax(logits, axis=-1)
    k = c["per_tok"]
    top, ranked = jax.lax.top_k(logits, min(k + 1, c["experts"]))
    experts = ranked[:, :k]
    held_here = jnp.asarray(np.isin(np.arange(c["experts"]), np.asarray(c["held"])))
    if ranked.shape[1] > k:     # the last chosen and the first left out, where either is held here
        tie = (top[:, k - 1] - top[:, k] < c["tie"]) & (held_here[ranked[:, k - 1]] | held_here[ranked[:, k]])
    else:
        tie = jnp.zeros(n.shape[:1], bool)
    gate = jnp.take_along_axis(p, experts, axis=-1)
    if c["norm_topk"]:
        gate = jnp.where(fault["softmax"], gate, gate / jnp.sum(gate, axis=-1, keepdims=True))
    held = np.asarray(c["held"], np.int32)
    is_held = held_here[experts]
    experts = jnp.where(fault["share"] & ~is_held, jnp.asarray(held)[experts % len(held)], experts)
    gate = jnp.where(fault["expert"] & (experts == held[min(7, len(held) - 1)]), 0.0, gate)
    counts = jnp.zeros((c["experts"],), jnp.int32).at[experts].add(live[:, None].astype(jnp.int32))
    return experts, gate, jnp.max(counts[jnp.asarray(held)]), tie


@kx._highest
def _gated_add(acc, shared, n, w_s, flags, *, c, quant):
    gate = jnp.where(dict(zip(FAULTS, flags))["shared_gate"], 1.0, jax.nn.sigmoid(dense(n, w_s, quant)))
    return acc + gate * shared


def sparse_mlp(weights, prefix, n, live, key, quant, flags):
    """The gated shared expert over every token plus the held experts' part,
    and which tokens' choice of experts is a tie."""
    c = dict(key)
    w = lambda name: weights[f"{prefix}.{name}"]
    f32 = lambda name: jnp.asarray(w(name), jnp.float32)
    experts, gate, most, tie = _route(n, live, f32("router"), flags, key=key, quant=quant)
    t = n.shape[0]
    cap = min(t, max(t // 8, 1 << max(3, (int(most) - 1).bit_length())))
    acc = jnp.zeros_like(n)
    for slot, expert_id in enumerate(c["held"]):
        acc = kx._expert(acc, n, experts, gate, live, w("gate")[slot], w("up")[slot], w("down")[slot],
                         expert_id, cap, quant)
    shared = kx.swiglu(n, w("shared_gate"), w("shared_up"), w("shared_down"), key, quant)
    return _gated_add(acc, shared, n, f32("shared_sigmoid"), flags, key=key, quant=quant), tie


# -- the stack ----------------------------------------------------------------------


def is_full_attention(cfg, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def layer(weights, prefix, x, need, key, quant, flags, full: bool):
    """One decoder layer over one row x [T, H]; positions at or past ``need``
    may be left unfinished (nothing before them reads them).  Returns the row
    and its tokens whose routing here is a tie."""
    f32 = lambda name: jnp.asarray(weights[f"{prefix}.{name}"], jnp.float32)
    lw = {k: f32(k) for k in (ATTN_KEYS if full else GDN_KEYS)}
    if full:
        mixed = attention(x, f32("attn_norm"), lw, need, key, quant, flags)
    else:
        mixed = delta_net(x, f32("attn_norm"), lw, key, quant, flags)
    del lw
    h, n = kx._post_attention(x, mixed, _centred(f32("mlp_norm"), flags), key=key, quant=quant)
    moe, tie = sparse_mlp(weights, prefix, n, jnp.arange(x.shape[0]) < need, key, quant, flags)
    return h + moe, tie


def row_hidden(weights, cfg, layers, ids, need=None, quant=None, length=None, ties=False):
    """Hidden states before the final norm of ONE row ``ids`` [T], finished
    up to ``need`` and run at ``length`` positions (default: ``need``
    rounded up, ``cut_length``); returns [length, H], with ``ties`` also
    the positions [length] whose routing is a tie in some layer."""
    quant, flags = split_control(quant)
    key = cfg_key(cfg)
    ids = np.asarray(ids)
    need = ids.shape[0] if need is None else need
    t = cut_length(need) if length is None else length
    ids = np.pad(ids, (0, max(0, t - ids.shape[0])))[:t]
    x = jnp.asarray(weights["embed"][jnp.asarray(ids)], jnp.float32)
    tied = jnp.zeros((t,), bool)
    for i in range(layers):
        x, tie = layer(weights, f"layers.{i}", x, need, key, quant, flags,
                       full=is_full_attention(cfg, i))
        tied |= tie
    return (x, tied) if ties else x


def _logits(weights, cfg, x, quant):
    quant, flags = split_control(quant)
    return kx._head(x, _centred(jnp.asarray(weights["final_norm"], jnp.float32), flags),
                    jnp.asarray(weights["head"], jnp.float32), key=cfg_key(cfg), quant=quant)


def row_logits(weights, cfg, layers, ids, quant=None):
    """float32 logits [T, V] of one whole row."""
    return _logits(weights, cfg, row_hidden(weights, cfg, layers, ids, quant=quant)[:len(ids)],
                   quant)


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
