"""Plain reference for the ``olmo_hybrid`` model family (Olmo-Hybrid-7B,
``model_type: olmo_hybrid``): straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the gated delta rule as the
TOKEN recurrence by ``lax.scan`` (independent of the chunked form it judges),
no kernels, nothing imported from ``accelerate_tpu``.  The dense matmul with
the controls' rounding, the RMS norm, the head-by-head causal attention,
float32 Lion and the host-resident momentum are ``reference/llama.py``'s, by
import.

With ``rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * w``, layer ``i`` of kind
``layer_types[i]`` (the norm on a sublayer's OUTPUT)::

    h = x + rms(Mixer_i(x); w1);   y = h + rms(MLP(h); w2);   logits = W_head rms(y_last; w_f)
    MLP(h) = W_down (silu(W_gate h) * (W_up h))

    full_attention: q = rms(W_q x; w_q), k = rms(W_k x; w_k) over all channels, then heads of
        hidden / heads; v = W_v x; no rotary; o = W_o softmax_s(q . k_s / sqrt(D), s <= t) v_s
    linear_attention (Gated DeltaNet; Hk key heads of Dk, Hv value heads of Dv):
        q = W_q x, k = W_k x, v = W_v x, z = W_g x, b = W_b x, a = W_a x
        q, k, v <- silu(sum_{tap} w_tap * (.)_{t - taps + 1 + tap}), depthwise, zeros before the row
        q <- l2norm(q) / sqrt(Dk);  k <- l2norm(k);  beta = 2 sigmoid(b) (sigmoid(b) if not
        linear_allow_neg_eigval);  g = -exp(A_log) softplus(a + dt_bias)
        S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T q_t     (S [Dk x Dv], zero at 0)
        out = W_o [rms_Dv(o_t; w_o) * silu(z_t)]

Weights are the benchmark's own (``perfbench/weights.py``), a flat dict, every
matrix ``[in, out]``: ``embed [V,H]``; ``layers.<i>.{mixer_norm, mlp_norm [H],
gate, up [H,F], down [F,H]}``; a Gated DeltaNet layer's ``lq, lk [H, Hk Dk]``,
``lv, lz [H, Hv Dv]``, ``ba [H, 2 Hv]`` (``W_b`` then ``W_a``), ``conv [taps, 2
Hk Dk + Hv Dv]`` (the q, k and v convs' taps side by side; tap ``taps - 1``
meets the current row), ``A_log, dt_bias [Hv]``, ``o_norm [Dv]``, ``lo [Hv Dv,
H]``; an attention layer's ``q, k, v, o``, ``q_norm,
k_norm``; ``final_norm [H]``, ``head [H,V]``.  ``A_log`` is its leaf plus
``assumed.weight_scales.A_log_mean``, ROUNDED TO bfloat16: the program's
parameters are bf16 leaves, so that sum is the seeded parameter on both sides
(the benchmark's leaves are zero-mean; the configuration file says why the mean).

``quant`` is the CONTROL (``"int8"`` / ``"fp8"``: both operands of every dense
matmul).  The names of ``FAULTS`` in its place plant ONE fault of this
family's own mechanisms in a float32 forward, as flags of a traced vector so
that the sound and the faulty programs are one compiled program.

Departures from the published description: none in the mathematics.  What is
STORED differs: rows (and inside a row the mixer and the MLP, each by itself)
are recomputed in the backward pass, attention runs one head at a time, the head's loss 1,024 positions at a time, and the recurrence
is a scan over spans of ``SPAN`` positions whose body (a scan over the span's
positions) is recomputed in the backward pass - its gradient would otherwise
keep a ``[Hv, Dk, Dv]`` state for every position (18 GB at 8,192).  Training
keeps the float32 Lion momentum on the host between steps, and the seeded
bf16 leaves there too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import llama as ll
from perfbench.reference.llama import dense, rms_norm

FAULTS = {
    "beta_unit": "beta = sigmoid(b) in (0, 1): linear_allow_neg_eigval lost, no transition has a "
                 "negative eigenvalue",
    "no_decay": "g = 0: the state never decays",
    "carry": "the recurrent state is not handed from block to block of the chunked form: zero at "
             "every multiple of 64 positions",
    "conv_off": "no short convolution: q, k, v <- silu of the projection itself",
    "qk_norm_off": "the full-attention layers' q and k not normed",
    "rotary": "rotary positions (theta 10,000, halves paired) on the full-attention layers' q and k, "
              "where the configuration has none",
}
NO_FAULT = np.zeros((len(FAULTS),), bool)
SPAN = 64               # positions of one recomputed span of the recurrence
HEAD_ROWS = 1024        # positions of one recomputed piece of the head's loss
MLP_KEYS = ("mixer_norm", "mlp_norm", "gate", "up", "down")
KEYS = {"full_attention": ("q", "k", "v", "o", "q_norm", "k_norm") + MLP_KEYS,
        "linear_attention": ("lq", "lk", "lv", "lz", "ba", "conv", "A_log", "dt_bias", "o_norm", "lo")
        + MLP_KEYS}


def split_control(quant):
    """A control's name -> (the precision of the matmuls, the planted faults' flags)."""
    if quant in FAULTS:
        return None, np.arange(len(FAULTS)) == list(FAULTS).index(quant)
    return quant, NO_FAULT


def kinds(cfg, layers: int) -> tuple:
    return tuple(cfg["layer_types"][:layers])


def cfg_key(cfg):
    """The sizes the reference reads, as a hashable static argument."""
    return (("heads", cfg["num_attention_heads"]), ("kv_heads", cfg["num_key_value_heads"]),
            ("key_heads", cfg["linear_num_key_heads"]), ("value_heads", cfg["linear_num_value_heads"]),
            ("dk", cfg["linear_key_head_dim"]), ("dv", cfg["linear_value_head_dim"]),
            ("taps", cfg["linear_conv_kernel_dim"]), ("neg_eigval", bool(cfg["linear_allow_neg_eigval"])),
            ("eps", cfg["rms_norm_eps"]))


def seeded(weights, cfg) -> dict:
    """The benchmark's leaves as the model's seeded parameters (bf16): the
    same arrays, but ``A_log`` with its mean added."""
    mean = float(((cfg.get("assumed") or {}).get("weight_scales") or {}).get("A_log_mean", 0.0))
    return {k: (v.astype(jnp.float32) + mean).astype(v.dtype) if k.endswith(".A_log") else v
            for k, v in weights.items()}


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


# -- the two mixers, over one row x [T, H] -----------------------------------------


def delta_scan(q, k, v, g, beta, restart):
    """The token recurrence from a zero state: q, k [T, Hv, Dk], v [T, Hv, Dv],
    g, beta [T, Hv], restart [T] (the state zeroed BEFORE that position) ->
    o [T, Hv, Dv]."""
    t, hv, dk = q.shape
    pad = -t % SPAN             # positions that change nothing and are cut off
    spans = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (-1, SPAN) + a.shape[1:])

    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t, zero = xs
        s = jnp.where(zero, 0.0, s) * jnp.exp(g_t)[:, None, None]
        written = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * written[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    span = jax.checkpoint(lambda s, xs: jax.lax.scan(token, s, xs))
    _, o = jax.lax.scan(span, jnp.zeros((hv, dk, v.shape[-1]), jnp.float32),
                        tuple(spans(a) for a in (q, k, v, g, beta, restart)))
    return o.reshape((-1,) + o.shape[2:])[:t]


def delta_net(x, lw, c, quant, fault):
    kh, vh, dk, dv, taps = c["key_heads"], c["value_heads"], c["dk"], c["dv"], c["taps"]
    t = x.shape[0]

    @jax.checkpoint             # its taps' products (four arrays the size of [q; k; v]) live only in its own backward
    def conved(a, w):
        before = jnp.pad(a, ((taps - 1, 0), (0, 0)))
        y = sum(before[tap:tap + t] * w[tap] for tap in range(taps))
        return jax.nn.silu(jnp.where(fault["conv_off"], a, y))

    qkv = conved(jnp.concatenate([dense(x, lw[n], quant) for n in ("lq", "lk", "lv")], axis=-1), lw["conv"])
    q, k = qkv[:, :kh * dk].reshape(t, kh, dk), qkv[:, kh * dk:2 * kh * dk].reshape(t, kh, dk)
    v = qkv[:, 2 * kh * dk:].reshape(t, vh, dv)
    z = dense(x, lw["lz"], quant).reshape(t, vh, dv)
    ba = dense(x, lw["ba"], quant)
    q, k = (jnp.repeat(a, vh // kh, axis=1) for a in (l2norm(q) / np.sqrt(dk), l2norm(k)))
    write = jax.nn.sigmoid(ba[:, :vh])
    beta = write * jnp.where(fault["beta_unit"], 1.0, 2.0 if c["neg_eigval"] else 1.0)
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(ba[:, vh:] + lw["dt_bias"])
    g = jnp.where(fault["no_decay"], 0.0, g)
    restart = fault["carry"] & (jnp.arange(t) % 64 == 0)
    o = delta_scan(q, k, v, g, beta, restart)
    gated = rms_norm(o, lw["o_norm"], c["eps"]) * jax.nn.silu(z)
    return dense(gated.reshape(t, vh * dv), lw["lo"], quant)


def attention(x, lw, c, quant, fault):
    hq, hkv, t = c["heads"], c["kv_heads"], x.shape[0]
    q, k = dense(x, lw["q"], quant), dense(x, lw["k"], quant)
    q = jnp.where(fault["qk_norm_off"], q, rms_norm(q, lw["q_norm"], c["eps"])).reshape(t, hq, -1)
    k = jnp.where(fault["qk_norm_off"], k, rms_norm(k, lw["k_norm"], c["eps"])).reshape(t, hkv, -1)
    q = jnp.where(fault["rotary"], ll.rope(q, np.arange(t), 10000.0), q)
    k = jnp.where(fault["rotary"], ll.rope(k, np.arange(t), 10000.0), k)
    v = dense(x, lw["v"], quant).reshape(t, hkv, -1)
    return dense(ll.attention(q, k, v), lw["o"], quant)


def block(x, lw, flags, kind, c, quant=None):
    """One decoder layer of ``kind`` over rows x [B, T, H]."""
    fault = dict(zip(FAULTS, flags))

    mixer = attention if kind == "full_attention" else delta_net
    mlp = lambda h: dense(jax.nn.silu(dense(h, lw["gate"], quant)) * dense(h, lw["up"], quant),
                          lw["down"], quant)

    def row(xr):
        # each half checkpointed by itself too: their intermediates are never live together
        h = xr + rms_norm(jax.checkpoint(lambda a: mixer(a, lw, c, quant, fault))(xr),
                          lw["mixer_norm"], c["eps"])
        return h + rms_norm(jax.checkpoint(mlp)(h), lw["mlp_norm"], c["eps"])

    # checkpointed: the backward pass keeps a row's input and recomputes the row
    return jax.lax.map(jax.checkpoint(row), x)


def head_logits(x, final_norm, head, c, quant=None):
    return dense(rms_norm(x, final_norm, c["eps"]), head, quant)


def head_loss(x, final_norm, head, labels, c, quant=None):
    """Mean next-token NLL over rows x [B, T, H] (position t predicts
    labels[t + 1]; a row's last position predicts nothing), a piece of
    ``HEAD_ROWS`` positions at a time."""
    b, t = labels.shape
    nxt = jnp.concatenate([labels[:, 1:], jnp.zeros((b, 1), labels.dtype)], axis=1)
    live = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))
    pad = -(b * t) % HEAD_ROWS
    pieces = lambda a: jnp.pad(a.reshape((b * t,) + a.shape[2:]),
                               ((0, pad),) + ((0, 0),) * (a.ndim - 2)).reshape(
        (-1, HEAD_ROWS) + a.shape[2:])

    def piece(args):
        xs, lab, on = args
        logits = head_logits(xs, final_norm, head, c, quant)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(on, nll, 0.0))

    return jnp.sum(jax.lax.map(jax.checkpoint(piece), (pieces(x), pieces(nxt), pieces(live)))) \
        / (b * (t - 1))


def layer_weights(params, i, kind, to=jnp.float32):
    return {k: jnp.asarray(params[f"layers.{i}.{k}"], to) for k in KEYS[kind]}


# -- compiled pieces: one layer, the head ---------------------------------------------


def _highest(f):
    """jit ``f(*arrays, kind=, key=, quant=)`` at the highest matmul precision."""
    @functools.partial(jax.jit, static_argnames=("kind", "key", "quant"))
    def run(*args, kind=None, key=None, quant=None):
        with jax.default_matmul_precision("highest"):
            return f(*args, kind=kind, c=dict(key), quant=quant)
    return run


@_highest
def _block(x, lw, flags, *, kind, c, quant):
    return block(x, lw, flags, kind, c, quant)


@_highest
def _block_vjp(x, lw, flags, dy, *, kind, c, quant):
    _, pull = jax.vjp(lambda x_, lw_: block(x_, lw_, flags, kind, c, quant), x, lw)
    return pull(dy)


@_highest
def _logits(x, final_norm, head, *, kind, c, quant):
    return head_logits(x, final_norm, head, c, quant)


@_highest
def _head_grad(x, final_norm, head, labels, *, kind, c, quant):
    return jax.value_and_grad(
        lambda x_, n_, h_: head_loss(x_, n_, h_, labels, c, quant), argnums=(0, 1, 2))(x, final_norm, head)


def forward_logits(weights, cfg, layers, ids, quant=None):
    """ids [B, T] int32 -> float32 logits [B, T, V], a layer at a time."""
    quant, flags = split_control(quant)
    key, params = cfg_key(cfg), seeded(weights, cfg)
    x = jnp.asarray(params["embed"], jnp.float32)[ids]
    for i, kind in enumerate(kinds(cfg, layers)):
        x = _block(x, layer_weights(params, i, kind), flags, kind=kind, key=key, quant=quant)
    return _logits(x, jnp.asarray(params["final_norm"], jnp.float32),
                   jnp.asarray(params["head"], jnp.float32), key=key, quant=quant)


class TrainReference(ll.TrainReference):
    """``reference/llama.TrainReference`` (float32 parameters resident, the
    float32 Lion momentum on the host, a layer at a time forward and
    backward) over this family's layers.  ``step(ids)`` returns the loss of
    the batch BEFORE the update and the per-leaf gradient norms."""

    def __init__(self, weights, cfg, layers, lr, b1, b2, steps, quant=None):
        devs = jax.devices()
        self.cfg, self.key, self.kinds = cfg, cfg_key(cfg), kinds(cfg, layers)
        self.quant, self.flags = split_control(quant)
        self.hyper = (np.float32(lr), np.float32(b1), np.float32(b2))
        self.steps_left = steps
        where = lambda name: devs[int(name.split(".")[1]) % len(devs)] if name.startswith("layers.") \
            else devs[(1 if name == "embed" else 2) % len(devs)]
        # the seeded bf16 leaves wait on the HOST for change_norms: beside 6.4 GB of float32
        # parameters a 4.9 GB layer's backward does not leave them 3.2 GB of the chip
        self.p0 = {k: np.asarray(v) for k, v in seeded(weights, cfg).items()}
        self.p = {k: jax.device_put(v, where(k)).astype(jnp.float32) for k, v in self.p0.items()}
        self.m, self._flying = {}, []

    def _layer(self, i):
        return {k: self.p[f"layers.{i}.{k}"] for k in KEYS[self.kinds[i]]}

    def step(self, ids):
        on = lambda x, name: jax.device_put(x, self.p[name].sharding)
        run = dict(key=self.key, quant=self.quant)
        ids_e = on(jnp.asarray(ids, jnp.int32), "embed")
        x = self.p["embed"][ids_e]
        inputs = []
        for i, kind in enumerate(self.kinds):
            x = on(x, f"layers.{i}.gate")
            inputs.append(x)
            x = _block(x, self._layer(i), self.flags, kind=kind, **run)
        loss, (dx, d_norm, d_head) = _head_grad(on(x, "head"), self.p["final_norm"], self.p["head"],
                                                on(ids_e, "head"), **run)
        norms = {}
        self._update("final_norm", d_norm, norms)
        self._update("head", d_head, norms)
        del d_norm, d_head
        self._land(keep=0)          # the head's 1.5 GB of momentum is off the chip before a layer's backward
        for i, kind in reversed(list(enumerate(self.kinds))):
            dx, d_lw = _block_vjp(inputs.pop(), self._layer(i), self.flags,
                                  on(dx, f"layers.{i}.gate"), kind=kind, **run)
            self._land(keep=len(KEYS[kind]))
            for k in KEYS[kind]:
                self._update(f"layers.{i}.{k}", d_lw[k], norms)
        d_embed = jnp.zeros(self.p["embed"].shape, jnp.float32).at[ids_e].add(on(dx, "embed"))
        self._update("embed", d_embed, norms)
        self._land(keep=0)
        self.steps_left -= 1
        return float(loss), {k: float(np.sqrt(float(v))) for k, v in norms.items()}
