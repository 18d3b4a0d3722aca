"""Plain reference for the ``llama`` model family (Mistral, Yi, Llama-shaped
decoders): pre-norm blocks, RMSNorm, rotary positions (rotate-half), grouped
query attention, SwiGLU MLP, untied head.  Straight ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and nothing imported from ``accelerate_tpu``.

Weights are the benchmark's own (``perfbench/weights.py``), a flat dict:
``embed [V,H]``, ``layers.<i>.{attn_norm,q,k,v,o,mlp_norm,gate,up,down}``
(matrices ``[in, out]``), ``final_norm [H]``, ``head [H,V]``.

``quant`` is the CONTROL, never a benchmark path: ``"int8"`` or ``"fp8"``
(e4m3) rounds both operands of every dense matmul to 8 bits (absmax scale per
row of the activations and per output column of the weights; straight-through
gradient) — the precisions next below the bf16 the configurations state.  ``correct`` has to come
out false for the one the limits were set against (``tests/perfbench_suite``; PERF.md section 4 says which and why).

Departures from the published description: none in the mathematics.  The
attention is evaluated one block of heads at a time and rows are recomputed in
the backward pass, and training keeps the float32 Lion momentum on the host
between steps, so that float32 state of a 1.6-3.2B parameter cut fits on the
chips once the program's own state is freed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("attn_norm", "q", "k", "v", "o", "mlp_norm", "gate", "up", "down")


def _fake_quant(x, axis, quant):
    """Round to int8 (127 levels a side) or fp8 e4m3 under an absmax scale
    along ``axis``; the gradient passes straight through."""
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        q = jnp.round(x / scale) * scale
    else:
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def dense(x, w, quant):
    if quant is not None:
        x, w = _fake_quant(x, -1, quant), _fake_quant(w, 0, quant)
    return x @ w


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, heads, D]; rotate-half convention of the published code."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.asarray(positions, jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v):
    """Causal attention of one sequence.  q [T,Hq,D], k/v [T,Hkv,D]; one kv
    head (and its group of query heads) at a time so scores stay [g,T,T]."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)  # [Hkv, g, T, D]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [Hkv, T, D]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("gtd,sd->gts", qh, kh) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh)

    # checkpointed: the backward pass keeps q, k, v of a head group, not its [g,T,T] scores
    out = jax.lax.map(jax.checkpoint(one), (qg, kg, vg))  # [Hkv, g, T, D]
    return out.transpose(2, 0, 1, 3).reshape(t, hq * d)


def block(x, lw, cfg, quant=None):
    """One decoder layer over rows x [B, T, H] (positions 0..T-1)."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pos = np.arange(x.shape[1])          # static: the row length is part of the program's shape

    def row(xr):
        h = rms_norm(xr, lw["attn_norm"], cfg["rms_norm_eps"])
        q = rope(dense(h, lw["q"], quant).reshape(-1, hq, d), pos, cfg["rope_theta"])
        k = rope(dense(h, lw["k"], quant).reshape(-1, hkv, d), pos, cfg["rope_theta"])
        v = dense(h, lw["v"], quant).reshape(-1, hkv, d)
        xr = xr + dense(attention(q, k, v), lw["o"], quant)
        h = rms_norm(xr, lw["mlp_norm"], cfg["rms_norm_eps"])
        mlp = dense(jax.nn.silu(dense(h, lw["gate"], quant)) * dense(h, lw["up"], quant),
                    lw["down"], quant)
        return xr + mlp

    # checkpointed: the backward pass keeps a row's input and recomputes the row
    return jax.lax.map(jax.checkpoint(row), x)


def head_logits(x, final_norm, head, cfg, quant=None):
    return dense(rms_norm(x, final_norm, cfg["rms_norm_eps"]), head, quant)


def head_loss(x, final_norm, head, labels, cfg, quant=None):
    """Sum over rows of the summed next-token NLL, and the target count.
    x [B,T,H]; labels [B,T] (position t predicts labels[t+1])."""

    def row(args):
        xr, lab = args
        logits = head_logits(xr[:-1], final_norm, head, cfg, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lab[1:, None], axis=-1)[:, 0])

    return (jnp.sum(jax.lax.map(jax.checkpoint(row), (x, labels))),
            labels.shape[0] * (labels.shape[1] - 1))


def layer_weights(weights, i, to=jnp.float32):
    return {k: jnp.asarray(weights[f"layers.{i}.{k}"], to) for k in LAYER_KEYS}


# ---------------------------------------------------------------------------
# serving: one full forward over prompt + served tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_jit(x, lw, cfg_key, quant):
    with jax.default_matmul_precision("highest"):
        return block(x, lw, dict(cfg_key), quant)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _logits_jit(x, final_norm, head, cfg_key, quant):
    with jax.default_matmul_precision("highest"):
        return head_logits(x, final_norm, head, dict(cfg_key), quant)


def cfg_key(cfg):
    """The sizes the reference reads, as a hashable static argument."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def forward_logits(weights, cfg, layers, ids, quant=None):
    """ids [B, T] int32 -> float32 logits [B, T, V], a layer at a time (each
    layer's weights are upcast from the benchmark's bf16 arrays and dropped)."""
    key = cfg_key(cfg)
    x = jnp.asarray(weights["embed"], jnp.float32)[ids]
    for i in range(layers):
        x = _block_jit(x, layer_weights(weights, i), key, quant)
    return _logits_jit(x, jnp.asarray(weights["final_norm"], jnp.float32),
                       jnp.asarray(weights["head"], jnp.float32), key, quant)


# ---------------------------------------------------------------------------
# training: loss, gradients and plain Lion, state streamed from the host
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _block_vjp(x, lw, dy, cfg_key, quant):
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(lambda x_, lw_: block(x_, lw_, dict(cfg_key), quant), x, lw)
        return pull(dy)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head_grad(x, final_norm, head, labels, cfg_key, quant):
    with jax.default_matmul_precision("highest"):
        def f(x_, n_, h_):
            total, count = head_loss(x_, n_, h_, labels, dict(cfg_key), quant)
            return total / count
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, final_norm, head)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _lion(p, m, g, lr, b1, b2):
    """optax.lion in float32, weight decay 0: returns new p, new m, |g|^2."""
    upd = jnp.sign(b1 * m + (1.0 - b1) * g)
    return p - lr * upd, b2 * m + (1.0 - b2) * g, jnp.sum(jnp.square(g))


@jax.jit
def _change(p, p0):
    return jnp.sqrt(jnp.sum(jnp.square(p - p0.astype(jnp.float32))))


class TrainReference:
    """Float32 parameters resident on the device(s) — layer i on device
    i mod n, the embedding and the head on the second and third — and the
    float32 Lion momentum on the HOST between steps (both do not fit beside
    the work of one layer).  Every step runs a layer at a time: forward
    keeping each layer's input, then backward with the layer recomputed and
    the update applied leaf by leaf.  ``step(ids)`` returns the loss of the
    batch BEFORE the update and the per-leaf gradient norms."""

    def __init__(self, weights, cfg, layers, lr, b1, b2, steps, quant=None):
        devs = jax.devices()
        self.cfg, self.key, self.layers, self.quant = cfg, cfg_key(cfg), layers, quant
        self.hyper = (np.float32(lr), np.float32(b1), np.float32(b2))
        self.steps_left = steps
        where = lambda name: devs[int(name.split(".")[1]) % len(devs)] if name.startswith("layers.") \
            else devs[(1 if name == "embed" else 2) % len(devs)]
        self.p0 = {k: jax.device_put(v, where(k)) for k, v in weights.items()}   # the seeded bf16
        self.p = {k: v.astype(jnp.float32) for k, v in self.p0.items()}
        self.m, self._flying = {}, []

    def _land(self, keep):
        """Finish the oldest momentum downloads, all but the newest ``keep``."""
        while len(self._flying) > keep:
            name, arr = self._flying.pop(0)
            self.m[name] = np.asarray(arr)
            arr.delete()

    def _update(self, name, grad, norms):
        p = self.p[name]
        m = self.m.pop(name, None)
        m = jnp.zeros_like(p) if m is None else jax.device_put(m, p.sharding)
        self.p[name], m, gsq = _lion(p, m, grad, *self.hyper)
        norms[name] = gsq
        if self.steps_left > 1:          # the last step's momentum is never read
            m.copy_to_host_async()
            self._flying.append((name, m))

    def _layer(self, i):
        return {k: self.p[f"layers.{i}.{k}"] for k in LAYER_KEYS}

    def step(self, ids):
        on = lambda x, name: jax.device_put(x, self.p[name].sharding)
        ids_e = on(jnp.asarray(ids, jnp.int32), "embed")
        x = self.p["embed"][ids_e]
        inputs = []
        for i in range(self.layers):
            x = on(x, f"layers.{i}.q")
            inputs.append(x)
            x = _block_jit(x, self._layer(i), self.key, self.quant)
        loss, (dx, d_norm, d_head) = _head_grad(
            on(x, "head"), self.p["final_norm"], self.p["head"], on(ids_e, "head"), self.key,
            self.quant)
        norms = {}
        self._update("final_norm", d_norm, norms)
        self._update("head", d_head, norms)
        for i in reversed(range(self.layers)):
            dx, d_lw = _block_vjp(inputs.pop(), self._layer(i), on(dx, f"layers.{i}.q"),
                                  self.key, self.quant)
            self._land(keep=len(LAYER_KEYS))
            for k in LAYER_KEYS:
                self._update(f"layers.{i}.{k}", d_lw[k], norms)
        d_embed = jnp.zeros(self.p["embed"].shape, jnp.float32).at[ids_e].add(on(dx, "embed"))
        self._update("embed", d_embed, norms)
        self._land(keep=0)
        self.steps_left -= 1
        return float(loss), {k: float(np.sqrt(float(v))) for k, v in norms.items()}

    def change_norms(self):
        """Per leaf, |p_now - p_seeded|."""
        return {k: float(_change(self.p[k], self.p0[k])) for k in self.p}
