"""Plain reference for the ``k_exaone`` model family (K-EXAONE-236B-A23B's
language model, ``model_type: exaone_moe``), given the SAME share of each
layer as the program: straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no batching
tricks, nothing imported from ``accelerate_tpu``.

One layer ``l`` (pre-norm, eps 1e-5), with ``n = rms_norm(x)``::

    h = x + Attn_l(n);   y = h + MLP_l(rms_norm(h));   final rms_norm, untied head
    q = W_q n (heads x 128), k = W_k n, v = W_v n (kv heads x 128), no bias;
        per-head rms_norm of q and k; rotary (theta 1e6, all 128 dims) on
        sliding_attention layers ONLY, none on full_attention layers
    o_t = W_o concat_heads softmax_s(q_t . k_s / sqrt(128) + mask) v_s   (8 q heads / kv head)
        mask: s <= t (full_attention);  t - 128 < s <= t (sliding_attention)
    dense MLP (layer 0):  W_down (silu(W_gate n) * W_up n), width 18,432
    sparse MLP (layers >= 1):
        s = sigmoid(W_r n) [128];  T = top8(s + b);  g_e = 2.5 s_e / sum_{j in T} s_j
        MLP(n) = E_shared(n) + sum_{e in T, e held here} g_e E_e(n)      (SwiGLUs of width 2048)
    MTP (cache-free tests only; weights ``mtp.*``): h'_t = W_p [rms_norm(h_t); rms_norm(Emb(x_{t+1}))],
        one full_attention sparse layer, the model's final norm and head: logits for x_{t+2}

**The share.**  The configuration's top-level ``num_attention_heads``,
``num_key_value_heads``, ``num_experts`` and ``vocab_size`` are what is HELD
(rank 0 of eight chips that share each layer); ``published`` has the model's
own counts and ``share.experts_held`` the global ids.  The router scores all
128 experts and normalises over all eight chosen; only the held experts' part
is added.  What absent heads and experts would add is left out, here as in the
program, and the partial result goes on to the next layer.

Weights are the benchmark's own (``perfbench/weights.py``), a flat dict:
``embed [V,H]``, ``layers.<i>.{attn_norm, q, k, v, o, q_norm, k_norm,
mlp_norm}`` (matrices ``[in, out]``), a dense layer's ``mlp_gate, mlp_up
[H,I]``, ``mlp_down [I,H]``, a sparse layer's ``router [H,E_all]``,
``router_bias [E_all]``, ``gate, up [E_held,H,F]``, ``down [E_held,F,H]``,
``shared_gate, shared_up [H,F]``, ``shared_down [F,H]``; ``final_norm [H]``,
``head [H,V]``.

``forward_logits`` returns an object, not an array (``kinds/serve.py`` pads
every sampled row to 18,432 positions: a float32 ``[4, 18432, 19200]`` array
is 5.7 GB): ``logits[row, span]`` runs that row up to ``span.stop``.

**Ties are not judged.**  The top-8 choice is discrete and every gate is
~2.5/8 (sigmoid scores of the chosen all lie near 1), so where the float32
scores of the eighth and the ninth expert lie within ``TIE_MARGIN`` of each
other and either is held here, a sound bf16 program may take the other one and
moves that token by a whole expert's output — as much as a dead expert moves
every token it is routed.  ``RowLogits`` reads such positions flat (gap 0
whatever token was served), so that the limit can stand close over the bf16
program's rounding and every planted fault reads far over it.

``quant`` is the CONTROL (``"int8"`` / ``"fp8"``: both operands of every dense
matmul, the experts' and the router's included).  The names of ``FAULTS`` in
its place plant ONE fault in a float32 forward (``prove.py --control
window,rope_global,...``).

How the reference blocks its work (each also a line of the configuration
file's ``departures``): every row of a call is cut to ONE length, the longest
row's own (its last token that is not padding) rounded up to 2,048 — causal,
so nothing earlier changes, and one length means one set of compiled programs
a run (a length a row cost ~40 s of compiles on the chip); the head runs over
spans padded to 2,048 positions for the same reason; attention runs 512 queries at a
time against the whole row with the mask; every SwiGLU (the dense layer's
18,432 columns, the shared expert, a routed expert) is run 2,048 columns of
width at a time, each upcast to float32 alone; the routed experts run one at a
time over the rows routed to them, in a buffer sized before each layer to the
most rows any held expert was routed (never drops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN_KEYS = ("attn_norm", "q", "k", "v", "o", "q_norm", "k_norm", "mlp_norm")
Q_BLOCK = 512            # queries attended at a time
ROW_ROUND = 2048         # a row is cut to a multiple of this
WIDTH_BLOCK = 2048       # columns of a SwiGLU's width upcast and run at a time
# A top-8 choice is discrete: where the float32 scores of the last expert
# chosen and the first left out lie closer than this (in units of score + bias)
# and either is held here, a bf16 program's rounding decides which it takes,
# and both are sound executions that differ by a whole expert's output for
# that token.  Such positions are not judged (``RowLogits``: their logits read
# flat); the configuration file's ``assumed.tie_margin`` states it, and the
# limits file says what share of the positions that is and what it buys.
TIE_MARGIN = 1e-3
FAULTS = {
    "window": "window layers attend every earlier key (the window mask dropped)",
    "rope_global": "rotary applied on the full-attention layers too",
    "shared": "the shared expert adds nothing",
    "expert": "held expert 7 adds nothing, as a grouped matmul that loses one group would",
    "share": "rows routed to ABSENT experts are multiplied by held experts' weights "
             "(expert e by held e % 16), as a grouped matmul that does not stop at the held rows would",
    "softmax": "softmax over the 128 logits in place of the sigmoid scores",
    "bias": "the selection ignores the bias b (top-8 of the scores alone)",
    "scale": "gate scale 1.0 in place of routed_scaling_factor 2.5",
}
NO_FAULT = np.zeros((len(FAULTS),), bool)


def split_control(quant):
    """A control's name -> (the precision of the matmuls, the planted faults' flags)."""
    if quant in FAULTS:
        return None, np.arange(len(FAULTS)) == list(FAULTS).index(quant)
    return quant, NO_FAULT


def _fake_quant(x, axis, quant):
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dense(x, w, quant):
    if quant is not None:
        x, w = _fake_quant(x, -1, quant), _fake_quant(w, 0, quant)
    return x @ w


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, ang):
    """x [T, heads, D], angles [T, D/2]; rotate-half convention."""
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def angles(positions, d, theta):
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    return jnp.asarray(positions, jnp.float32)[:, None] * inv[None, :]


def held_experts(cfg) -> tuple:
    share = cfg.get("share") or {}
    return tuple(share.get("experts_held", range(cfg["num_experts"])))


def cfg_key(cfg):
    routed = cfg.get("published", cfg)["num_experts"]
    return (("heads", cfg["num_attention_heads"]), ("kv_heads", cfg["num_key_value_heads"]),
            ("head_dim", cfg["head_dim"]), ("eps", cfg["rms_norm_eps"]),
            ("theta", float(cfg["rope_parameters"]["rope_theta"])),
            ("window", cfg["sliding_window"]), ("experts", routed),
            ("per_tok", cfg["num_experts_per_tok"]), ("norm_topk", bool(cfg["norm_topk_prob"])),
            ("scale", float(cfg["routed_scaling_factor"])), ("scoring", cfg["scoring_func"]),
            ("held", held_experts(cfg)),
            ("tie", float((cfg.get("assumed") or {}).get("tie_margin", TIE_MARGIN))))


def _highest(fn):
    """jit with the static names every piece here shares, at the highest precision."""
    def run(*args, key, quant=None, **static):
        with jax.default_matmul_precision("highest"):
            return fn(*args, c=dict(key), quant=quant, **static)
    run.__name__ = fn.__name__
    return jax.jit(run, static_argnames=("key", "quant", "sliding"))


@_highest
def _keys(x, lw, ang, flags, *, c, quant, sliding):
    """k (normed, rotated where the layer's kind says) and v of the row: [T, Hkv, D]."""
    n = rms_norm(x, lw["attn_norm"], c["eps"])
    hkv, d = c["kv_heads"], c["head_dim"]
    k = rms_norm(dense(n, lw["k"], quant).reshape(-1, hkv, d), lw["k_norm"], c["eps"])
    v = dense(n, lw["v"], quant).reshape(-1, hkv, d)
    rotate = sliding | dict(zip(FAULTS, flags))["rope_global"]
    return jnp.where(rotate, rope(k, ang), k), v


@_highest
def _attend_block(i, x, lw, ang, k, v, flags, *, c, quant, sliding):
    """``W_o`` applied to the held heads' attention of queries [i*Bq, (i+1)*Bq)
    of one row over the whole row's keys, masked."""
    hq, hkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    fault = dict(zip(FAULTS, flags))
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * bq, bq, axis=0)
    n = rms_norm(cut(x), lw["attn_norm"], c["eps"])
    q = rms_norm(dense(n, lw["q"], quant).reshape(bq, hq, d), lw["q_norm"], c["eps"])
    q = jnp.where(sliding | fault["rope_global"], rope(q, cut(ang)), q)
    at, s_pos = i * bq + jnp.arange(bq)[:, None], jnp.arange(t)[None, :]
    seen = s_pos <= at
    if sliding:
        seen &= (s_pos > at - c["window"]) | fault["window"]
    qg = q.reshape(bq, hkv, hq // hkv, d)
    s = jnp.einsum("thgd,shd->hgts", qg, k) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hgts,shd->thgd", p, v).reshape(bq, hq * d)
    return dense(out, lw["o"], quant)


@_highest
def _post_attention(x, attn, mlp_norm, *, c, quant):
    h = x + attn
    return h, rms_norm(h, mlp_norm, c["eps"])


@_highest
def _swiglu_columns(acc, n, wg, wu, wd, *, c, quant):
    """acc + W_down[cols] (silu(n W_gate[:, cols]) * n W_up[:, cols]): a block
    of a SwiGLU's width; the blocks of the whole width add up to the SwiGLU."""
    f32 = lambda w: jnp.asarray(w, jnp.float32)
    return acc + dense(jax.nn.silu(dense(n, f32(wg), quant)) * dense(n, f32(wu), quant), f32(wd), quant)


def swiglu(n, wg, wu, wd, key, quant, acc=None):
    """A SwiGLU ``[H, I] x 2, [I, H]`` over the rows ``n``, ``WIDTH_BLOCK``
    columns of its width at a time (each upcast alone)."""
    acc = jnp.zeros_like(n) if acc is None else acc
    for at in range(0, wg.shape[1], WIDTH_BLOCK):
        cols = slice(at, at + WIDTH_BLOCK)
        acc = _swiglu_columns(acc, n, wg[:, cols], wu[:, cols], wd[cols], key=key, quant=quant)
    return acc


@_highest
def _route(n, live, router, bias, flags, *, c, quant):
    """Each token's experts (global ids) and gates, the most live tokens any
    one held expert was routed, and the tokens whose choice is a tie."""
    fault = dict(zip(FAULTS, flags))
    logits = dense(n, router, quant)
    s = jnp.where(fault["softmax"], jax.nn.softmax(logits, axis=-1), jax.nn.sigmoid(logits))
    k = c["per_tok"]
    top, ranked = jax.lax.top_k(s + jnp.where(fault["bias"], 0.0, bias), min(k + 1, c["experts"]))
    experts = ranked[:, :k]
    held_here = jnp.asarray(np.isin(np.arange(c["experts"]), np.asarray(c["held"])))
    if ranked.shape[1] > k:     # the last chosen and the first left out, where either is held here
        tie = (top[:, k - 1] - top[:, k] < c["tie"]) & (held_here[ranked[:, k - 1]] | held_here[ranked[:, k]])
    else:
        tie = jnp.zeros(n.shape[:1], bool)
    gate = jnp.take_along_axis(s, experts, axis=-1)
    if c["norm_topk"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate * jnp.where(fault["scale"], 1.0, c["scale"])
    held = np.asarray(c["held"], np.int32)
    is_held = held_here[experts]
    experts = jnp.where(fault["share"] & ~is_held, jnp.asarray(held)[experts % len(held)], experts)
    gate = jnp.where(fault["expert"] & (experts == held[min(7, len(held) - 1)]), 0.0, gate)
    counts = jnp.zeros((c["experts"],), jnp.int32).at[experts].add(live[:, None].astype(jnp.int32))
    return experts, gate, jnp.max(counts[jnp.asarray(held)]), tie


@functools.partial(jax.jit, static_argnames=("cap", "quant"))
def _expert(acc, n, experts, gate, live, wg, wu, wd, expert_id, cap, quant):
    """Add to ``acc`` [T, H] what ONE expert gives the live tokens routed to
    it, over a buffer of ``cap`` rows."""
    hit = experts == expert_id
    mine = jnp.any(hit, axis=-1) & live
    g = jnp.sum(jnp.where(hit, gate, 0.0), axis=-1)
    rows, = jnp.nonzero(mine, size=cap, fill_value=n.shape[0])
    x = n.at[rows].get(mode="fill", fill_value=0.0)
    f32 = lambda w: jnp.asarray(w, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = dense(jax.nn.silu(dense(x, f32(wg), quant)) * dense(x, f32(wu), quant), f32(wd), quant)
    return acc.at[rows].add(y * g.at[rows].get(mode="fill", fill_value=0.0)[:, None], mode="drop")


@_highest
def _head(x, final_norm, head, *, c, quant):
    return dense(rms_norm(x, final_norm, c["eps"]), head, quant)


def sparse_mlp(weights, prefix, n, live, key, quant, flags):
    """The shared expert over every token plus the held experts' part, and
    which tokens' choice of experts is a tie (``TIE_MARGIN``)."""
    c = dict(key)
    w = lambda name: weights[f"{prefix}.{name}"]
    experts, gate, most, tie = _route(n, live, jnp.asarray(w("router"), jnp.float32),
                                      jnp.asarray(w("router_bias"), jnp.float32), flags,
                                      key=key, quant=quant)
    t = n.shape[0]
    cap = min(t, max(t // 8, 1 << max(3, (int(most) - 1).bit_length())))
    acc = jnp.zeros_like(n)
    for slot, expert_id in enumerate(c["held"]):
        acc = _expert(acc, n, experts, gate, live, w("gate")[slot], w("up")[slot], w("down")[slot],
                      expert_id, cap, quant)
    if dict(zip(FAULTS, np.asarray(flags)))["shared"]:
        return acc, tie
    return swiglu(n, w("shared_gate"), w("shared_up"), w("shared_down"), key, quant, acc), tie


def layer(weights, prefix, x, ang, need, key, quant, flags, sliding: bool, sparse: bool):
    """One decoder layer over one row x [T, H]; positions at or past ``need``
    are left unfinished (nothing before them reads them).  Returns the row
    and its tokens whose routing here is a tie (none in a dense layer)."""
    t = x.shape[0]
    bq = min(Q_BLOCK, t)
    lw = {k: jnp.asarray(weights[f"{prefix}.{k}"], jnp.float32) for k in ATTN_KEYS}
    k, v = _keys(x, lw, ang, flags, key=key, quant=quant, sliding=sliding)
    blocks = [_attend_block(b, x, lw, ang, k, v, flags, key=key, quant=quant, sliding=sliding)
              for b in range(-(-need // bq))]
    attn = jnp.concatenate(blocks)
    attn = jnp.pad(attn, ((0, t - attn.shape[0]), (0, 0)))
    del k, v, blocks
    h, n = _post_attention(x, attn, lw["mlp_norm"], key=key, quant=quant)
    if sparse:
        moe, tie = sparse_mlp(weights, prefix, n, jnp.arange(t) < need, key, quant, flags)
        return h + moe, tie
    w = lambda name: weights[f"{prefix}.{name}"]
    return h + swiglu(n, w("mlp_gate"), w("mlp_up"), w("mlp_down"), key, quant), jnp.zeros((t,), bool)


def layer_kinds(cfg, layers):
    return [(cfg["layer_types"][i] == "sliding_attention", cfg["mlp_layer_types"][i] == "sparse")
            for i in range(layers)]


def cut_length(need: int) -> int:
    """The length a row that is read up to ``need`` is run at: ``need``
    rounded up to ``ROW_ROUND`` (to ``Q_BLOCK`` for a short row)."""
    unit = ROW_ROUND if need > ROW_ROUND else min(Q_BLOCK, need)
    return -(-need // unit) * unit


def row_hidden(weights, cfg, layers, ids, need=None, quant=None, length=None, ties=False):
    """Hidden states before the final norm of ONE row ``ids`` [T], finished
    up to ``need`` and run at ``length`` positions (default: ``need``
    rounded up, :func:`cut_length`); returns [length, H], with ``ties`` also
    the positions [length] whose routing is a tie in some sparse layer."""
    quant, flags = split_control(quant)
    key = cfg_key(cfg)
    c = dict(key)
    ids = np.asarray(ids)
    need = ids.shape[0] if need is None else need
    t = cut_length(need) if length is None else length
    ids = np.pad(ids, (0, max(0, t - ids.shape[0])))[:t]
    x = jnp.asarray(weights["embed"][jnp.asarray(ids)], jnp.float32)
    ang = angles(np.arange(t), c["head_dim"], c["theta"])
    tied = jnp.zeros((t,), bool)
    for i, (sliding, sparse) in enumerate(layer_kinds(cfg, layers)):
        x, tie = layer(weights, f"layers.{i}", x, ang, need, key, quant, flags, sliding, sparse)
        tied |= tie
    return (x, tied) if ties else x


def _logits(weights, cfg, x, quant):
    return _head(x, jnp.asarray(weights["final_norm"], jnp.float32),
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
    ang = angles(np.arange(t + pad), c["head_dim"], c["theta"])
    y, _ = layer(weights, "mtp", x, ang, t, key, quant_, flags, sliding=False, sparse=True)
    return _logits(weights, cfg, y[:t], quant)


class RowLogits:
    """``logits[row, span]`` -> float32 [len(span), V]: the forward of
    ``ids[row]`` up to ``span.stop``, then the norm and the head on ``span``.
    Positions whose choice of experts is a tie in some sparse layer
    (``TIE_MARGIN``) read flat — every logit 0, so whatever token is judged
    there reads a gap of 0 — in the sound forward only: a control or a
    planted fault is judged against every position the sound forward judges.
    Every row runs at the length of the call's longest row (token ids are
    never 0, which is the benchmark's padding), and the head over the span
    padded to ``ROW_ROUND`` positions: one set of shapes a call."""

    def __init__(self, weights, cfg, layers, ids, quant):
        self.weights, self.cfg, self.layers = weights, cfg, layers
        self.ids, self.quant = np.asarray(ids), quant
        self.shape = self.ids.shape + (cfg["vocab_size"],)
        used = np.flatnonzero(self.ids.any(axis=0))
        self.length = cut_length(int(used[-1]) + 1 if used.size else 1)
        self.tied = 0           # positions read so far that were left out as ties

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
