"""Stochastic rounding + the bf16-master lion and adamw optimizers.

The 7B host-offload step is host-DRAM-bound and its dominant traffic is
the fp32 master r/w (54 GB of the ~108 GB/step — docs/performance.md "The
7B-offload ceiling, accounted").  Keeping masters in bf16 halves that, but
plain bf16 masters diverge: with lion's tiny updates (|Δ| = lr) the
nearest-even round kills every update smaller than half a bf16 ulp of the
weight.  **Stochastic rounding** makes the round unbiased
(E[round(x)] = x), which is why bf16-master + SR training matches fp32
masters in practice (Gupta et al. 2015; standard on large TPU runs).

``lion_bf16_sr`` is an optax-compatible transform whose ``update`` is
per-leaf independent elementwise math — the exact contract the chunked
host-compute update region requires (accelerator.py
``host_update_chunk_gib``): no cross-leaf stats, deterministic key
derivation from a carried counter (no host RNG state).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


def stochastic_round_to_bf16(x: jax.Array, key: jax.Array) -> jax.Array:
    """Round fp32 ``x`` to bf16, randomly up/down with probability equal to
    the fractional position between the two neighboring bf16 values —
    unbiased: ``E[result] = x`` (up to fp32 arithmetic).

    Implementation: add uniform noise over the truncation gap to the fp32
    bit pattern, then truncate the mantissa (round-to-negative-infinity in
    magnitude after the add == stochastic round).  bf16 keeps the top 16
    bits of the fp32 pattern, so the gap is the low 16 bits.
    """
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    noise = jax.random.randint(
        key, x.shape, 0, 1 << 16, dtype=jnp.uint32
    )
    rounded = jax.lax.bitcast_convert_type(bits + noise, jnp.float32)
    # truncation of the low 16 bits == bf16 conversion of the bumped value
    return jax.lax.convert_element_type(
        jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rounded, jnp.uint32) & jnp.uint32(0xFFFF0000),
            jnp.float32,
        ),
        jnp.bfloat16,
    )


def stochastic_round_to_bf16_hashed(x: jax.Array, salt: jax.Array,
                                    consts: Optional[dict] = None,
                                    entropy: Optional[jax.Array] = None) -> jax.Array:
    """Stochastic round via a murmur-style hash of the value bits, a
    per-(step, leaf) ``salt``, and optional per-element ``entropy`` (the
    gradient, in the optimizer) — the host-region-safe variant.

    ``jax.random`` cannot run inside ``compute_on("device_host")``: its
    internal literal constants are device-space and elementwise ops reject
    mixed memory spaces (observed on v5e at 7B).  Hashing the fp32 bit
    pattern with traced scalars uses only elementwise ops, and when
    ``consts`` carries the hash constants as *traced* scalars (see
    ``lion_bf16_sr``) no literal-born full-leaf broadcast is materialized
    in the host region either.  ``entropy`` decorrelates elements whose
    values coincide (an all-equal leaf would otherwise round in lockstep);
    with both value and entropy constant across a leaf the noise is shared
    — unbiasedness per element still holds, only spatial variance grows.
    """
    c = consts or {}
    hi16 = c.get("hi16", jnp.uint32(0xFFFF0000))
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    noise = sr_noise_bits(x, salt, c, entropy=entropy)
    bumped = bits + noise
    return jax.lax.convert_element_type(
        jax.lax.bitcast_convert_type(bumped & hi16, jnp.float32), jnp.bfloat16
    )


def sr_noise_bits(x: jax.Array, salt: jax.Array, consts: Optional[dict] = None,
                  entropy: Optional[jax.Array] = None) -> jax.Array:
    """The ONE deterministic-SR noise stream: 16 uniform bits (uint32 in
    [0, 2^16)) hashed murmur-style from ``x``'s fp32 bit pattern, the salt,
    and the optional entropy channel.  Every SR consumer — the bf16 param
    write above, the int8/log-uint8 state requants (ops/int8_state.py) —
    draws through here, so the hash scheme can only change in one place
    (the ``_sr_hash_consts`` contract)."""
    c = consts or {}
    m1 = c.get("m1", jnp.uint32(0x9E3779B1))
    m2 = c.get("m2", jnp.uint32(0x85EBCA77))
    s16 = c.get("s16", jnp.uint32(16))
    s13 = c.get("s13", jnp.uint32(13))
    mask16 = c.get("mask16", jnp.uint32(0xFFFF))
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    h = bits ^ salt.astype(jnp.uint32)
    if entropy is not None:
        e = jax.lax.bitcast_convert_type(entropy.astype(jnp.float32), jnp.uint32)
        h = h ^ (e * m2)
    h = h * m1
    h = h ^ (h >> s16)
    h = h * m2
    h = h ^ (h >> s13)
    return h & mask16


def _sr_hash_consts(seed: int) -> dict:
    """The shared deterministic-SR key material, as traced uint32 scalars
    (inside a host region a LITERAL scalar materializes as a full-leaf-size
    broadcast — hoisted = resident, unhoisted = OOM; docs/offload.md,
    the 7B study made before PR 1).
    Both SR optimizers carry exactly these keys so the hash scheme can only
    change in one place."""
    return {
        "seed": jnp.uint32(seed),
        "m1": jnp.uint32(0x9E3779B1), "m2": jnp.uint32(0x85EBCA77),
        "s16": jnp.uint32(16), "s13": jnp.uint32(13),
        "mask16": jnp.uint32(0xFFFF), "hi16": jnp.uint32(0xFFFF0000),
    }


def _base_salt(count: jax.Array, hp: dict) -> jax.Array:
    """Per-step scalar salt (all scalar math — no leaf-size tensors)."""
    return (count.astype(jnp.uint32) + jnp.uint32(1)) * hp["m1"] ^ hp["seed"]


def _leaf_salt(base_salt: jax.Array, i: int, size: int) -> jax.Array:
    """Leaf-distinct salt; ``i`` is group-relative under the chunked host
    update, so the leaf size folds in as a stable-ish identity."""
    return base_salt ^ jnp.uint32((i * 2654435761 + size) & 0xFFFFFFFF)


def _fp32_deltas(new_leaves: list, old_leaves: list) -> list:
    """The optax delta contract: return fp32 differences.  Exact — the
    difference of two bf16 values is exact in fp32 (both have 8-bit
    mantissas and an optimizer step keeps their exponents close), and
    ``optax.apply_updates`` computes p + u in the promoted dtype before
    casting back to p.dtype, so the stochastically-rounded weight is
    reconstructed bit-for-bit.  A bf16 delta would round a second time."""
    return [
        np_.astype(jnp.float32) - p.astype(jnp.float32)
        for np_, p in zip(new_leaves, old_leaves)
    ]


class LionSRState(NamedTuple):
    count: jax.Array  # step counter; folds into the per-leaf SR key
    mu: optax.Updates  # bf16 momentum
    # hyperparams ride the state as TRACED scalars: under the XLA host-
    # compute lowering a *literal* scalar materializes as a full-leaf-size
    # fp32 broadcast (measured OOM at 7B — same issue inject_hyperparams
    # solves for the stock optimizers; docs/offload.md).  A dict, not a
    # tuple: the chunked host update slices params-congruent subtrees by
    # tree structure, and a 4-tuple could false-match a 4-leaf group.
    hyperparams: dict


def lion_bf16_sr(
    learning_rate: float = 1e-4,
    b1: float = 0.9,
    b2: float = 0.99,
    weight_decay: float = 0.0,
    seed: int = 0,
) -> optax.GradientTransformation:
    """Lion whose *parameters themselves* stay bf16 (no fp32 master tree).

    Math runs in fp32 transiently per leaf; the new weight is written back
    with stochastic rounding, so the expected update survives even when
    ``lr`` is below the local bf16 ulp.  State is the bf16 momentum plus a
    step counter (keys derive deterministically: fold_in(count, leaf_idx)
    — bit-exact resume without RNG state in the checkpoint).

    Use with ``mixed_precision="bf16"`` and bf16 params: vs
    ``optax.lion(mu_dtype=bfloat16)`` over fp32 masters, per-step traffic
    drops **16 → 10 B/param** (fp32 path: master r+w 8, momentum r+w 4,
    grad r 2, bf16 compute-copy w 2; SR path: param r+w 4, momentum r+w
    4, grad r 2 — the param IS the compute copy, so no cast write).

    Validated envelope: 600m/1.35B resident and 600m/7B offload on chip
    (859-888 tok/s/chip at 7B), held-out-quality-checked to 200 steps on
    the sr_quality harness (docs/performance.md).
    """

    def init(params):
        hyper = {
            k: jnp.float32(v)
            for k, v in (("lr", learning_rate), ("b1", b1), ("b2", b2),
                         ("wd", weight_decay))
        }
        hyper.update(_sr_hash_consts(seed))
        return LionSRState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.bfloat16), params),
            hyperparams=hyper,
        )

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("lion_bf16_sr is a weight update: pass params")
        hp = state.hyperparams
        lr_t, b1_t, b2_t, wd_t = hp["lr"], hp["b1"], hp["b2"], hp["wd"]
        count = state.count + 1
        base_salt = _base_salt(count, hp)
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state.mu)
        new_p, new_m = [], []
        for i, (g, p, m) in enumerate(zip(leaves, p_leaves, m_leaves)):
            g32 = g.astype(jnp.float32)
            m32 = m.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            direction = jnp.sign(b1_t * m32 + (1.0 - b1_t) * g32)
            step = lr_t * (direction + wd_t * p32)
            salt = _leaf_salt(base_salt, i, p.size)
            new_p.append(stochastic_round_to_bf16_hashed(p32 - step, salt, hp, entropy=g32))
            new_m.append((b2_t * m32 + (1.0 - b2_t) * g32).astype(jnp.bfloat16))
        deltas = _fp32_deltas(new_p, p_leaves)
        return (
            jax.tree_util.tree_unflatten(treedef, deltas),
            LionSRState(count=count, mu=jax.tree_util.tree_unflatten(treedef, new_m),
                        hyperparams=hp),
        )

    return optax.GradientTransformation(init, update)


class AdamWSRState(NamedTuple):
    count: jax.Array  # step counter; bias correction + per-leaf SR key
    mu: optax.Updates  # bf16 first moment (nearest round — see adamw_bf16_sr)
    nu: optax.Updates  # bf16 second moment, written back with SR
    hyperparams: dict  # traced scalars — same host-region contract as LionSRState


def adamw_bf16_sr(
    learning_rate: float = 1e-4,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    seed: int = 0,
) -> optax.GradientTransformation:
    """AdamW whose parameters AND both moments stay bf16 (no fp32 trees).

    Three bf16 trees, three rounding regimes, each chosen by the size of a
    step's increment relative to the stored value's bf16 ulp (2^-8 relative):

    - **params**: the update ``lr * m_hat / (sqrt(v_hat)+eps)`` is routinely
      below the weight's half-ulp, so the write-back uses **stochastic
      rounding** (exactly the lion_bf16_sr argument).
    - **mu**: moves by ``(1-b1)(g - m)`` per step — ~10% relative with the
      default b1=0.9, far above the bf16 ulp, so **nearest-even** is lossless
      in expectation (same as optax's own ``mu_dtype=bfloat16``).
    - **nu**: moves by ``(1-b2)(g² - v)`` — ~0.1% relative with b2=0.999,
      *below* the 0.39% bf16 ulp, so nearest-even freezes nu once it is
      warmed up and the effective lr silently stops adapting.  **SR** keeps
      ``E[nu]`` exact; the extra variance enters through ``sqrt`` (halved in
      relative terms) and is averaged by the b2 EMA itself.

    Per-step host traffic under ZeRO-offload: param r+w 4 + mu r+w 4 +
    nu r+w 4 + grad r 2 = **14 B/param**, vs the fp32-master adamw recipe's
    28 (masters 8, fp32 mu 8, fp32 nu 8, grad 2, bf16 compute-copy write 2)
    — an even larger relative cut than lion's 16 → 10.

    Same contracts as :func:`lion_bf16_sr`: per-leaf independent (safe under
    ``host_update_chunk_gib`` slicing), deterministic hashed SR (no RNG
    state; ``jax.random`` cannot run in host regions), traced-scalar
    hyperparams (a literal would materialize leaf-sized in the host region),
    fp32 delta return (exact — ``optax.apply_updates`` reconstructs the
    rounded weight bit-for-bit).

    Validated envelope: **1.35B resident (13.8k tok/s, 64.9% MFU) and 600m
    offload on chip; 7B pending host RAM** — four 7B attempts crashed the
    worker host on the 37.7 GiB pinned bf16-moment tree.  The int8-state
    variant ``adamw-sr8`` (ops/int8_state.py) shrinks that tree to
    ~25.2 GiB and is the expected unlock; its on-chip 7B validation is
    itself pending a chip (docs/performance.md "validated envelopes").
    """

    def init(params):
        hyper = {
            k: jnp.float32(v)
            for k, v in (("lr", learning_rate), ("b1", b1), ("b2", b2),
                         ("eps", eps), ("wd", weight_decay))
        }
        hyper.update(_sr_hash_consts(seed))
        # decorrelates the nu write's noise stream from the param write's
        hyper["nu_salt"] = jnp.uint32(0x27D4EB2F)
        zeros_bf16 = lambda p: jnp.zeros_like(p, jnp.bfloat16)
        return AdamWSRState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros_bf16, params),
            nu=jax.tree_util.tree_map(zeros_bf16, params),
            hyperparams=hyper,
        )

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("adamw_bf16_sr is a weight update: pass params")
        hp = state.hyperparams
        lr_t, b1_t, b2_t = hp["lr"], hp["b1"], hp["b2"]
        eps_t, wd_t = hp["eps"], hp["wd"]
        count = state.count + 1
        c32 = count.astype(jnp.float32)
        # bias corrections as traced scalars (integer_pow needs a static
        # exponent, so b^t goes through exp(t*log(b)))
        bc1 = 1.0 - jnp.exp(c32 * jnp.log(b1_t))
        bc2 = 1.0 - jnp.exp(c32 * jnp.log(b2_t))
        base_salt = _base_salt(count, hp)
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        new_p, new_m, new_v = [], [], []
        for i, (g, p, m, v) in enumerate(zip(leaves, p_leaves, m_leaves, v_leaves)):
            g32 = g.astype(jnp.float32)
            m32 = b1_t * m.astype(jnp.float32) + (1.0 - b1_t) * g32
            v32 = b2_t * v.astype(jnp.float32) + (1.0 - b2_t) * g32 * g32
            p32 = p.astype(jnp.float32)
            step = lr_t * ((m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps_t) + wd_t * p32)
            salt = _leaf_salt(base_salt, i, p.size)
            new_p.append(stochastic_round_to_bf16_hashed(p32 - step, salt, hp, entropy=g32))
            new_m.append(m32.astype(jnp.bfloat16))
            # nu's own SR stream: salted apart from the param write, entropy
            # from the (pre-EMA) squared grad so equal-valued lanes decouple
            new_v.append(
                stochastic_round_to_bf16_hashed(v32, salt ^ hp["nu_salt"], hp,
                                                entropy=g32 * g32)
            )
        deltas = _fp32_deltas(new_p, p_leaves)
        return (
            jax.tree_util.tree_unflatten(treedef, deltas),
            AdamWSRState(
                count=count,
                mu=jax.tree_util.tree_unflatten(treedef, new_m),
                nu=jax.tree_util.tree_unflatten(treedef, new_v),
                hyperparams=hp,
            ),
        )

    return optax.GradientTransformation(init, update)
