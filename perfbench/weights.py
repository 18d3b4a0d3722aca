"""Seeded weights, made on the device in one jitted call, in bfloat16.

The benchmark makes the weights, never the program: the family adapter hands
the same arrays to the system under test and the plain reference upcasts them.
A leaf's values depend on the seed and the leaf's index only (partitionable
threefry), so a sharded call and an unsharded one give the same numbers, and
one leaf can be made again later without the others (``leaf_again``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """``--seed`` may exceed 31 bits; both halves go into the key."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf(key, index, shape, std):
    if std is None:  # a norm scale
        return jnp.ones(shape, jnp.bfloat16)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)


def make_weights(shapes: dict, seed: int, shardings: dict | None = None) -> dict:
    """``shapes``: name -> (shape, std or None), in a fixed order.  Returns
    name -> bf16 array, placed by ``shardings`` (name -> Sharding) if given."""
    names = list(shapes)

    def init(key):
        return {n: _leaf(key, i, *shapes[n]) for i, n in enumerate(names)}

    return jax.jit(init, out_shardings=shardings)(seed_key(seed))


def leaf_again(shapes: dict, name: str, key):
    """The seeded values of one leaf, for use INSIDE a jitted function."""
    return _leaf(key, list(shapes).index(name), *shapes[name])
