"""Corrected twins of ``planted_ast_rules.py`` — graft-lint must stay
quiet on every one of these (GL202 host syncs,
GL204 impure calls under trace)."""

import time

import jax
import jax.numpy as jnp


@jax.jit
def step_without_host_syncs(x):
    # metrics stay abstract; the caller reads them outside the jit
    loss = (x * x).sum()
    return loss, jnp.mean(x)


def read_metrics_outside(step_out):
    # host sync is fine here: nothing in this function runs under trace
    loss, mean = step_out
    return float(loss), mean.item()


def step_with_threaded_inputs(x, stamp, key):
    # wall-clock and randomness ride in as arguments
    noise = jax.random.normal(key, x.shape)
    return x * stamp + noise


jitted_pure = jax.jit(step_with_threaded_inputs)


def make_inputs(x):
    # impurity lives outside the trace, threaded in per call
    return x, time.time(), jax.random.key(0)

