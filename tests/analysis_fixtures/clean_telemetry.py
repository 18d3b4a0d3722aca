"""CLEAN twins of ``planted_telemetry.py`` — the same timing shapes with
the hazard corrected (materialize before closing the clock), plus the
quiet shapes GL109 must not fire on.  Every function here must produce
zero findings.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def decorated_step(x):
    return jnp.tanh(x @ x)


jitted_step = jax.jit(lambda x: x * 2.0)


def times_with_block_until_ready(x):
    # the timed-loop idiom: materialize, then read the clock
    t0 = time.perf_counter()
    y = decorated_step(x)
    jax.block_until_ready(y)
    dt = time.perf_counter() - t0
    return y, dt


def times_with_float_fetch(x):
    t0 = time.perf_counter()
    out = jitted_step(x)
    loss = float(out.sum())
    dt = time.perf_counter() - t0
    return loss, dt


def times_with_host_materialization(x):
    start = time.monotonic()
    y = decorated_step(x)
    arr = np.asarray(y)
    elapsed = time.monotonic() - start
    return arr, elapsed


def times_plain_host_work(rows):
    # no jitted call between the clock reads: plain host timing is quiet
    t0 = time.perf_counter()
    total = sum(len(r) for r in rows)
    dt = time.perf_counter() - t0
    return total, dt


def jitted_call_outside_the_window(x):
    # the jitted call completes BEFORE the timed window opens
    y = decorated_step(x)
    t0 = time.perf_counter()
    total = int(np.asarray(y).sum())
    dt = time.perf_counter() - t0
    return total, dt
