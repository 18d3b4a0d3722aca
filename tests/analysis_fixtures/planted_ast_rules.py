"""PLANTED BUGS — one per AST rule (GL202/GL204).

Linted as source only, never imported.  Each planted call sits inside a
function the engine must recognize as a jit context (decorated, passed to
``jax.jit``, or reached transitively from one).  Corrected twins:
``clean_ast_rules.py``.
"""

import random
import time

import jax
import numpy as np


@jax.jit
def step_with_host_syncs(x):
    loss = (x * x).sum()
    scalar = loss.item()          # GL202: device->host sync under trace
    host = np.asarray(x)          # GL202: materializes the tracer
    lr = float(x)                 # GL202: concretizes a traced argument
    return loss + scalar + host.sum() + lr


def _inner_metrics(x):
    # reached from step_with_impurity below — jit context by propagation
    return x.tolist()             # GL202: sync in transitively-jitted code


def step_with_impurity(x, seed):
    stamp = time.time()           # GL204: baked in at trace time
    jitter = random.random()      # GL204: host randomness drawn once
    noise = np.random.rand()      # GL204: numpy RNG under trace
    return x * stamp + jitter + noise + sum(_inner_metrics(x))


jitted_impure = jax.jit(step_with_impurity, static_argnums=(1,))

