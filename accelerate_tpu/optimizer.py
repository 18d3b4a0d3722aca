"""Optimizer wrapper over optax transforms.

TPU-native re-design of reference ``optimizer.py`` (213 LoC,
``AcceleratedOptimizer`` :38).  The reference wraps a *built* torch optimizer
and gates ``step``/``zero_grad`` on ``GradientState.sync_gradients``
(:162/:113); under JAX the update is pure and lives inside the jitted train
step, so the user hands over the optimizer *construction* (an optax
``GradientTransformation``) — exactly the design shift SURVEY §7 'hard parts'
calls for: owning the train-state pytree kills the reference's
param-identity remapping dance (accelerator.py:1524-1568, 1693-1744).

The wrapper still exposes the reference's imperative surface (``step``,
``zero_grad``, ``is_overflow``, ``param_groups``-style hyperparam access) for
loop-compatibility: ``step()`` outside a prepared train step raises a clear
error instead of silently doing nothing.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax

from .state import AcceleratorState, GradientState


# ---------------------------------------------------------------------------
# Named optimizer recipes, constructible by name (both train cells of the
# benchmark run ``lion-sr``: perfbench/families/llama.py).  Families:
#   <base>      — fp32 masters, bf16 first moment (the stock recipe)
#   <base>-sr   — bf16 params with stochastic rounding, bf16 moments
#                 (ops/stochastic_rounding.py; no fp32 master tree)
#   <base>-sr8  — bf16 SR params + int8 blockwise moment state with
#                 SR-dithered requantization (ops/int8_state.py; the
#                 host-byte floor of the offload ladder)
# ---------------------------------------------------------------------------

OPTIMIZER_RECIPES: dict[str, str] = {
    "lion": "optax.lion, fp32 masters + bf16 momentum",
    "adamw": "optax.adamw, fp32 masters + bf16 first moment",
    "lion-sr": "bf16 SR params + bf16 momentum (16 -> 10 host-B/param)",
    "adamw-sr": "bf16 SR params + bf16 m/v (28 -> 14 host-B/param)",
    "lion-sr8": "bf16 SR params + int8 momentum (10 -> ~8 host-B/param)",
    "adamw-sr8": "bf16 SR params + int8 m + uint8 v (14 -> ~10 host-B/param)",
}


def reference_recipe(name: str) -> str:
    """The fp32-master reference recipe an -sr/-sr8 recipe is validated
    against (tests/test_stochastic_rounding.py, tests/test_int8_state.py;
    the train cells' ``correct`` holds ``lion-sr`` to a float32 plain Lion):
    ``lion-sr8`` -> ``lion``."""
    return name.split("-", 1)[0]


def make_optimizer(
    name: str,
    learning_rate: Optional[float] = None,
    *,
    weight_decay: float = 0.0,
    block_size: Optional[int] = None,
    seed: int = 0,
) -> optax.GradientTransformation:
    """Build a named optimizer recipe at its benchmarked hyperparameters.

    ``learning_rate`` defaults to the bench operating points (lion family
    1e-4, adam family 3e-4).  ``weight_decay`` is passed **explicitly** to
    every recipe — including the stock optax references, whose own defaults
    differ (optax.adamw 1e-4, optax.lion 1e-3) — so an SR-vs-reference
    comparison built from this registry really runs at the same
    hyperparameters (the sr_quality harness contract).  ``block_size``
    applies to the -sr8 recipes only (per-block scale granularity,
    default :data:`~.ops.int8_state.DEFAULT_BLOCK_SIZE`); ``seed`` keys
    the deterministic SR hash of the -sr/-sr8 recipes.
    """
    from .ops.int8_state import DEFAULT_BLOCK_SIZE, adamw_int8_sr, lion_int8_sr
    from .ops.stochastic_rounding import adamw_bf16_sr, lion_bf16_sr

    if name not in OPTIMIZER_RECIPES:
        raise ValueError(
            f"unknown optimizer recipe {name!r}; options: {sorted(OPTIMIZER_RECIPES)}"
        )
    if block_size is not None:
        if not name.endswith("-sr8"):
            raise ValueError(
                f"block_size only applies to the -sr8 int8-state recipes, got {name!r}"
            )
        if block_size < 1:
            # mirror the plugin knob's validation — the same value arriving
            # via --int8-block must not silently fall back or, worse, pass
            # a negative through to int8_scale_shape (one scale PER ELEMENT)
            raise ValueError(f"block_size must be >= 1, got {block_size}")
    lion_family = reference_recipe(name) == "lion"
    lr = learning_rate if learning_rate is not None else (1e-4 if lion_family else 3e-4)
    block = DEFAULT_BLOCK_SIZE if block_size is None else block_size
    if name == "lion":
        return optax.lion(lr, b1=0.9, b2=0.99, weight_decay=weight_decay,
                          mu_dtype=jnp.bfloat16)
    if name == "adamw":
        return optax.adamw(lr, b1=0.9, b2=0.999, eps=1e-8,
                           weight_decay=weight_decay, mu_dtype=jnp.bfloat16)
    if name == "lion-sr":
        return lion_bf16_sr(lr, b1=0.9, b2=0.99, weight_decay=weight_decay, seed=seed)
    if name == "adamw-sr":
        return adamw_bf16_sr(lr, b1=0.9, b2=0.999, eps=1e-8,
                             weight_decay=weight_decay, seed=seed)
    if name == "lion-sr8":
        return lion_int8_sr(lr, b1=0.9, b2=0.99, weight_decay=weight_decay,
                            seed=seed, block_size=block)
    return adamw_int8_sr(lr, b1=0.9, b2=0.999, eps=1e-8,
                         weight_decay=weight_decay, seed=seed, block_size=block)


class AcceleratedOptimizer:
    """Wraps an ``optax.GradientTransformation`` (reference optimizer.py:38).

    Attributes:
        tx: the optax transform (possibly wrapped with clipping/accumulation).
        learning_rate: the schedule or float the transform was built with, if
            known (used by trackers and ``AcceleratedScheduler``).
    """

    def __init__(
        self,
        tx: optax.GradientTransformation,
        learning_rate: Optional[Any] = None,
        scheduler=None,
    ):
        if not isinstance(tx, optax.GradientTransformation):
            raise TypeError(
                f"AcceleratedOptimizer expects an optax.GradientTransformation, got {type(tx)}. "
                "Hand over the optimizer *construction* (e.g. optax.adamw(lr)), not a stepped object."
            )
        self.tx = tx
        self.learning_rate = learning_rate
        self.scheduler = scheduler
        self.accelerator_state = AcceleratorState()
        self.gradient_state = GradientState()
        self._is_overflow = False
        self._accelerator_backward_called = False

    # -- functional surface (used by Accelerator/train step) ----------------

    def init(self, params):
        return self.tx.init(params)

    def update(self, grads, opt_state, params=None):
        return self.tx.update(grads, opt_state, params)

    # -- reference-API compatibility surface --------------------------------

    @property
    def step_was_skipped(self) -> bool:
        """Whether the last step overflowed (reference optimizer.py:197)."""
        return self._is_overflow

    def step(self, closure=None):
        raise RuntimeError(
            "Under accelerate_tpu the optimizer update runs inside the jitted train step. "
            "Use `state, metrics = accelerator.step(state, batch)` (or the function returned by "
            "`accelerator.prepare_train_step(loss_fn)`) instead of calling optimizer.step()."
        )

    def zero_grad(self, set_to_none: Optional[bool] = None):
        raise RuntimeError(
            "Gradients are functional values under JAX — there is nothing to zero. "
            "Remove optimizer.zero_grad() from the loop; the prepared train step handles accumulation."
        )

    def state_dict(self):
        raise RuntimeError(
            "Optimizer state lives in the TrainState pytree; use accelerator.save_state() "
            "or checkpoint the TrainState directly."
        )

    def __repr__(self):
        return f"AcceleratedOptimizer(tx={self.tx}, learning_rate={self.learning_rate})"
