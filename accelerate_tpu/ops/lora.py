"""Segment-batched multi-adapter LoRA: one gathered einsum for any tenant mix.

The multi-tenant serving problem (ROADMAP item 2, the most direct
"millions of users" scenario): thousands of LoRA adapters share one base
model, and a decode batch mixes requests from different tenants.  The naive
schedule — loop over adapters, run each tenant's rows through its own
``x @ A_t @ B_t`` — recompiles or re-dispatches per tenant mix and collapses
the batch the serving engine worked to fill.  The S-LoRA/BGMV discipline
batches the heterogeneous adapters instead:

- every resident adapter's A/B factors live **stacked** in HBM
  (``a_stack [P, d_in, r]``, ``b_stack [P, r, d_out]`` — P pool slots);
- each batch row carries an **adapter id** (a pool-slot index; id 0 is the
  reserved null adapter = base model);
- the adapter contribution is ONE gathered einsum over the ids,
  ``y[b] += (x[b] @ a_stack[ids[b]]) @ b_stack[ids[b]]`` — fixed shapes for
  any tenant mix, so the serving decode step stays a single compiled
  program no matter how many tenants are in flight.

Two execution paths, selected like the attention kernels
(``attn_implementation``-style dispatch):

- **native**: gather + batched einsum, XLA everywhere.  Bitwise-identical
  to applying each row's adapter sequentially (the per-request reference —
  pinned by tests/test_lora.py): a batched ``dot_general`` runs each batch
  slice as the same contraction, and id-0 rows return ``y`` itself through
  a ``where`` select, not ``y + 0``.
- **bgmv**: a Pallas gather-matmul kernel for batched T=1 decode — the ids
  ride as a scalar-prefetch operand so each grid step DMAs exactly its
  row's adapter block from the stack (no [B, d, r] gather materialized in
  HBM).  Interpret-mode parity is pinned on CPU; TPU measurement follows
  the paged-attention kernel's pending-chip caveat.

The device pool behind the stacks (hot-swap from host memmaps, LRU,
refcount pinning) is :class:`accelerate_tpu.serving.adapters.AdapterStore`.
"""

from __future__ import annotations

import functools
import threading
from contextlib import contextmanager
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu


# ---------------------------------------------------------------------------
# Kernel-mode dispatch (the attn_implementation-style ambient knob)
# ---------------------------------------------------------------------------

LORA_KERNELS = ("auto", "native", "bgmv")

_mode_state = threading.local()


def normalize_lora_kernel(mode: Optional[str]) -> str:
    mode = (mode or "auto").lower()
    if mode not in LORA_KERNELS:
        raise ValueError(f"lora kernel must be one of {LORA_KERNELS}, got {mode!r}")
    return mode


def set_lora_kernel(mode: Optional[str]) -> None:
    """Install the ambient LoRA kernel mode (trace-time dispatch; ``None``
    restores the ``auto`` default).  The serving engine installs the
    :class:`~accelerate_tpu.utils.dataclasses.LoraPlugin` mode at
    construction; tests reset via conftest like the collective-matmul knob."""
    _mode_state.mode = normalize_lora_kernel(mode) if mode is not None else "auto"


def lora_kernel_mode() -> str:
    return getattr(_mode_state, "mode", "auto")


@contextmanager
def lora_kernel(mode: str):
    """Scoped ambient kernel override (mirrors ``collective_matmul``)."""
    prev = lora_kernel_mode()
    set_lora_kernel(mode)
    try:
        yield
    finally:
        set_lora_kernel(prev)


def _resolve_kernel(mode: str, t: int) -> str:
    if mode == "auto":
        return "bgmv" if (_on_tpu() and t == 1) else "native"
    return mode


# ---------------------------------------------------------------------------
# The segment-batched adapter matmul
# ---------------------------------------------------------------------------


def lora_apply(x, y, a_stack, b_stack, adapter_ids, *, kernel: Optional[str] = None):
    """Add each row's adapter contribution to the base output ``y``.

    ``x``: ``[B, T, d_in]`` (or ``[B, d_in]``); ``y``: base matmul output
    with trailing dim ``d_out``; ``a_stack``/``b_stack``:
    ``[P, d_in, r]`` / ``[P, r, d_out]`` (slot 0 = the null adapter);
    ``adapter_ids``: ``[B]`` int32 pool-slot indices — id 0 rows come back
    **bitwise-unchanged** (a ``where`` select, not ``y + 0``, so a negative
    zero in the base output survives).

    One fixed-shape gathered contraction for any id mix: the batched
    program never re-specializes on which adapters are present, which is
    what keeps the serving decode step at one compiled executable under
    multi-tenant traffic (``strict_compiles``-enforced).
    """
    ids = adapter_ids.astype(jnp.int32)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
        y = y[:, None, :]
    t = x.shape[1]
    mode = _resolve_kernel(normalize_lora_kernel(kernel) if kernel is not None
                           else lora_kernel_mode(), t)
    if mode == "bgmv" and t == 1:
        delta = bgmv(x[:, 0], a_stack, b_stack, ids)[:, None]
    else:
        a = a_stack[ids].astype(x.dtype)            # [B, d_in, r]
        b = b_stack[ids].astype(x.dtype)            # [B, r, d_out]
        h = jnp.einsum("btd,bdr->btr", x, a)
        delta = jnp.einsum("btr,bro->bto", h, b)
    out = jnp.where((ids > 0)[:, None, None], y + delta.astype(y.dtype), y)
    return out[:, 0] if squeeze else out


def lora_apply_sequential(x, y, a_stack, b_stack, adapter_ids):
    """Per-request reference schedule: one adapter matmul per row, applied
    sequentially — what a tenant would get from a dedicated single-adapter
    pass.  The batched :func:`lora_apply` native path must reproduce this
    **bitwise** (tests/test_lora.py pins it); this reference is host-driven
    (python loop over rows) and exists for parity pins and the per-adapter
    -loop bench twin, not for serving."""
    ids = np.asarray(adapter_ids)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
        y = y[:, None, :]
    rows = []
    for i in range(x.shape[0]):
        if int(ids[i]) == 0:
            rows.append(y[i])
            continue
        a = a_stack[int(ids[i])].astype(x.dtype)
        b = b_stack[int(ids[i])].astype(x.dtype)
        h = jnp.einsum("btd,bdr->btr", x[i][None], a[None])
        delta = jnp.einsum("btr,bro->bto", h, b[None])
        rows.append(y[i] + delta[0].astype(y.dtype))
    out = jnp.stack(rows)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Pallas BGMV kernel (batched gather-matmul for T=1 decode)
# ---------------------------------------------------------------------------


def _bgmv_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """Grid: (slots,).  The BlockSpec index_map already routed this row's
    adapter A/B blocks into VMEM through the scalar-prefetched ids — the
    body is two small matmuls with fp32 accumulation."""
    del ids_ref  # consumed by the index_maps
    h = jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), a_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                    # [1, r]
    o_ref[0] = jax.lax.dot_general(
        h, b_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)                                # [1, d_out]


def bgmv(x, a_stack, b_stack, ids, *, interpret: Optional[bool] = None):
    """Batched gather-matmul ``(x[s] @ a_stack[ids[s]]) @ b_stack[ids[s]]``.

    ``x``: ``[S, d_in]`` (one token per decode slot); stacks as in
    :func:`lora_apply`; ``ids``: ``[S]`` int32.  Returns the adapter delta
    ``[S, d_out]`` in ``x.dtype`` (fp32-accumulated).  The ids are a
    scalar-prefetch operand, so each grid step DMAs exactly one adapter's
    factor blocks — the gathered ``[S, d_in, r]`` tensor never exists in
    HBM (the BGMV trick; id-0 rows read the null slot's zeros and the
    caller's ``where`` keeps them bitwise-clean).
    """
    if interpret is None:
        interpret = not _on_tpu()
    s_slots, d_in = x.shape
    pool, _, r = a_stack.shape
    d_out = b_stack.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_slots,),
        # x / out carry a unit middle axis: a one-row block of [S, 1, d]
        # has its last two dims equal to the array's, which the TPU
        # lowering's block-shape rule requires ((1, d) over [S, d] is refused)
        in_specs=[
            pl.BlockSpec((1, 1, d_in), lambda s, ids: (s, 0, 0)),
            pl.BlockSpec((1, d_in, r), lambda s, ids: (ids[s], 0, 0)),
            pl.BlockSpec((1, r, d_out), lambda s, ids: (ids[s], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d_out), lambda s, ids: (s, 0, 0)),
    )
    return pl.pallas_call(
        _bgmv_kernel,
        name="bgmv",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, 1, d_out), x.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), x[:, None, :], a_stack, b_stack)[:, 0]


# ---------------------------------------------------------------------------
# Adapter parameter plumbing (spec, pool, single-adapter init)
# ---------------------------------------------------------------------------

DEFAULT_LORA_TARGETS = ("q_proj", "v_proj")


def lora_spec(params, targets=DEFAULT_LORA_TARGETS) -> dict[str, tuple[int, int]]:
    """Map every LoRA-targeted module path to its kernel's ``(d_in, d_out)``.

    ``params`` is the model's variables dict (with or without the flax
    ``params`` wrapper — abstract ShapeDtypeStruct leaves work too); a
    module participates when its **name** (last path component) is in
    ``targets`` and it holds a 2-D ``kernel``.  Keys are '/'-joined module
    paths — the same paths the ``lora`` collection tree uses, so the spec
    IS the pool/adapter tree schema."""
    inner = params.get("params", params) if isinstance(params, dict) else params
    targets = tuple(targets)
    spec: dict[str, tuple[int, int]] = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        kernel = node.get("kernel")
        if (path and path[-1] in targets and kernel is not None
                and hasattr(kernel, "shape") and len(kernel.shape) == 2):
            spec["/".join(path)] = (int(kernel.shape[0]), int(kernel.shape[1]))
            return
        for k in sorted(node):
            if isinstance(node[k], dict):
                walk(node[k], path + (k,))

    walk(inner, ())
    if not spec:
        raise ValueError(
            f"no LoRA target modules found for targets={targets} — module "
            "names must match a path component holding a 2-D 'kernel'"
        )
    return spec


def _nest(flat: dict[str, Any]) -> dict:
    tree: dict = {}
    for key, leaf in flat.items():
        node = tree
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def init_lora_pool(spec: dict, pool_slots: int, rank: int, dtype=jnp.bfloat16) -> dict:
    """The device-resident adapter pool: per target path, zeroed
    ``a``/``b`` stacks with leading dim ``pool_slots + 1`` — slot 0 is the
    reserved **null adapter** (all zeros, never written), so id 0 means
    "base model" everywhere and an uninitialized slot can never leak a
    stale tenant's weights into a base request.

    The result is the ``lora`` variable-collection tree
    ``model.apply({"params": ..., "lora": pool}, ..., adapter_ids=ids)``
    consumes; :class:`~accelerate_tpu.serving.adapters.AdapterStore` owns
    its slot assignment/eviction."""
    if pool_slots < 1:
        raise ValueError(f"pool_slots must be >= 1, got {pool_slots}")
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    flat = {}
    for path, (d_in, d_out) in spec.items():
        flat[path] = {
            "a": jnp.zeros((pool_slots + 1, d_in, rank), dtype),
            "b": jnp.zeros((pool_slots + 1, rank, d_out), dtype),
        }
    return _nest(flat)


def init_adapter_params(rng, spec: dict, rank: int, *, alpha: float = 16.0,
                        dtype=jnp.bfloat16, init_b: str = "zeros") -> dict:
    """One tenant's adapter tree ``{path: {"a": [d_in, r], "b": [r, d_out]}}``.

    ``a`` draws Kaiming-style ``N(0, 1/d_in)``; ``b`` starts at zeros (the
    LoRA convention — a fresh adapter is an exact no-op) or, with
    ``init_b="normal"``, at small random values (test/bench fixtures need a
    nonzero delta).  The ``alpha / rank`` scaling is **folded into b** here,
    once, so the hot path's gathered einsum never multiplies by a scalar
    and a stored adapter is exactly what the matmul consumes."""
    flat = {}
    scaling = alpha / rank
    for i, (path, (d_in, d_out)) in enumerate(sorted(spec.items())):
        ka, kb = jax.random.split(jax.random.fold_in(rng, i))
        a = jax.random.normal(ka, (d_in, rank), jnp.float32) / np.sqrt(d_in)
        if init_b == "zeros":
            b = jnp.zeros((rank, d_out), jnp.float32)
        elif init_b == "normal":
            b = jax.random.normal(kb, (rank, d_out), jnp.float32) / np.sqrt(rank)
        else:
            raise ValueError(f"init_b must be 'zeros' or 'normal', got {init_b!r}")
        flat[path] = {"a": a.astype(dtype), "b": (b * scaling).astype(dtype)}
    return _nest(flat)


def adapter_param_count(spec: dict, rank: int) -> int:
    """Trainable params per adapter: ``sum_t r * (d_in + d_out)``."""
    return sum(rank * (d_in + d_out) for d_in, d_out in spec.values())


def adapter_state_accounting(spec: dict, rank: int, n_adapters: int, *,
                             optimizer: str = "lion-sr8",
                             dtype_bytes: int = 2) -> dict:
    """Predicted host-memory ladder for per-adapter optimizer state — the
    multi-tenant extension of the offload host-byte ladder
    (:data:`~accelerate_tpu.ops.streaming.HOST_BYTES_PER_PARAM`).

    Adapter states are tiny (``r * (d_in + d_out)`` params per target), so
    the int8-SR recipes hold per-tenant fp-master-free state out to huge
    tenant counts: the ladder reports bytes/adapter and total host GiB at
    ``n_adapters`` for the chosen recipe, next to the device pool's HBM
    cost per resident slot."""
    from .streaming import HOST_BYTES_PER_PARAM

    n_params = adapter_param_count(spec, rank)
    host_b_per_param = HOST_BYTES_PER_PARAM.get(optimizer, 16.0)
    per_adapter_state = int(n_params * host_b_per_param)
    per_adapter_weights = n_params * dtype_bytes
    gib = lambda b: round(b / 2**30, 6)
    return {
        "optimizer": optimizer,
        "rank": rank,
        "params_per_adapter": n_params,
        "weight_bytes_per_adapter": per_adapter_weights,
        "state_bytes_per_adapter": per_adapter_state,
        "n_adapters": n_adapters,
        "total_weight_gib": gib(per_adapter_weights * n_adapters),
        "total_state_gib": gib(per_adapter_state * n_adapters),
        # how many tenants one host fits at common DRAM sizes (state+weights)
        "adapters_per_host": {
            "64GiB": int(64 * 2**30 // max(per_adapter_state + per_adapter_weights, 1)),
            "256GiB": int(256 * 2**30 // max(per_adapter_state + per_adapter_weights, 1)),
        },
        "kind": "predicted",
    }
