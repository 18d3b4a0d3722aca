"""Pytree collectives and data movement — the L1 of the framework.

TPU-native re-design of reference ``utils/operations.py`` (871 LoC).  The
reference dispatches per backend (``_tpu_gather`` :301 / ``_gpu_gather`` :316)
over ``torch.distributed``; here there are two collective planes:

1. **In-jit** (the hot path): collectives are *implicit* — XLA inserts
   psum/all-gather from sharding annotations; explicit ones live in
   ``parallel/collectives.py`` for ``shard_map`` bodies.
2. **Host-level** (this module): eager cross-process ops on arbitrary pytrees
   for metrics/logging/checkpoint control flow — the direct analog of the
   reference's ``gather``/``broadcast``/``reduce``/``pad_across_processes``
   (operations.py:419/539/728/632), built on
   ``jax.experimental.multihost_utils``.

Debug mode (``ACCELERATE_DEBUG_MODE``) wraps each collective with a cross-rank
shape verification pass that turns would-be hangs into
``DistributedOperationException`` (reference ``verify_operation``
operations.py:364-398).
"""

from __future__ import annotations

import functools
import pickle
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.dataclasses import DistributedOperationException


def _state():
    from ..state import PartialState

    return PartialState()


def is_array_like(x: Any) -> bool:
    return isinstance(x, (np.ndarray, jax.Array)) or (
        hasattr(x, "shape") and hasattr(x, "dtype") and not isinstance(x, (str, bytes))
    )


def _container_spec(node) -> Optional[tuple]:
    """One level of pytree structure: ``(children, rebuild)`` for a container
    node, ``None`` for a leaf.

    ``rebuild`` is a closure that reassembles the *same* container type from a
    list of (possibly transformed) children — namedtuples via positional
    construction, Mappings via their own constructor with insertion order kept.
    This is the pytree registry the host-level ops run on; it mirrors what
    ``jax.tree_util`` does for jit-side trees but also accepts arbitrary
    ``Mapping`` subclasses (e.g. ``transformers.BatchEncoding``) that JAX's
    registry treats as opaque leaves.
    """
    if isinstance(node, Mapping):
        keys = list(node.keys())
        return [node[k] for k in keys], lambda vals: type(node)(dict(zip(keys, vals)))
    if isinstance(node, (list, tuple)):
        children = list(node)
        if hasattr(node, "_fields"):  # namedtuple: positional ctor
            return children, lambda vals: type(node)(*vals)
        return children, lambda vals: type(node)(vals)
    return None


def map_pytree(on_leaf: Callable[[Any], Any], node: Any) -> Any:
    """Depth-first structural map over list/tuple/namedtuple/Mapping nests,
    calling ``on_leaf`` on everything else and rebuilding containers with
    their original types via :func:`_container_spec`."""
    spec = _container_spec(node)
    if spec is None:
        return on_leaf(node)
    children, rebuild = spec
    return rebuild([map_pytree(on_leaf, child) for child in children])


def honor_type(obj, generator):
    """Rebuild ``obj``'s container type holding ``generator``'s values
    (kept for the reference's public-API contract, operations.py:62):
    namedtuples construct positionally, everything else — list/tuple/set,
    and dicts from a generator of pairs — through its own constructor."""
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        return type(obj)(*generator)
    return type(obj)(generator)


def recursively_apply(
    func: Callable,
    data: Any,
    *args,
    test_type: Callable[[Any], bool] = is_array_like,
    error_on_other_type: bool = False,
    **kwargs,
):
    """Map ``func`` over every array leaf of a nested list/tuple/dict pytree.

    The engine every host-level collective is built on (the role of reference
    operations.py:85): leaves matching ``test_type`` get ``func`` applied;
    other leaves pass through untouched, or raise when
    ``error_on_other_type`` — collectives set it so a stray non-array in a
    gathered pytree fails loudly instead of desyncing ranks.
    """

    def on_leaf(leaf):
        if test_type(leaf):
            return func(leaf, *args, **kwargs)
        if error_on_other_type:
            raise TypeError(
                f"Unsupported type {type(leaf)} passed to {getattr(func, '__name__', func)}; only nested "
                "list/tuple/dict of arrays are supported."
            )
        return leaf

    return map_pytree(on_leaf, data)


# ---------------------------------------------------------------------------
# Device movement (reference send_to_device operations.py:136)
# ---------------------------------------------------------------------------


def send_to_device(tensor, device=None, non_blocking: bool = True, skip_keys=None):
    """``jax.device_put`` over a pytree.  ``device`` may be a Device, a
    Sharding, or None (default device).  ``skip_keys`` are honored at every
    Mapping level (reference send_to_device operations.py:136-155)."""
    del non_blocking  # device_put is always async under JAX

    if isinstance(skip_keys, str):
        skip_keys = [skip_keys]
    if skip_keys and isinstance(tensor, Mapping):
        return type(tensor)(
            {
                k: (v if k in skip_keys else send_to_device(v, device, skip_keys=skip_keys))
                for k, v in tensor.items()
            }
        )
    if isinstance(tensor, (tuple, list)):
        return honor_type(tensor, (send_to_device(t, device, skip_keys=skip_keys) for t in tensor))

    def _send(t):
        return jax.device_put(t, device)

    return recursively_apply(_send, tensor)


def get_data_structure(data):
    """Shape/dtype skeleton of a pytree (reference operations.py:158) — used by
    the dispatching dataloader to broadcast batch structure."""

    def _info(t):
        return jax.ShapeDtypeStruct(np.shape(t), np.asarray(t).dtype if not hasattr(t, "dtype") else t.dtype)

    return recursively_apply(_info, data)


def initialize_tensors(data_structure):
    """Materialize zeros matching a skeleton (reference operations.py:185)."""

    def _init(t):
        return np.zeros(t.shape, t.dtype)

    return recursively_apply(_init, data_structure, test_type=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def find_batch_size(data) -> Optional[int]:
    """First dim of the first array leaf (reference operations.py:212)."""
    leaves = jax.tree_util.tree_leaves(data, is_leaf=is_array_like)
    for leaf in leaves:
        if is_array_like(leaf) and np.ndim(leaf) >= 1:
            return np.shape(leaf)[0]
    return None


def slice_tensors(data, tensor_slice, process_index=None, num_processes=None):
    """Slice every leaf along dim 0 (reference operations.py:589)."""

    def _slice(t):
        return t[tensor_slice]

    return recursively_apply(_slice, data)


def listify(data):
    """Convert array leaves to nested python lists (reference operations.py:240)."""

    def _to_list(t):
        return np.asarray(t).tolist()

    return recursively_apply(_to_list, data)


def convert_to_fp32(tensor):
    """Upcast float16/bfloat16 leaves to float32
    (reference operations.py:777-801)."""

    def _convert(t):
        return t.astype(jnp.float32)

    def _is_low_precision(t):
        # .dtype is read directly — np.asarray here would crash on tracers
        # (jit) and non-addressable global arrays, and force a device sync.
        dtype = getattr(t, "dtype", None)
        return is_array_like(t) and dtype in (jnp.float16, jnp.bfloat16)

    return recursively_apply(_convert, tensor, test_type=_is_low_precision)


class ConvertOutputsToFp32:
    """Decorator class keeping pickleability (reference operations.py:804-827)."""

    def __init__(self, model_forward):
        self.model_forward = model_forward
        functools.update_wrapper(self, model_forward)

    def __call__(self, *args, **kwargs):
        return convert_to_fp32(self.model_forward(*args, **kwargs))


convert_outputs_to_fp32 = ConvertOutputsToFp32


# ---------------------------------------------------------------------------
# Debug-mode shape verification (reference operations.py:364-398)
# ---------------------------------------------------------------------------


def _tree_shapes(data):
    return [
        (np.shape(leaf), str(np.asarray(leaf).dtype) if not hasattr(leaf, "dtype") else str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(data, is_leaf=is_array_like)
        if is_array_like(leaf)
    ]


def verify_operation(function):
    """Under ``ACCELERATE_DEBUG_MODE``, all-gather the pytree shapes before
    running the collective and raise on cross-rank mismatch — turning silent
    hangs into actionable errors (reference operations.py:364-398)."""

    @functools.wraps(function)
    def wrapper(*args, **kwargs):
        state = _state()
        if not state.debug or state.num_processes == 1:
            return function(*args, **kwargs)
        tensor = kwargs.get("tensor", args[0] if args else None)
        shapes = _tree_shapes(tensor)
        all_shapes = gather_object([shapes])
        if not all(s == all_shapes[0] for s in all_shapes):
            operation = f"{function.__module__}.{function.__name__}"
            raise DistributedOperationException(
                f"Cannot apply desired operation due to shape mismatches. All shapes across devices must be "
                f"valid.\n\nOperation: `{operation}`\nInput shapes:\n"
                + "\n".join(f"  - Process {i}: {s}" for i, s in enumerate(all_shapes))
            )
        return function(*args, **kwargs)

    return wrapper


# ---------------------------------------------------------------------------
# Host-level collectives
# ---------------------------------------------------------------------------


def _process_allgather(x, tiled: bool):
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(x, tiled=tiled)


@verify_operation
def gather(tensor):
    """Gather along dim 0 across processes (reference gather operations.py:419).

    Single-process worlds return the input unchanged — with GSPMD, per-device
    "ranks" don't exist at host level; a global sharded ``jax.Array`` already
    *is* the gathered value (use ``np.asarray`` to materialize).
    Multi-host: concatenates each process's local value along dim 0.
    """
    state = _state()
    if state.num_processes == 1:
        return tensor

    def _gather(t):
        return _process_allgather(np.asarray(t), tiled=True)

    return recursively_apply(_gather, tensor, error_on_other_type=True)


def gather_object(object: Any) -> list:
    """All-gather arbitrary picklable python objects
    (reference gather_object operations.py:445).  Returns the concatenated
    list of every process's (list-typed) input."""
    state = _state()
    if state.num_processes == 1:
        return object if isinstance(object, list) else [object]
    payload = np.frombuffer(pickle.dumps(object), dtype=np.uint8)
    sizes = _process_allgather(np.array([payload.size], dtype=np.int64), tiled=False).reshape(-1)
    max_size = int(sizes.max())
    padded = np.zeros(max_size, dtype=np.uint8)
    padded[: payload.size] = payload
    gathered = _process_allgather(padded, tiled=False).reshape(state.num_processes, max_size)
    out = []
    for i in range(state.num_processes):
        obj = pickle.loads(gathered[i, : int(sizes[i])].tobytes())
        if isinstance(obj, list):
            out.extend(obj)
        else:
            out.append(obj)
    return out


@verify_operation
def broadcast(tensor, from_process: int = 0):
    """Broadcast a pytree from ``from_process`` (reference operations.py:539).

    Any source rank wires through ``broadcast_one_to_all(is_source=...)`` —
    only the source contributes data, so the traffic is one tensor's worth
    regardless of pod size (an earlier non-zero-source path allgathered
    every rank's copy and selected one).
    """
    state = _state()
    if state.num_processes == 1:
        return tensor

    from jax.experimental import multihost_utils

    def _bcast(t):
        t = np.asarray(t)
        return np.asarray(
            multihost_utils.broadcast_one_to_all(
                t, is_source=state.process_index == from_process
            )
        )

    return recursively_apply(_bcast, tensor, error_on_other_type=True)


def broadcast_object_list(object_list: list, from_process: int = 0) -> list:
    """Broadcast picklable objects (reference operations.py:560).  Mutates and
    returns ``object_list`` like the reference."""
    state = _state()
    if state.num_processes == 1:
        return object_list
    gathered = gather_object([object_list])
    src = gathered[from_process]
    object_list[:] = src
    return object_list


@verify_operation
def reduce(tensor, reduction: str = "mean", scale: float = 1.0):
    """Cross-process reduce of a pytree (reference operations.py:728).

    Wired as a true all-reduce: each process contributes its slice of a
    process-axis global array and a jitted sum produces the replicated
    result — one reduction's traffic, not N allgathered copies landing on
    every host (same pod-scale fix as :func:`broadcast`)."""
    state = _state()

    def _reduce(t):
        t = np.asarray(t)
        if state.num_processes > 1:
            t = _sum_across_processes(t)
            if reduction == "mean":
                t = t / state.num_processes
        return t * scale

    return recursively_apply(_reduce, tensor, error_on_other_type=True)


@functools.lru_cache(maxsize=1)
def _reduce_plumbing():
    """(mesh over [proc, dev], jitted replicated sum) — built once so repeat
    reduce() calls hit the jit cache instead of re-tracing per call."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n_proc = jax.process_count()
    # group rows by owning process explicitly: device ids are not guaranteed
    # to be contiguous per host, and a row mixing hosts would hand
    # host_local_array_to_global_array shards this process doesn't own
    devices = np.array(sorted(jax.devices(), key=lambda d: (d.process_index, d.id)))
    mesh = Mesh(devices.reshape(n_proc, -1), ("proc", "dev"))
    summed = jax.jit(
        lambda x: jnp.sum(x, axis=0),
        out_shardings=NamedSharding(mesh, PartitionSpec()),
    )
    return mesh, summed


def _sum_across_processes(t: np.ndarray) -> np.ndarray:
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec

    mesh, summed = _reduce_plumbing()
    global_arr = multihost_utils.host_local_array_to_global_array(
        t[None], mesh, PartitionSpec("proc")
    )
    return np.asarray(
        multihost_utils.global_array_to_host_local_array(
            summed(global_arr), mesh, PartitionSpec()
        )
    )


def pad_across_processes(tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
    """Pad every process's arrays to the max size along ``dim`` so they can be
    gathered (reference operations.py:632-678)."""
    state = _state()

    def _pad(t):
        t = np.asarray(t)
        if dim >= t.ndim:
            return t
        if state.num_processes == 1:
            return t
        sizes = _process_allgather(np.array([t.shape[dim]], dtype=np.int64), tiled=False).reshape(-1)
        max_size = int(sizes.max())
        if t.shape[dim] == max_size:
            return t
        new_shape = list(t.shape)
        new_shape[dim] = max_size
        out = np.full(new_shape, pad_index, dtype=t.dtype)
        idx = [slice(None)] * t.ndim
        if pad_first:
            idx[dim] = slice(max_size - t.shape[dim], max_size)
        else:
            idx[dim] = slice(0, t.shape[dim])
        out[tuple(idx)] = t
        return out

    return recursively_apply(_pad, tensor, error_on_other_type=True)


def pad_input_tensors(tensor, batch_size: int, num_processes: int, dim: int = 0):
    """Pad dim 0 so batch divides evenly across processes
    (reference operations.py:681-725 — used by ``even_batches``)."""

    def _pad(t):
        t = np.asarray(t)
        remainder = batch_size % num_processes
        if remainder == 0:
            return t
        extra = num_processes - remainder
        reps = [t[:1]] * extra  # duplicate head samples (reference semantics)
        return np.concatenate([t] + reps, axis=dim)

    return recursively_apply(_pad, tensor, error_on_other_type=True)


def concatenate(data: list, dim: int = 0):
    """Concatenate a list of structurally-identical pytrees leafwise
    (reference operations.py:601)."""
    if isinstance(data[0], (tuple, list)):
        return honor_type(data[0], (concatenate([d[i] for d in data], dim=dim) for i in range(len(data[0]))))
    if isinstance(data[0], Mapping):
        return type(data[0])({k: concatenate([d[k] for d in data], dim=dim) for k in data[0].keys()})
    if not is_array_like(data[0]):
        raise TypeError(f"Can only concatenate arrays or nested list/tuple/dicts of arrays, got {type(data[0])}")
    if isinstance(data[0], jax.Array):
        return jnp.concatenate(data, axis=dim)
    return np.concatenate([np.asarray(d) for d in data], axis=dim)


# ---------------------------------------------------------------------------
# Global-array helpers (the GSPMD-native plane)
# ---------------------------------------------------------------------------


def host_local_to_global(batch, mesh, spec):
    """Form a global sharded ``jax.Array`` from per-process local data
    (the TPU-native dataloader boundary, SURVEY §2.2 'TPU-native equivalent')."""

    def _make(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            jax.sharding.NamedSharding(mesh, spec if not callable(spec) else spec(x)), x
        )

    return recursively_apply(_make, batch, error_on_other_type=True)


def global_to_host_local(tree):
    """Materialize global arrays to full host numpy values (inverse of
    :func:`host_local_to_global`).  Non-fully-addressable arrays are first
    resharded to fully-replicated (XLA all-gather) so every process gets one
    exact copy — no shard duplication or reordering."""

    def _get(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            mesh = x.sharding.mesh
            replicated = jax.jit(
                lambda a: a,
                out_shardings=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            )(x)
            return np.asarray(replicated.addressable_shards[0].data)
        return np.asarray(x)

    return recursively_apply(_get, tree)
