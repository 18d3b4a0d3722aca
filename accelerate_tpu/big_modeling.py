"""Big-model loading & dispatch.

TPU-native re-design of reference ``big_modeling.py`` + ``utils/modeling.py``
+ ``utils/offload.py`` (SURVEY §2.7):

- ``init_empty_weights`` (reference big_modeling.py:61 monkey-patches
  ``register_parameter`` onto the meta device) → :func:`abstract_init` /
  ``init_empty_weights``: ``jax.eval_shape`` gives the ShapeDtypeStruct tree
  for free — no monkey-patching, no materialization.
- ``infer_auto_device_map`` (reference modeling.py:1278 greedy layer placement
  across gpu/cpu/disk budgets) → :func:`infer_auto_placement`: under GSPMD a
  *sharding plan* replaces the per-layer device map for multi-chip; the
  planner survives for **over-HBM** models, deciding which subtrees live in
  device HBM vs pinned host memory vs disk.
- ``load_checkpoint_in_model`` (reference modeling.py:1788 streams safetensor
  slices per device) → :func:`load_checkpoint_in_model`: safetensors shards
  stream **directly into device shards** per NamedSharding — each host
  touches only bytes it owns; host/disk-assigned leaves become lazy memmaps.
- ``AlignDevicesHook`` forward hooks (reference hooks.py:227 move weights
  in/out per-forward) → :func:`offloaded_apply`: a functional wrapper that
  fetches offloaded leaves before ``apply`` and drops them after — same
  capability, no monkey-patched ``forward``.
- ``OffloadedWeightsLoader`` (reference offload.py:127 lazy mmap of .dat +
  index.json) → :class:`OffloadStore`, same on-disk format idea.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
from pathlib import Path
from typing import Any, Callable, Mapping, Optional, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .logging import get_logger
from .utils.constants import SAFE_WEIGHTS_INDEX_NAME

logger = get_logger(__name__)


# ---------------------------------------------------------------------------
# Abstract init (meta device analog)
# ---------------------------------------------------------------------------


def abstract_init(module, rng, *sample_args, **sample_kwargs):
    """ShapeDtypeStruct tree of a flax module's params — zero memory."""
    return jax.eval_shape(lambda: module.init(rng, *sample_args, **sample_kwargs))


def init_params_leafwise(model, accelerator, sample_ids, *, scale: float = 0.02,
                         dtype=None):
    """Materialize params leaf-by-leaf straight into their planned shards —
    peak device memory is one leaf, like the streaming checkpoint loader.

    This is the big-model alternative to ``Accelerator.init_params`` when
    the full-precision tree exceeds HBM (e.g. 7B fp32 masters on a 16GiB
    chip under host offload): flax's monolithic init executable stages the
    whole tree on device before writing outputs (measured OOM at 7B).  The
    initialization is *synthetic* (normal(0, scale) matrices, ones for
    norm scales, zeros elsewhere) — real 7B flows load trained weights via
    :func:`load_checkpoint_in_model`, which is leaf-streamed already.
    """
    import jax.numpy as jnp

    from .parallel.sharding import host_offload_supported, host_plan, path_str

    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), sample_ids))
    if dtype is not None:
        # storage-dtype override: bf16 "masters" for the stochastic-rounding
        # optimizer path (halves the host/PCIe bytes of every param leaf)
        abstract = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            abstract,
        )
    plan = accelerator._params_plan(abstract)
    if accelerator._offload_flags()[1] and host_offload_supported():
        plan = host_plan(plan)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    shardings = jax.tree_util.tree_leaves(plan, is_leaf=lambda x: hasattr(x, "spec"))
    # one jit per distinct (kind, shape, dtype, sharding) — NOT per leaf
    # (a per-leaf closure would pay a full compile hundreds of times)
    jits: dict = {}

    def initializer(kind, shape, dtype, sh):
        key = (kind, shape, str(dtype), sh)
        if key not in jits:
            if kind == "normal":
                jits[key] = jax.jit(
                    lambda k: (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype),
                    out_shardings=sh,
                )
            elif kind == "ones":
                jits[key] = jax.jit(lambda: jnp.ones(shape, dtype), out_shardings=sh)
            else:
                jits[key] = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sh)
        return jits[key]

    out = []
    for i, ((path, sds), sh) in enumerate(zip(flat, shardings)):
        name = path_str(path)
        if sds.ndim >= 2:
            out.append(initializer("normal", sds.shape, sds.dtype, sh)(jax.random.key(i)))
        elif "scale" in name or "norm" in name.lower():
            out.append(initializer("ones", sds.shape, sds.dtype, sh)())
        else:
            out.append(initializer("zeros", sds.shape, sds.dtype, sh)())
    return jax.tree_util.tree_unflatten(treedef, out)


@contextlib.contextmanager
def init_empty_weights(include_buffers: bool = False):
    """API-parity context (reference :61).  Under JAX initialization is
    already lazy/functional; the context exists so ported user code runs
    unchanged — inside it, use :func:`abstract_init` instead of
    ``module.init``."""
    yield


init_on_device = init_empty_weights


# ---------------------------------------------------------------------------
# Size accounting (reference compute_module_sizes modeling.py:651)
# ---------------------------------------------------------------------------


def _dtype_size(dtype) -> int:
    return np.dtype(dtype).itemsize if not hasattr(dtype, "itemsize") else dtype.itemsize


def compute_module_sizes(params, prefix: str = "") -> dict[str, int]:
    """Bytes per subtree path ('' = total), like reference modeling.py:651."""
    sizes: dict[str, int] = {}

    def _walk(node, path):
        if isinstance(node, Mapping):
            total = 0
            for k, v in node.items():
                total += _walk(v, f"{path}.{k}" if path else str(k))
            sizes[path] = total
            return total
        if isinstance(node, (list, tuple)):
            total = 0
            for i, v in enumerate(node):
                total += _walk(v, f"{path}.{i}" if path else str(i))
            sizes[path] = total
            return total
        nbytes = int(np.prod(node.shape)) * _dtype_size(node.dtype) if hasattr(node, "shape") else 0
        sizes[path] = nbytes
        return nbytes

    total = _walk(params, prefix)
    sizes[""] = total
    return sizes


def get_max_memory(max_memory: Optional[dict] = None) -> dict:
    """Available budget per target (reference get_max_memory modeling.py:744):
    one entry per local device (HBM limit) + 'cpu' (host RAM).  Values may be
    overridden with ints or strings like '10GB'."""
    from .checkpointing import parse_size

    if max_memory is not None:
        return {
            k: (parse_size(v) if isinstance(v, str) else v) for k, v in max_memory.items()
        }
    out = {}
    for i, d in enumerate(jax.local_devices()):
        stats = d.memory_stats() or {}
        # leave 10% headroom like the reference's 90% scaling
        out[i] = int(stats.get("bytes_limit", 16 * 2**30) * 0.9)
    try:
        import psutil

        out["cpu"] = int(psutil.virtual_memory().available * 0.9)
    except ImportError:
        out["cpu"] = int(_available_host_memory() * 0.9)
    return out


def _available_host_memory() -> int:
    """Available (not total) host RAM, /proc/meminfo fallback for no-psutil
    hosts; budgeting total RAM would overcommit an already-loaded host."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    # last resort: assume half of physical RAM is usable
    return int(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES") * 0.5)


# ---------------------------------------------------------------------------
# Placement planner (device_map analog for over-HBM models)
# ---------------------------------------------------------------------------


def _placement_units(
    params, sizes: dict[str, int], max_unit: int, no_split: frozenset[str]
) -> list[str]:
    """Split the tree into placement units: descend into any subtree larger
    than ``max_unit`` (the biggest single budget) unless it is listed in
    ``no_split``; keep tree (layer) order so adjacent layers stay on the
    same tier (reference infer_auto_device_map iterates modules in order)."""
    units: list[str] = []

    def _walk(node, path):
        splittable = (
            isinstance(node, Mapping)
            and len(node) > 0
            and path not in no_split
            and (path == "" or sizes.get(path, 0) > max_unit)
        )
        if splittable:
            for k, v in node.items():
                _walk(v, f"{path}.{k}" if path else str(k))
        elif path:
            units.append(path)

    _walk(params, "")
    return units


def infer_auto_placement(
    params,
    max_memory: Optional[dict] = None,
    no_split_paths: Optional[list[str]] = None,
    offload_to_disk: bool = True,
) -> dict[str, Union[int, str]]:
    """Greedy assignment of subtrees to device HBM / 'cpu' / 'disk' budgets
    (reference infer_auto_device_map modeling.py:1278).  Returns
    {subtree_path: target} with dot-separated paths.  Subtrees too big for
    any single budget are recursively split down to leaves (flax trees have
    a single 'params' root, so descending is required for tiering to do
    anything); ``no_split_paths`` pins listed subtrees to one tier.  Under
    GSPMD multi-chip sharding handles *splitting*; this planner handles
    *capacity overflow* (host/disk tiers for >HBM models)."""
    budgets = dict(get_max_memory(max_memory))
    sizes = compute_module_sizes(params)
    device_targets = [k for k in budgets if isinstance(k, int)]
    order = device_targets + ["cpu"] + (["disk"] if offload_to_disk else [])
    # Units larger than the biggest HBM budget are split so device memory can
    # still be packed; cpu budget is the ceiling only when no devices exist.
    max_unit = max(
        (budgets[t] for t in device_targets),
        default=budgets.get("cpu", 0),
    )
    units = _placement_units(params, sizes, max_unit, frozenset(no_split_paths or ()))

    placement: dict[str, Union[int, str]] = {}
    for path in units:
        size = sizes.get(path, 0)
        placed = False
        for target in order:
            if target == "disk":
                placement[path] = "disk"
                placed = True
                break
            if budgets.get(target, 0) >= size:
                budgets[target] -= size
                placement[path] = target
                placed = True
                break
        if not placed:
            raise ValueError(
                f"Cannot place subtree {path!r} ({size} bytes) within max_memory {budgets}; "
                "enable offload_to_disk or raise budgets"
            )
    return placement


# ---------------------------------------------------------------------------
# Offload store (reference utils/offload.py)
# ---------------------------------------------------------------------------


class OffloadStore:
    """Disk-backed weights: one .dat memmap per tensor + index.json
    (reference OffloadedWeightsLoader offload.py:127 format)."""

    def __init__(self, save_folder: Union[str, os.PathLike], autoflush: bool = True):
        self.folder = Path(save_folder)
        self.folder.mkdir(parents=True, exist_ok=True)
        self.index_file = self.folder / "index.json"
        self.autoflush = autoflush
        self._dirty = False
        self.index: dict[str, dict] = (
            json.loads(self.index_file.read_text()) if self.index_file.exists() else {}
        )

    def save(self, key: str, array) -> None:
        arr = np.asarray(array)
        path = self.folder / f"{key.replace('/', '--')}.dat"
        mm = np.memmap(path, dtype=arr.dtype, mode="w+", shape=arr.shape or (1,))
        mm[...] = arr.reshape(arr.shape or (1,))
        mm.flush()
        self.index[key] = {"dtype": str(arr.dtype), "shape": list(arr.shape)}
        self._dirty = True
        if self.autoflush:
            self.flush()

    def flush(self) -> None:
        """Write index.json once; bulk writers pass autoflush=False and call
        this at the end (index rewrite per tensor is O(n²) over a 10k-tensor
        checkpoint)."""
        if self._dirty:
            self.index_file.write_text(json.dumps(self.index))
            self._dirty = False

    def load(self, key: str) -> np.ndarray:
        meta = self.index[key]
        path = self.folder / f"{key.replace('/', '--')}.dat"
        shape = tuple(meta["shape"])
        return np.memmap(path, dtype=np.dtype(meta["dtype"]), mode="r", shape=shape or (1,)).reshape(shape)

    def keys(self):
        return self.index.keys()

    def __contains__(self, key):
        return key in self.index


def offload_state_dict(save_dir: str, state_dict: Mapping[str, Any]) -> OffloadStore:
    """reference offload_state_dict (offload.py:85)."""
    store = OffloadStore(save_dir, autoflush=False)
    for k, v in state_dict.items():
        store.save(k, v)
    store.flush()
    return store


def offload_store_params(store: OffloadStore) -> dict:
    """Rebuild the nested params pytree from an :class:`OffloadStore` as
    **lazy memmap leaves** — the disk tier behind
    :func:`~accelerate_tpu.generation.generate_streamed`.

    Each leaf stays an ``np.memmap`` until its layer's turn to stream, so
    building the tree costs no RAM; ``generate_streamed``'s
    :class:`~accelerate_tpu.ops.streaming.LayerPrefetcher` then uploads
    layer *k+1* straight from its ``.dat`` files into the device-side double
    buffer while layer *k*'s matmuls run (page-cache-warm files overlap like
    host RAM; cold files add the disk read to the hidden transfer).  Keys
    are the '/'-joined tree paths :func:`offload_state_dict` /
    :func:`load_checkpoint_in_model` wrote."""
    tree: dict = {}
    for key in store.keys():
        parts = key.split("/")
        node = tree
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = store.load(key)
    return tree


# ---------------------------------------------------------------------------
# Checkpoint streaming into shards
# ---------------------------------------------------------------------------


def _path_key(path) -> str:
    """'/'-joined key for a tree_flatten_with_path path (DictKey/SequenceKey/
    GetAttrKey all covered)."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def _normalize_placement(placement: Mapping[str, Any]) -> dict[str, Any]:
    """Placement maps use dot-separated paths (the compute_module_sizes /
    infer_auto_placement convention) but '/' is accepted too."""
    return {k.replace(".", "/"): v for k, v in placement.items()}


def _lookup_placement(key: str, normalized: Mapping[str, Any]):
    """Most-specific entry for '/'-keyed ``key`` in a ``_normalize_placement``
    result; ancestors match, deepest wins."""
    parts = key.split("/")
    for depth in range(len(parts), 0, -1):
        hit = normalized.get("/".join(parts[:depth]))
        if hit is not None:
            return hit
    return normalized.get("")  # root catch-all ({"": "cpu"} = whole tree)


def _iter_checkpoint_tensors(checkpoint_path):
    """Yield (name, numpy array (possibly lazy)) from a file, a sharded dir,
    or — for stream adapters like hf_interop's expert stacking — any
    already-built iterable of (name, array) pairs, passed through."""
    if not isinstance(checkpoint_path, (str, os.PathLike)):
        yield from checkpoint_path
        return
    p = Path(checkpoint_path)
    files: list[Path]
    if p.is_dir():
        index = p / SAFE_WEIGHTS_INDEX_NAME
        if index.exists():
            names = sorted(set(json.loads(index.read_text())["weight_map"].values()))
            files = [p / n for n in names]
        else:
            files = sorted(p.glob("*.safetensors")) or sorted(p.glob("*.npz"))
    else:
        files = [p]
    for f in files:
        if f.suffix == ".safetensors":
            from .utils.serialization import LazySafetensorsFile

            sf = LazySafetensorsFile(str(f))
            for name in sf.keys():
                yield name, sf.get(name)
        elif f.suffix == ".npz":
            data = np.load(f)
            for name in data.files:
                yield name, data[name]
        else:
            raise ValueError(f"unsupported checkpoint file {f}")


def load_checkpoint_in_model(
    abstract_params,
    checkpoint: Union[str, os.PathLike],
    sharding_plan=None,
    dtype=None,
    offload_placement: Optional[dict[str, Union[int, str]]] = None,
    offload_folder: Optional[str] = None,
    strict: bool = False,
    key_map: Optional[Callable[[str], str]] = None,
    tensor_map: Optional[Callable[[str, np.ndarray], np.ndarray]] = None,
):
    """Stream a checkpoint directly into (sharded) device arrays.

    ``abstract_params``: pytree of ShapeDtypeStruct (from abstract_init) or
    real arrays; ``sharding_plan``: matching pytree of NamedSharding (e.g.
    from make_sharding_plan).  Tensors assigned to 'cpu'/'disk' by
    ``offload_placement`` stay on host / in an OffloadStore.

    ``key_map``/``tensor_map`` adapt FOREIGN checkpoint layouts at stream
    time: key_map renames (return None to skip a tensor), tensor_map
    receives (our_key, array) and may transpose/reshape — e.g. torch
    ``Linear.weight`` [out, in] into a flax kernel [in, out]; see
    ``models/hf_interop.py`` for the HuggingFace-format maps.

    Returns (params pytree, OffloadStore|None).  reference:
    load_checkpoint_in_model modeling.py:1788 + set_module_tensor_to_device
    :217 — but no per-layer hooks: arrays land in their final shards.
    """
    flat_abstract = {
        _path_key(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_params)[0]
    }
    flat_plan = {}
    if sharding_plan is not None:
        flat_plan = {
            _path_key(path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                sharding_plan, is_leaf=lambda x: isinstance(x, NamedSharding)
            )[0]
        }
    store = OffloadStore(offload_folder, autoflush=False) if offload_folder else None
    normalized_placement = _normalize_placement(offload_placement) if offload_placement else None
    loaded: dict[str, Any] = {}
    unexpected = []

    def _normalize(name: str) -> Optional[str]:
        name = key_map(name) if key_map else name
        return None if name is None else name.replace(".", "/")

    try:
        for name, tensor in _iter_checkpoint_tensors(checkpoint):
            key = _normalize(name)
            if key is None:  # key_map skip (e.g. HF rotary inv_freq buffers)
                continue
            if key not in flat_abstract:
                unexpected.append(name)
                continue
            target_dtype = dtype or flat_abstract[key].dtype
            tensor = np.asarray(tensor)
            if tensor_map is not None:
                tensor = np.asarray(tensor_map(key, tensor))
            if tuple(tensor.shape) != tuple(flat_abstract[key].shape):
                raise ValueError(
                    f"shape mismatch for {name}: checkpoint {tensor.shape} vs model {flat_abstract[key].shape}"
                )
            placement = _lookup_placement(key, normalized_placement) if normalized_placement else None
            if placement == "disk":
                if store is None:
                    raise ValueError("offload_placement says 'disk' but no offload_folder given")
                store.save(key, tensor.astype(target_dtype))
                loaded[key] = store.load(key)
            elif placement == "cpu":
                loaded[key] = tensor.astype(target_dtype)
            else:
                sharding = flat_plan.get(key)
                # cast on HOST before the transfer: device_put ships exactly
                # the target dtype's bytes (fp32 ckpt -> bf16 target halves
                # H2D traffic, which dominates load time on thin links)
                arr = tensor if tensor.dtype == np.dtype(target_dtype) else tensor.astype(target_dtype)
                if sharding is not None:
                    loaded[key] = jax.device_put(arr, sharding)
                elif isinstance(placement, (int, np.integer)):
                    loaded[key] = jax.device_put(arr, jax.local_devices()[int(placement)])
                else:
                    loaded[key] = jax.device_put(arr)
    finally:
        # keep index.json consistent with any .dat files already rewritten,
        # even when a shape-mismatch/strict error aborts the stream
        if store is not None:
            store.flush()

    missing = [k for k in flat_abstract if k not in loaded]
    if strict and (missing or unexpected):
        raise ValueError(f"missing keys: {missing}; unexpected keys: {unexpected}")
    for k in missing:
        logger.warning("key %s missing from checkpoint; leaving abstract", k)
        loaded[k] = flat_abstract[k]

    # unflatten back to the original structure
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    leaves = [loaded[_path_key(path)] for path, _ in paths_leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves), store


def load_checkpoint_and_dispatch(
    module,
    checkpoint: Union[str, os.PathLike],
    rng=None,
    sample_args: tuple = (),
    sample_kwargs: Optional[dict] = None,
    mesh: Optional[Mesh] = None,
    device_map: Union[str, dict, None] = "auto",
    max_memory: Optional[dict] = None,
    offload_folder: Optional[str] = None,
    dtype=None,
    strict: bool = False,
    key_map: Optional[Callable[[str], str]] = None,
    tensor_map: Optional[Callable[[str, np.ndarray], np.ndarray]] = None,
):
    """One-call UX (reference load_checkpoint_and_dispatch big_modeling.py:513):
    abstract-init the module, plan sharding/offload, stream the checkpoint
    into final placement.  Returns (params, offload_store).
    ``key_map``/``tensor_map`` adapt foreign checkpoint layouts (see
    load_checkpoint_in_model)."""
    rng = rng if rng is not None else jax.random.key(0)
    abstract = abstract_init(module, rng, *sample_args, **(sample_kwargs or {}))

    plan = None
    if mesh is not None:
        from .parallel.sharding import make_sharding_plan
        from .state import AcceleratorState

        state = AcceleratorState()
        plan = make_sharding_plan(abstract, mesh, parallelism_config=state.parallelism_config)

    placement = None
    if device_map == "auto":
        sizes = compute_module_sizes(abstract)
        budgets = get_max_memory(max_memory)
        total_hbm = sum(v for k, v in budgets.items() if isinstance(k, int))
        if sizes[""] > total_hbm:
            placement = infer_auto_placement(abstract, max_memory, offload_to_disk=offload_folder is not None)
    elif isinstance(device_map, dict):
        placement = device_map

    return load_checkpoint_in_model(
        abstract, checkpoint, sharding_plan=plan, dtype=dtype,
        offload_placement=placement, offload_folder=offload_folder, strict=strict,
        key_map=key_map, tensor_map=tensor_map,
    )


def serve_model(model, params, serving_plugin=None, generation_config=None, rng=None):
    """Stand up a continuous-batching :class:`~accelerate_tpu.serving.ServingEngine`
    over an already-dispatched param tree — the serving-side completion of
    the reference's load→dispatch→generate contract (reference
    big_modeling.py:513 + its benchmarks/big_model_inference), rebuilt at production scale: paged KV
    cache, per-step admission/eviction, chunked prefill (docs/serving.md).

    ``params`` is whatever :func:`load_checkpoint_and_dispatch` or
    :meth:`~accelerate_tpu.accelerator.Accelerator.init_params` produced —
    including int8 ``QuantizedTensor`` leaves, which decode through the
    Pallas in-tile-dequant matmuls unchanged."""
    from .serving import ServingEngine

    return ServingEngine(model, params, serving_plugin, generation_config, rng=rng)


def load_checkpoint_and_serve(
    module,
    checkpoint: Union[str, os.PathLike],
    *,
    serving_plugin=None,
    generation_config=None,
    sample_args: tuple = (),
    dtype=None,
    **dispatch_kwargs,
):
    """One call from checkpoint to serving engine:
    :func:`load_checkpoint_and_dispatch` then :func:`serve_model`."""
    params, _store = load_checkpoint_and_dispatch(
        module, checkpoint, sample_args=sample_args, dtype=dtype, **dispatch_kwargs
    )
    return serve_model(module, params, serving_plugin, generation_config)


def dispatch_model(params, placement: dict[str, Union[int, str]], offload_folder: Optional[str] = None):
    """Place an already-materialized pytree per a placement map
    (reference dispatch_model big_modeling.py:310)."""
    devices = jax.local_devices()
    store = OffloadStore(offload_folder, autoflush=False) if offload_folder else None
    normalized = _normalize_placement(placement)

    def _place(path, leaf):
        key = _path_key(path)
        target = _lookup_placement(key, normalized)
        if target is None:
            target = 0
        if target == "disk":
            if store is None:
                raise ValueError("disk placement requires offload_folder")
            store.save(key, leaf)
            return store.load(key)
        if target == "cpu":
            return np.asarray(leaf)
        return jax.device_put(leaf, devices[int(target)])

    try:
        placed = jax.tree_util.tree_map_with_path(_place, params)
    finally:
        if store is not None:
            store.flush()
    return placed, store


def cpu_offload(params, apply_fn: Optional[Callable] = None, execution_device=None):
    """Whole-tree host offload (reference big_modeling.py:cpu_offload:175):
    every leaf moves to host memory; with ``apply_fn`` given, also returns a
    wrapped apply that ships leaves to ``execution_device`` just-in-time per
    call and frees them after.  For layer-granular streaming at generation
    time, prefer :func:`accelerate_tpu.generation.generate_streamed`."""
    placed, _ = dispatch_model(params, {"": "cpu"})
    if apply_fn is None:
        return placed
    return placed, offloaded_apply(apply_fn, execution_device)


def disk_offload(params, offload_dir: Union[str, os.PathLike],
                 apply_fn: Optional[Callable] = None, execution_device=None):
    """Whole-tree disk offload (reference big_modeling.py:disk_offload:226):
    leaves are written to ``offload_dir`` and rebound as memory-maps; with
    ``apply_fn`` given, also returns the just-in-time wrapped apply."""
    placed, _store = dispatch_model(params, {"": "disk"}, offload_folder=str(offload_dir))
    if apply_fn is None:
        return placed
    return placed, offloaded_apply(apply_fn, execution_device)


# Reference-name alias (reference modeling.py:infer_auto_device_map:1278):
# same planner, TPU-native semantics — GSPMD sharding handles *splitting*,
# this handles *capacity overflow* into host/disk tiers.
infer_auto_device_map = infer_auto_placement


def offloaded_apply(apply_fn: Callable, device=None):
    """Wrap ``apply_fn(params, *args)`` so host/disk-resident leaves are
    shipped to device just-in-time and freed after — the AlignDevicesHook
    capability (reference hooks.py:227), functionally."""

    def wrapped(params, *args, **kwargs):
        def _fetch(x):
            if isinstance(x, np.memmap) or isinstance(x, np.ndarray):
                return jax.device_put(np.asarray(x), device)
            return x

        device_params = jax.tree_util.tree_map(_fetch, params)
        try:
            return apply_fn(device_params, *args, **kwargs)
        finally:
            del device_params

    return wrapped
