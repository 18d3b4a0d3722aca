"""Ring collective-matmul: hide TP/SP communication inside the matmuls it feeds.

The monolithic GSPMD collectives around a tensor-parallel matmul serialize ICI
communication against the MXU: an ``all_gather`` must finish before the matmul
that consumes it starts, and a ``psum_scatter`` cannot start before the matmul
that feeds it ends.  Decomposing both into **ring schedules over ppermute**
(Pope et al. 2022, *Efficiently Scaling Transformer Inference*; Wang et al.
2023, *Overlap Communication with Dependent Computation via Decomposition*)
lets each ring tick send one shard to the neighbor while the matmul for the
already-resident shard runs — the ``cur``/``nxt`` pair is the double-buffered
comm slot, and XLA's latency-hiding scheduler slides the collective-permute
``start``/``done`` pair under the independent per-chunk matmul.

Two schedules, matching the Megatron column/row split
(``parallel/sharding.py`` TRANSFORMER_TP_RULES):

- **all-gather -> matmul** (column-parallel entry): the input's sequence dim is
  sharded over the ring axis, the kernel's output dim over ``tp``.  Each tick
  multiplies the resident sequence shard into its output rows while the shard
  travels on to the neighbor; after ``p-1`` hops every rank has consumed every
  shard and holds the full-sequence, feature-sharded product.
- **matmul -> reduce-scatter** (row-parallel exit): the contraction dim is
  sharded, and the output's sequence dim scatters over the ring.  Each tick
  adds the local partial for the accumulator's target chunk and forwards the
  accumulator; after ``p-1`` hops each rank holds the fully-reduced chunk
  destined for it.

The optional **bidirectional ring** splits the schedule into two opposing
streams, halving ring depth to ``ceil((p-1)/2)`` hops (both ICI directions of
the ring link carry traffic concurrently).

Fallbacks: the XLA monolithic path is used whenever the ring axis is trivial
(size 1) or shapes do not divide the ring.  The knob rides
``FullyShardedDataParallelPlugin.collective_matmul``
/ env ``ACCELERATE_COLLECTIVE_MATMUL`` and is resolved at **trace time**: set it
before the step compiles.
"""

from __future__ import annotations

import functools
import os
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..parallel.collectives import (
    axis_index,
    axis_size,
    partial_manual_kwargs,
    ring_permute,
)

MODES = ("off", "ring", "bidir")

# trace-time mode override (None = fall through to the env default); set by
# the Accelerator from the plugin knob or by the `collective_matmul` context
# manager in tests
_MODE_OVERRIDE: list[Optional[str]] = [None]

_NORMALIZE = {
    "off": "off", "false": "off", "0": "off", "none": "off", "": "off",
    "on": "ring", "ring": "ring", "true": "ring", "1": "ring", "uni": "ring",
    "bidir": "bidir", "bidirectional": "bidir",
}


def normalize_mode(mode) -> str:
    """Canonical mode string ('off' | 'ring' | 'bidir') or ValueError."""
    norm = _NORMALIZE.get(str(mode).strip().lower())
    if norm is None:
        raise ValueError(
            f"collective_matmul mode {mode!r} not one of "
            f"{sorted(set(_NORMALIZE))} (canonical: {MODES})"
        )
    return norm


def set_collective_matmul(mode: Optional[str]) -> Optional[str]:
    """Set the ambient mode (``None`` clears back to the env default).
    Returns the previous override.  Trace-time: flip it before compiling."""
    prev = _MODE_OVERRIDE[0]
    _MODE_OVERRIDE[0] = None if mode is None else normalize_mode(mode)
    return prev


def collective_matmul_mode() -> str:
    """The effective mode: explicit override, else env
    ``ACCELERATE_COLLECTIVE_MATMUL``, else 'off'."""
    if _MODE_OVERRIDE[0] is not None:
        return _MODE_OVERRIDE[0]
    return normalize_mode(os.environ.get("ACCELERATE_COLLECTIVE_MATMUL", "off"))


@contextmanager
def collective_matmul(mode: str):
    """Scoped mode override (test/bench A/B harnesses)."""
    prev = set_collective_matmul(mode)
    try:
        yield
    finally:
        _MODE_OVERRIDE[0] = prev


def ring_supported(mesh: Optional[Mesh], axis_name: str) -> bool:
    """Whether the explicit ring path is usable on ``mesh`` over ``axis_name``.

    Trivial ring axes fall back to the monolithic path (nothing to hide).
    """
    if mesh is None or axis_name not in getattr(mesh, "shape", {}):
        return False
    return mesh.shape[axis_name] > 1


# ---------------------------------------------------------------------------
# shard_map bodies (local shards; must run inside a manual region over axis)
# ---------------------------------------------------------------------------


def _dot(x, w, preferred_element_type=None):
    """[..., Tc, K] @ [K, N] with fp32 accumulation when requested."""
    contract = (((x.ndim - 1,), (0,)), ((), ()))
    return lax.dot_general(x, w, contract, preferred_element_type=preferred_element_type)


def ring_all_gather_matmul(x, w, axis_name: str, *, bidirectional: bool = False,
                           preferred_element_type=None):
    """Latency-hiding ``all_gather(x, seq) @ w`` as a ring of partial matmuls.

    Local shapes: ``x`` [B, T/p, K] (sequence-sharded over the ring),
    ``w`` [K, N/p] (the local column shard); returns [B, T, N/p].  Each tick
    dispatches the ppermute of the resident shard *before* its matmul, so the
    hop rides under the MXU; ``bidirectional`` sends opposing half-rings.
    Numerically the per-chunk dots are the monolithic matmul's rows computed
    chunk-by-chunk — no reduction reordering.
    """
    p = axis_size(axis_name)
    i = axis_index(axis_name)
    b, tc, _ = x.shape
    n = w.shape[1]
    out_dtype = (
        preferred_element_type
        if preferred_element_type is not None
        else jnp.result_type(x.dtype, w.dtype)
    )
    out = jnp.zeros((b, p * tc, n), out_dtype)

    def put(out, shard, src):
        y = _dot(shard, w, preferred_element_type)
        return lax.dynamic_update_slice(out, y.astype(out_dtype), (0, src * tc, 0))

    if not bidirectional:
        cur = x
        for s in range(p):
            if s + 1 < p:
                nxt = ring_permute(cur, axis_name, shift=1)  # in flight under the dot
            out = put(out, cur, (i - s) % p)
            if s + 1 < p:
                cur = nxt
        return out

    out = put(out, x, i)
    fwd = bwd = x
    for s in range(1, (p - 1 + 1) // 2 + 1):  # ceil((p-1)/2) opposing hops
        fwd = ring_permute(fwd, axis_name, shift=1)
        bwd = ring_permute(bwd, axis_name, shift=-1)
        out = put(out, fwd, (i - s) % p)
        if (2 * s) % p != 0:  # even p: the final hop's two shards coincide
            out = put(out, bwd, (i + s) % p)
    return out


def ring_matmul_reduce_scatter(x, w, axis_name: str, *, bidirectional: bool = False,
                               preferred_element_type=None):
    """Latency-hiding ``psum_scatter(x @ w, seq)`` as a ring of accumulators.

    Local shapes: ``x`` [B, T, K/p] (contraction-sharded), ``w`` [K/p, N];
    returns [B, T/p, N] — the fully-reduced sequence chunk owned by this
    rank.  The accumulator created at rank ``d`` targets chunk ``(d-1) % p``
    and collects one local partial per hop; the next chunk's matmul is
    independent of the in-flight accumulator, so the hop hides under it.
    ``bidirectional`` splits contributions between two opposing accumulators
    (forward covers ``ceil((p-1)/2)+1`` ranks incl. the target, backward the
    rest), halving ring depth.
    """
    p = axis_size(axis_name)
    i = axis_index(axis_name)
    b, t, k = x.shape
    tc = t // p

    def chunk_mm(c):
        xs = lax.dynamic_slice(x, (0, c * tc, 0), (b, tc, k))
        return _dot(xs, w, preferred_element_type)

    if not bidirectional:
        acc = chunk_mm((i - 1) % p)
        for s in range(1, p):
            flight = ring_permute(acc, axis_name, shift=1)
            acc = flight + chunk_mm((i - s - 1) % p)  # dot overlaps the hop
        return acc

    hf = (p - 1 + 1) // 2  # ceil((p-1)/2) forward hops
    hb = (p - 1) // 2      # the rest travel backward
    facc = chunk_mm((i + hf) % p)
    for s in range(1, hf + 1):
        flight = ring_permute(facc, axis_name, shift=1)
        facc = flight + chunk_mm((i - s + hf) % p)
    if hb == 0:
        return facc
    bacc = chunk_mm((i - hb) % p)
    for s in range(1, hb):
        flight = ring_permute(bacc, axis_name, shift=-1)
        bacc = flight + chunk_mm((i + s - hb) % p)
    bacc = ring_permute(bacc, axis_name, shift=-1)  # final hop: target adds nothing
    return facc + bacc


def all_gather_matmul_monolithic(x, w, axis_name: str, *, preferred_element_type=None):
    """The XLA-shaped baseline body: one blocking gather, then the matmul."""
    full = lax.all_gather(x, axis_name, axis=1, tiled=True)
    return _dot(full, w, preferred_element_type)


def matmul_reduce_scatter_monolithic(x, w, axis_name: str, *, preferred_element_type=None):
    """Baseline body: the full partial matmul, then one blocking scatter."""
    y = _dot(x, w, preferred_element_type)
    return lax.psum_scatter(y, axis_name, scatter_dimension=1, tiled=True)


# ---------------------------------------------------------------------------
# global-array entry points (shard_map wrappers over a mesh)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_collective_dense(mesh: Mesh, axis_name: str = "tp", kind: str = "column",
                          mode: str = "ring", preferred_element_type=None):
    """Mesh-bound collective dense on GLOBAL arrays.

    ``kind='column'``: x [B, T, K] (seq shardable over ``axis_name``) @
    w [K, N] (N sharded over ``axis_name``) -> [B, T, N] feature-sharded.
    ``kind='row'``: x [B, T, K] (K sharded) @ w [K, N] (K sharded) ->
    [B, T, N] sequence-sharded over ``axis_name``.

    ``mode``: 'ring' | 'bidir' | 'monolithic' (the A/B baseline through the
    same specs).  Partial-manual over only the ring axis — dp/sp stay under
    GSPMD; run under a cached jit like ``make_ulysses_attention``.
    """
    if kind not in ("column", "row"):
        raise ValueError(f"kind must be 'column' or 'row', got {kind!r}")
    if mode == "monolithic":
        body_fn = (all_gather_matmul_monolithic if kind == "column"
                   else matmul_reduce_scatter_monolithic)
        body = functools.partial(body_fn, axis_name=axis_name,
                                 preferred_element_type=preferred_element_type)
    else:
        body_fn = ring_all_gather_matmul if kind == "column" else ring_matmul_reduce_scatter
        body = functools.partial(body_fn, axis_name=axis_name,
                                 bidirectional=(mode == "bidir"),
                                 preferred_element_type=preferred_element_type)
    if kind == "column":
        in_specs = (P(None, axis_name, None), P(None, axis_name))
        out_specs = P(None, None, axis_name)
    else:
        in_specs = (P(None, None, axis_name), P(axis_name, None))
        out_specs = P(None, axis_name, None)
    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  **partial_manual_kwargs({axis_name}))
    )


def _shapes_divide(x, w, kind: str, p: int) -> bool:
    if x.ndim != 3 or w.ndim != 2 or x.shape[-1] != w.shape[0]:
        return False
    t, k, n = x.shape[1], w.shape[0], w.shape[1]
    if t % p or t < p:
        return False  # both schedules chunk the sequence dim by the ring
    if kind == "column":
        return n % p == 0
    return k % p == 0


def dense_collective_matmul(x, w, kind: str, *, axis_name: str = "tp",
                            preferred_element_type=None):
    """The TP-linear-layer hook: ``x @ w`` through the ring schedule, or
    ``None`` when the caller should take its ordinary (XLA monolithic) path.

    Falls back (returns ``None``) when the mode is off, no mesh is ambient,
    the ring axis is trivial, or the
    sequence/feature/contraction dims don't divide the ring.  A fallback is
    always semantics-preserving: the global values are identical either way,
    only the collective schedule differs.
    """
    mode = collective_matmul_mode()
    if mode == "off" or kind not in ("column", "row"):
        return None
    from ..state import ambient_mesh

    mesh = ambient_mesh()
    if not ring_supported(mesh, axis_name):
        return None
    if not _shapes_divide(x, w, kind, mesh.shape[axis_name]):
        return None
    fn = make_collective_dense(mesh, axis_name, kind, mode, preferred_element_type)
    return fn(x, w)


def ulysses_sp_boundary(num_heads: int, num_kv_heads: int, seq_len: int,
                        axis_name: str = "sp") -> bool:
    """Whether the Ulysses attention boundary should run as collective
    matmuls over ``sp``: the q/k/v projections fuse with all_to_all #1 as
    ring all-gather->matmuls (the column ring over ``sp`` gathers the
    sequence while slicing heads), and o_proj fuses with all_to_all #2 as a
    ring matmul->reduce-scatter.  Requires head counts and the sequence to
    divide ``sp``, the ring to be supported, and a trivial ``tp`` axis (the
    kernel's feature dim can't be manual over ``sp`` and auto over ``tp`` at
    once — composed sp x tp keeps the all_to_all path).
    """
    if collective_matmul_mode() == "off":
        return False
    from ..state import ambient_mesh

    mesh = ambient_mesh()
    if not ring_supported(mesh, axis_name):
        return False
    if mesh.shape.get("tp", 1) > 1:
        return False
    sp = mesh.shape[axis_name]
    return num_heads % sp == 0 and num_kv_heads % sp == 0 and seq_len % sp == 0


# ---------------------------------------------------------------------------
# overlap accounting (predicted; the measured twin reads the profiler trace
# via utils/xplane.ici_overlap_report)
# ---------------------------------------------------------------------------


def tp_comm_accounting(
    m_tokens: int,
    k: int,
    n: int,
    ring_size: int,
    *,
    dtype_bytes: int = 2,
    bidirectional: bool = False,
    ici_gibs: float = 45.0,
    peak_flops: float = 197e12,
) -> dict:
    """Predicted hideable fraction of the ring's ICI traffic for an
    all-gather->matmul of [m_tokens, k] @ [k, n] over a ``ring_size`` ring.

    Per tick the resident shard's matmul (``2 * m/p * k * n/p`` FLOPs) runs
    while one hop (``m/p * k`` elements) is in flight; the hop is fully
    hidden when its wire time fits under the tick's MXU time.  Defaults are
    the v5e figures (one ICI link direction ~45 GiB/s, 197 Tbf16FLOP/s);
    bidirectional rings halve hop count, not per-hop time (the two streams
    ride opposite link directions concurrently).
    """
    p = max(1, int(ring_size))
    if p == 1:
        return {
            "ring_size": 1, "steps": 0, "bytes_per_hop": 0,
            "mm_s_per_step": 0.0, "comm_s_per_step": 0.0,
            "tp_overlap_frac": 0.0, "kind": "predicted",
        }
    steps = ((p - 1) + 1) // 2 if bidirectional else p - 1
    bytes_per_hop = (m_tokens // p) * k * dtype_bytes
    # per-tick output width is the ring-sharded column slice; ceil-div keeps
    # the model honest for non-dividing n (the real ring would fall back
    # there, but the prediction must not inflate the tick's FLOPs ~p-fold)
    mm_flops_per_step = 2 * (m_tokens // p) * k * (-(-n // p))
    mm_s = mm_flops_per_step / peak_flops
    comm_s = bytes_per_hop / (ici_gibs * 2**30)
    overlap = 1.0 if comm_s <= 0 else min(1.0, mm_s / comm_s)
    # twin registry: PREDICTED hideable fraction; measured side is
    # xplane.ici_overlap_report off a captured trace
    from ..telemetry import twin_registry

    twin_registry().record_predicted(
        "tp_comm.overlap_frac", overlap,
        source="ops/collective_matmul.tp_comm_accounting",
    )
    return {
        "ring_size": p,
        "steps": steps,
        "bytes_per_hop": int(bytes_per_hop),
        "mm_s_per_step": round(mm_s, 9),
        "comm_s_per_step": round(comm_s, 9),
        "tp_overlap_frac": round(overlap, 4),
        "kind": "predicted",
    }
