"""Double-buffered host↔device streaming: overlap offload transfers with compute.

The two serialized hot paths this module feeds (ZeRO-Offload, Ren et al.
2021, and ZeRO-Infinity both overlap the offload data path with compute via
double buffering — the discipline the reference delegates to DeepSpeed's
overlapping offload engine):

1. **Training** — the chunked host-compute optimizer update
   (``accelerator.prepare_train_step`` under ``cpu_offload`` +
   ``host_update_chunk_gib``) runs as a 3-stage software pipeline over the
   chunk sequence: while chunk *k* runs its host update, chunk *k+1*'s grads
   are in D2H flight and chunk *k−1*'s outputs are in write-back flight.
   Only the **update regions** ride the serialization token chain (the
   bounded-working-set invariant); the transfer stages are un-gated, so
   XLA's latency-hiding scheduler can slide them under the host compute.
   The stage helpers here (:func:`chunk_groups`, :func:`slice_congruent`,
   :func:`merge_congruent`, :func:`stage_put`) are what the accelerator's
   pipeline is built from, and the math per chunk is untouched — the
   pipelined update is bitwise-identical to the serial one (same chunk
   boundaries, same SR hash streams; pinned by ``tests/test_offload.py``).

2. **Inference** — ``generation.generate_streamed`` decodes a model whose
   weights live in (pinned) host memory or an ``OffloadStore``.  The serial
   path fetched each layer *inside* that layer's jitted program, so the PCIe
   copy and the matmuls took turns.  :class:`LayerPrefetcher` is the
   device-side double buffer: layer *k+1*'s H2D copy is **dispatched before
   the caller blocks on layer *k*** (JAX dispatch is asynchronous), so the
   next layer streams in under the current layer's matmuls.  HBM holds at
   most ``depth + 1`` layers.

The host-side staging analog for *byte producers* (dataloader batches) is
the in-tree C++ staging ring (``native/src/ring.cc``,
``data_loader._RingPrefetcher``); this module is the *array-tree* layer on
top of JAX async dispatch + donation for the device-facing paths.

Every pipeline reports **overlap accounting**: the host-driven decode path
measures directly (:class:`StreamStats` — bytes, stall time, hits); the
in-jit training path reports exact bytes + predicted overlap through
:func:`offload_transfer_accounting` (Python-side counters cannot run under
trace) with the measured counterpart read off the profiler
(``utils/xplane.streaming_overlap_report``).  Either way a negative result
is a documented measurement, not a silent regression.  Not measured on
the chip: no benchmark cell offloads (ROADMAP.md A11).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from ..resilience.faults import maybe_fail_transfer
from ..resilience.retry import DEFAULT_POLICY, RetryPolicy, with_retries


# ---------------------------------------------------------------------------
# Byte accounting
# ---------------------------------------------------------------------------


def tree_bytes(tree) -> int:
    """Total payload bytes of a pytree's array leaves (shape×itemsize for
    abstract leaves, ``nbytes`` for concrete ones)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


# Host bytes touched per param per offloaded step, by optimizer recipe
# (docs/performance.md "host-byte ladder": master r+w + moment r+w + grad
# read at bf16 wire width + bf16 param copy written for the fp32-master
# recipes; scales of the -sr8 codes ride in the fraction).  The denominator
# of the training pipeline's predicted-overlap model.
HOST_BYTES_PER_PARAM: dict[str, float] = {
    "adamw": 28.0,
    "lion": 16.0,
    "adamw-sr": 14.0,
    "lion-sr": 10.0,
    "adamw-sr8": 10.1,
    "lion-sr8": 8.1,
}


@dataclasses.dataclass
class StreamStats:
    """Overlap accounting for one streaming run.

    ``h2d_bytes``/``d2h_bytes`` are exact (summed from leaf ``nbytes``);
    ``fetch_wait_s`` is the time the compute thread actually blocked waiting
    for an in-flight transfer (the *unhidden* remainder of the transfer
    time); ``prefetch_hits`` counts fetches that were already in flight when
    requested.  Achieved overlap needs a serial-transfer baseline:
    ``overlap_report(serial_transfer_s)`` — with prefetch off, the same
    pipeline measures that baseline (``fetch_wait_s`` ≈ total transfer).

    ``ici_bytes``/``tp_overlap_frac`` carry the ICI plane's accounting when
    a ring collective-matmul is active (``ops/collective_matmul.py``):
    bytes permuted around the TP/SP ring per step and the predicted hidden
    fraction (``tp_comm_accounting``; measured twin:
    ``utils/xplane.ici_overlap_report``).  They join the report only when
    set — host↔device-only pipelines keep their original key set.
    """

    h2d_bytes: int = 0
    d2h_bytes: int = 0
    fetches: int = 0
    prefetch_hits: int = 0
    fetch_wait_s: float = 0.0
    wall_s: float = 0.0
    ici_bytes: int = 0
    tp_overlap_frac: Optional[float] = None
    # transient host-transfer failures absorbed by the bounded retry layer
    # (resilience/retry.py) — joins the report only when nonzero, like the
    # ICI fields above
    transfer_retries: int = 0

    def overlap_report(self, serial_transfer_s: Optional[float] = None) -> dict:
        rep = {
            "h2d_bytes": int(self.h2d_bytes),
            "d2h_bytes": int(self.d2h_bytes),
            "fetches": self.fetches,
            "prefetch_hits": self.prefetch_hits,
            "fetch_wait_s": round(self.fetch_wait_s, 4),
            "wall_s": round(self.wall_s, 4),
        }
        if self.wall_s > 0:
            rep["stall_frac"] = round(self.fetch_wait_s / self.wall_s, 4)
        if serial_transfer_s:
            rep["overlap_frac"] = round(
                max(0.0, 1.0 - self.fetch_wait_s / serial_transfer_s), 4
            )
        if self.ici_bytes:
            rep["ici_bytes"] = int(self.ici_bytes)
        if self.tp_overlap_frac is not None:
            rep["tp_overlap_frac"] = round(self.tp_overlap_frac, 4)
        if self.transfer_retries:
            rep["transfer_retries"] = self.transfer_retries
        return rep


def predicted_overlap(transfer_s: float, compute_s: float) -> float:
    """Fraction of serial transfer time a perfect double buffer hides: the
    transfer can only disappear under compute that exists to hide it."""
    if transfer_s <= 0:
        return 1.0
    return min(1.0, max(0.0, compute_s / transfer_s))


def offload_transfer_accounting(
    n_params: int,
    *,
    optimizer: str = "lion-sr",
    grad_bytes_per_param: int = 2,
    fetch_bytes_per_param: int = 2,
    offload_params: bool = True,
    host_rate_gibs: float = 1.61,
    pcie_rate_gibs: float = 8.0,
) -> dict:
    """Predicted per-step transfer/overlap model for the offloaded update.

    ``d2h_bytes`` = the grad wire (compute width under
    ``GradSyncKwargs(grad_dtype='bf16')``); ``h2d_bytes`` = the compute-width
    param fetch (zero when masters stay resident).  Host-update time comes
    from the recipe's host-byte ladder row at the **measured** serialized
    host-region rate (1.61 GiB/s on a quiet worker host, measured before
    PR 1 on another toolchain); transfer time from a nominal PCIe rate.  The
    predicted ``overlap_frac`` is the share of transfer hideable under the
    host update — ≈1.0 whenever the step is host-DRAM-bound, which is
    exactly the 7B regime (94.7 % host compute, docs/performance.md).
    """
    d2h = n_params * grad_bytes_per_param
    h2d = n_params * fetch_bytes_per_param if offload_params else 0
    host_b = n_params * HOST_BYTES_PER_PARAM.get(optimizer, 16.0)
    transfer_s = (d2h + h2d) / (pcie_rate_gibs * 2**30)
    host_s = host_b / (host_rate_gibs * 2**30)
    # twin registry (telemetry/twins.py): this is the PREDICTED side; the
    # measured side is xplane.streaming_overlap_report off a captured trace
    from ..telemetry import twin_registry

    twin_registry().record_predicted(
        "offload_transfer.overlap_frac",
        predicted_overlap(transfer_s, host_s),
        source="ops/streaming.offload_transfer_accounting",
    )
    return {
        "h2d_bytes": int(h2d),
        "d2h_bytes": int(d2h),
        "host_update_bytes": int(host_b),
        "transfer_s_pred": round(transfer_s, 3),
        "host_update_s_pred": round(host_s, 3),
        "overlap_frac": round(predicted_overlap(transfer_s, host_s), 4),
        "kind": "predicted",
    }


# ---------------------------------------------------------------------------
# Chunking: leaf groups of bounded footprint (the training pipeline's unit)
# ---------------------------------------------------------------------------


def chunk_groups(params, chunk_bytes: int, itemsize: int = 4) -> list[list[int]]:
    """Partition the params' leaf indices into contiguous groups whose
    ``itemsize``-wide footprint stays under ``chunk_bytes`` (one oversized
    leaf = its own group).  The chunk boundaries are a **numerics contract**:
    the -sr/-sr8 recipes salt their SR hash streams with group-relative leaf
    indices, so pipelined and serial schedules over the *same* groups are
    bitwise-identical."""
    groups: list[list[int]] = []
    cur: list[int] = []
    size = 0
    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        n = int(np.prod(leaf.shape)) * itemsize if hasattr(leaf, "shape") else itemsize
        if cur and size + n > chunk_bytes:
            groups.append(cur)
            cur, size = [], 0
        cur.append(i)
        size += n
    if cur:
        groups.append(cur)
    return groups


def is_congruent_to(treedef):
    """Predicate: does a subtree have exactly the params' tree structure?
    (per-leaf optimizer moments are params-congruent; adam's count scalar is
    not and passes through chunking whole)."""

    def check(node):
        try:
            return jax.tree_util.tree_structure(node) == treedef
        except Exception:  # pragma: no cover - exotic nodes
            return False

    return check


def slice_congruent(tree, treedef, idxs: list[int]):
    """Replace every params-congruent subtree of ``tree`` (per-leaf optimizer
    moments, or the params tree itself) by the tuple of its selected leaves;
    scalars and other leaves pass through.  The result is a valid optax state
    for an update over the matching sliced params tuple."""
    check = is_congruent_to(treedef)
    return jax.tree_util.tree_map(
        lambda sub: (
            tuple(jax.tree_util.tree_leaves(sub)[i] for i in idxs)
            if check(sub)
            else sub  # shared scalar (e.g. adam count) — passes whole
        ),
        tree,
        is_leaf=check,
    )


def merge_congruent(template, group_outs: list, treedef, groups: list[list[int]]):
    """Inverse of :func:`slice_congruent` across all groups: rebuild each
    congruent subtree from the per-group output tuples; non-congruent leaves
    (shared scalars like adam's count — every group advances it identically)
    come from group 0."""

    def merge(orig_sub, *outs):
        if is_congruent_to(treedef)(orig_sub):
            leaves: list = [None] * treedef.num_leaves
            for idxs, out in zip(groups, outs):
                out_leaves = (
                    list(out) if isinstance(out, tuple) else jax.tree_util.tree_leaves(out)
                )
                for j, i in enumerate(idxs):
                    leaves[i] = out_leaves[j]
            return jax.tree_util.tree_unflatten(treedef, leaves)
        return outs[0]

    return jax.tree_util.tree_map(
        merge, template, *group_outs, is_leaf=is_congruent_to(treedef)
    )


def stage_put(tree, shardings):
    """One transfer stage: ``device_put`` every array leaf of ``tree`` to the
    congruent ``shardings`` tree (leaves with ``None`` sharding pass
    through).  Dispatch is asynchronous — issuing a stage un-gated by the
    update token chain is what lets it fly under a neighboring chunk's host
    region.  Runs under trace inside the train step, so it carries no
    Python-side byte accounting; the training path's bytes come from
    :func:`offload_transfer_accounting` (exact leaf arithmetic), the
    host-driven decode path's from :class:`LayerPrefetcher`'s stats."""
    return jax.tree_util.tree_map(
        # graft-lint: disable=GL103 -- these transfers ARE the streaming pipeline's overlapped stages: issued un-gated by the update token chain so XLA slides them under neighboring chunks' host compute
        lambda x, s: jax.device_put(x, s) if s is not None else x, tree, shardings
    )


# ---------------------------------------------------------------------------
# Device-side double buffer for layer-streamed decode
# ---------------------------------------------------------------------------


class LayerPrefetcher:
    """Host-driven double buffer over per-layer weight trees.

    ``fetch(i)`` must *dispatch* the H2D upload of layer ``i``'s tree and
    return immediately (``jax.device_put`` semantics).  ``get(i)`` first
    issues the prefetch of the next ``depth`` layers, then resolves layer
    ``i`` — so while the caller's matmuls for layer ``i`` run, layer
    ``i+1``'s weights are crossing PCIe.  With ``wrap=True`` the prefetch
    wraps past the last layer (layer 0's weights for the *next* token stream
    in under the LM head + sampling).

    HBM cost: at most ``depth + 1`` layers resident.  ``enabled=False``
    degrades to blocking per-layer fetches through the same interface (the
    serial baseline the overlap accounting is measured against).

    ``depth=0`` disables the *sequential* lookahead while keeping the
    double-buffer slots: the caller drives prefetch explicitly through
    :meth:`prefetch` — the adapter hot-swap path
    (``serving/adapters.py``), where "the next index" is the scheduler's
    waiting queue, not ``i + 1``.
    """

    def __init__(self, fetch: Callable[[int], Any], n_layers: int, *,
                 depth: int = 1, wrap: bool = False, enabled: bool = True,
                 stats: Optional[StreamStats] = None,
                 retry_policy: Optional[RetryPolicy] = DEFAULT_POLICY):
        if n_layers < 1:
            raise ValueError(f"n_layers must be >= 1, got {n_layers}")
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        self.fetch = fetch
        self.n_layers = n_layers
        self.depth = depth
        self.wrap = wrap
        self.enabled = enabled
        self.stats = stats
        # bounded retry/backoff for the host-driven H2D staging (a transient
        # PCIe/pinned-alloc failure must not kill a decode mid-sweep); None
        # restores fail-on-first-error.  The injected-fault hook fires inside
        # each attempt, so the CPU suite exercises the real backoff path.
        self.retry_policy = retry_policy
        self._slots: dict[int, Any] = {}

    def _on_retry(self, site, attempt, exc):
        if self.stats is not None:
            self.stats.transfer_retries += 1

    def _issue(self, i: int):
        def attempt():
            maybe_fail_transfer("transfer")
            return self.fetch(i)

        if self.retry_policy is not None:
            tree = with_retries(
                attempt, policy=self.retry_policy,
                site=f"layer-prefetch[{i}]", on_retry=self._on_retry,
            )
        else:
            tree = attempt()
        if self.stats is not None:
            self.stats.h2d_bytes += tree_bytes(tree)
            self.stats.fetches += 1
        return tree

    def get(self, i: int):
        """The device tree for layer ``i``; issues the next prefetches first."""
        if not (0 <= i < self.n_layers):
            raise IndexError(f"layer {i} out of range [0, {self.n_layers})")
        if not self.enabled:
            tree = self._issue(i)
            if self.stats is not None:
                t0 = time.perf_counter()
                jax.block_until_ready(tree)
                self.stats.fetch_wait_s += time.perf_counter() - t0
            return tree
        tree = self._slots.pop(i, None)
        if tree is None:
            # cold miss (first layer of a fresh run): issue the layer needed
            # RIGHT NOW before any lookahead — transfers execute in dispatch
            # order, and queueing depth layers ahead of it would add their
            # upload time to time-to-first-token
            tree = self._issue(i)
        elif self.stats is not None:
            self.stats.prefetch_hits += 1
        # dispatch the NEXT uploads before blocking on this one: the copies
        # ride under the caller's compute on layer i
        for d in range(1, self.depth + 1):
            j = i + d
            if self.wrap:
                j %= self.n_layers
            if 0 <= j < self.n_layers and j != i and j not in self._slots:
                self._slots[j] = self._issue(j)
        if self.stats is not None:
            t0 = time.perf_counter()
            jax.block_until_ready(tree)  # measure the unhidden remainder
            self.stats.fetch_wait_s += time.perf_counter() - t0
        return tree

    def prefetch(self, i: int) -> bool:
        """Dispatch layer ``i``'s upload NOW without blocking (explicit
        lookahead for callers whose next index is data-dependent — the
        adapter hot-swap path).  Returns True when a transfer was issued
        (False: already in flight, or prefetch disabled)."""
        if not (0 <= i < self.n_layers):
            raise IndexError(f"layer {i} out of range [0, {self.n_layers})")
        if not self.enabled or i in self._slots:
            return False
        self._slots[i] = self._issue(i)
        return True

    def invalidate(self, i: int) -> None:
        """Discard layer ``i``'s staged upload if one is in flight — the
        source tree changed (adapter re-publish), so the staged copy must
        never be served."""
        self._slots.pop(i, None)

    def drop(self):
        """Release any in-flight slots (frees their HBM)."""
        self._slots.clear()
