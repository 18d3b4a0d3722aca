"""Multi-tenant adapter management: device pool, hot-swap streaming, LRU.

The serving side of ROADMAP item 2 (batched LoRA): thousands of tenants'
adapters cannot all live in HBM, so the :class:`AdapterStore` keeps a
**fixed-size device pool** of stacked A/B factors (``ops/lora.py``
geometry — slot 0 is the reserved null adapter) and hot-swaps cold
adapters in from host/:class:`~accelerate_tpu.big_modeling.OffloadStore`
memmaps on demand:

- **cold tier**: each published adapter lives as '/'-keyed arrays in an
  ``OffloadStore`` (disk memmaps — the PR 2 streaming tier) or a host
  dict; publishing costs no HBM.
- **hot-swap streaming**: uploads go through the existing
  :class:`~accelerate_tpu.ops.streaming.LayerPrefetcher` double buffer
  (``depth=0`` + explicit :meth:`prefetch`): the scheduler prefetches the
  waiting queue's adapters so the H2D copy flies under the current decode
  step, and the bounded-retry/fault hooks ride along like every other
  host transfer.
- **pool discipline**: a free-list + one donated jitted scatter
  (``pool.at[slot].set``) mirrors ``ops/paged_cache.py`` — the pool
  buffers alias in place, so the decode step stays donation-clean and
  ``ServingEngine.audit_decode_step()`` stays green.
- **pinning**: every in-flight request holding adapter *t* keeps a
  refcount on its slot; LRU eviction only considers refcount-0 slots, so
  evicting a *request* can never evict a **shared hot adapter** another
  tenant's requests are decoding with.

The fine-tuning side (:class:`LoraTrainer`) batches mixed-tenant
gradients through the same gathered einsum and keeps **per-adapter
optimizer state on host** under the ``make_optimizer`` recipes — with the
int8-SR ladder (``lion-sr8``/``adamw-sr8``) an adapter's state is a few
hundred KiB, so host DRAM holds out to huge tenant counts
(:func:`~accelerate_tpu.ops.lora.adapter_state_accounting`).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.lora import (
    DEFAULT_LORA_TARGETS,
    _nest,
    adapter_param_count,
    init_adapter_params,
    init_lora_pool,
    lora_spec,
)
from ..ops.streaming import LayerPrefetcher, StreamStats, predicted_overlap
from ..resilience.faults import maybe_fail_transfer
from ..resilience.retry import DEFAULT_POLICY, with_retries
from ..utils.dataclasses import LoraPlugin


def _flatten(tree, prefix=()) -> dict[str, Any]:
    """Inverse of :func:`~accelerate_tpu.ops.lora._nest`: '/'-keyed leaves
    (the OffloadStore / npz key schema)."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(_flatten(v, prefix + (k,)))
        else:
            out["/".join(prefix + (k,))] = v
    return out


class AdapterPoolFullError(RuntimeError):
    """Every pool slot is pinned by an in-flight request — the scheduler
    must wait for a retire/evict before this tenant's adapter can swap in
    (admission checks :meth:`AdapterStore.can_pin` first, so seeing this
    raised means a scheduling bug, not an operational condition)."""


class AdapterStore:
    """Fixed-size device adapter pool with LRU hot-swap over a cold tier.

    >>> store = AdapterStore(params, LoraPlugin(pool_slots=4))
    >>> store.publish(7, adapter_tree)        # cold tier, no HBM
    >>> slot = store.pin(7)                   # resident + refcounted
    >>> ...                                   # decode with ids[row] = slot
    >>> store.unpin(7)                        # eligible for LRU eviction

    ``pool`` is the ``lora`` variable-collection tree the model consumes
    (``model.apply({**params, "lora": store.pool}, ..., adapter_ids=ids)``);
    inserts rebind it through one donated jitted scatter, so the engine
    always reads the current binding.
    """

    def __init__(self, params, plugin: Optional[LoraPlugin] = None, *,
                 dtype=jnp.bfloat16, offload_dir: Optional[str] = None):
        self.plugin = plugin or LoraPlugin()
        p = self.plugin
        self.spec = lora_spec(params, p.targets or DEFAULT_LORA_TARGETS)
        self.dtype = dtype
        self.pool = init_lora_pool(self.spec, p.pool_slots, p.rank, dtype)
        self._insert = jax.jit(
            lambda pool, staged, slot: jax.tree_util.tree_map(
                lambda pl_, st: pl_.at[slot].set(st.astype(pl_.dtype)), pool, staged
            ),
            donate_argnums=(0,),
        )
        # cold tier: OffloadStore memmaps when a directory is given (the
        # production tier), else host arrays (tests / small tenant counts)
        self._offload = None
        if offload_dir is not None:
            from ..big_modeling import OffloadStore

            self._offload = OffloadStore(offload_dir, autoflush=False)
        self._host: dict[int, dict] = {}
        self._tids: list[int] = []           # registration order (prefetch index)
        self._idx_of: dict[int, int] = {}
        self.slot_of: dict[int, int] = {}    # resident tenant -> pool slot
        self.tid_of: dict[int, int] = {}     # pool slot -> tenant
        self.free_slots: list[int] = list(range(1, p.pool_slots + 1))
        self.refcount: dict[int, int] = {}
        self._last_use: dict[int, int] = {}
        self._use_seq = 0
        self.stats = StreamStats()
        self.hits = 0
        self.swaps = 0
        self._prefetcher: Optional[LayerPrefetcher] = None

    # -- cold tier ----------------------------------------------------------

    def publish(self, tid: int, tree: dict) -> None:
        """Register tenant ``tid``'s adapter tree (``{path: {"a", "b"}}`` in
        the store's :attr:`spec` schema) in the cold tier."""
        if tid < 1:
            raise ValueError(f"adapter id must be >= 1 (0 is the null adapter), got {tid}")
        flat = _flatten(tree)
        want = {f"{path}/{f}" for path in self.spec for f in ("a", "b")}
        if set(flat) != want:
            raise ValueError(
                f"adapter {tid} tree does not match the store spec: "
                f"missing {sorted(want - set(flat))[:3]}, "
                f"extra {sorted(set(flat) - want)[:3]}"
            )
        if self._offload is not None:
            for key, leaf in flat.items():
                self._offload.save(f"adapter_{tid}/{key}", np.asarray(leaf))
            self._offload.flush()
        else:
            self._host[tid] = {k: np.asarray(v) for k, v in flat.items()}
        if tid not in self._idx_of:
            self._idx_of[tid] = len(self._tids)
            self._tids.append(tid)
            self._prefetcher = None  # registry grew: rebuild lazily
        else:
            # RE-publish of a known tenant (continuous fine-tuning →
            # redeploy): a staged prefetch of the old weights must never be
            # served, and a resident slot refreshes in place immediately —
            # in-flight requests pin the SLOT, and the tenant's new weights
            # are what that slot must now hold
            if self._prefetcher is not None:
                self._prefetcher.invalidate(self._idx_of[tid])
            if tid in self.slot_of:
                staged = self._ensure_prefetcher().get(self._idx_of[tid])
                self.pool = self._insert(
                    self.pool, staged, jnp.asarray(self.slot_of[tid], jnp.int32)
                )

    def publish_random(self, tid: int, rng, *, init_b: str = "normal") -> dict:
        """Convenience for benches/tests: publish a seeded random adapter."""
        tree = init_adapter_params(
            rng, self.spec, self.plugin.rank, alpha=self.plugin.alpha,
            dtype=self.dtype, init_b=init_b,
        )
        self.publish(tid, tree)
        return tree

    def known(self, tid: int) -> bool:
        return tid in self._idx_of

    def _host_tree(self, tid: int) -> dict[str, np.ndarray]:
        if self._offload is not None:
            # cold-tier memmap reads fail transiently in exactly the ways
            # checkpoint I/O does (NFS hiccup, stale handle across a
            # preemption) — the bounded retry/backoff budget applies, and
            # the injected-fault hook (site "adapter_memmap") fires inside
            # each attempt so the CPU suite exercises the real backoff path
            def attempt():
                maybe_fail_transfer("adapter_memmap")
                return {
                    f"{path}/{f}": self._offload.load(f"adapter_{tid}/{path}/{f}")
                    for path in self.spec for f in ("a", "b")
                }

            return with_retries(
                attempt, policy=DEFAULT_POLICY,
                site=f"adapter_memmap[{tid}]", on_retry=self._on_retry,
            )
        return self._host[tid]

    def _on_retry(self, site, attempt, exc) -> None:
        self.stats.transfer_retries += 1

    # -- hot-swap streaming -------------------------------------------------

    def _ensure_prefetcher(self) -> LayerPrefetcher:
        if self._prefetcher is None or self._prefetcher.n_layers != len(self._tids):
            def fetch(idx):
                # the serving-specific fault site: an adapter-swap transfer
                # failing mid-prefetch raises HERE, inside the prefetcher's
                # bounded-retry wrapper — a transient blip costs one backoff
                # (counted into StreamStats.transfer_retries, surfaced in
                # the replay report), not the whole replay
                maybe_fail_transfer("adapter_transfer")
                return jax.device_put(_nest(self._host_tree(self._tids[idx])))

            self._prefetcher = LayerPrefetcher(
                fetch, max(1, len(self._tids)), depth=0, stats=self.stats,
            )
        return self._prefetcher

    def warmup_insert(self) -> None:
        """Compile the pool-insert program before traffic: one zeros
        insert into the null slot (zeros over zeros — the slot-0 invariant
        holds).  Without this the FIRST hot-swap would compile mid-traffic
        and trip the engine's ``strict_compiles`` recompile guard — the
        exact class of stall the warmup contract exists to remove."""
        staged = jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape[1:], l.dtype),
                                        self.pool)
        self.pool = self._insert(self.pool, staged, jnp.asarray(0, jnp.int32))

    def prefetch(self, tid: int) -> bool:
        """Dispatch tenant ``tid``'s H2D staging now (non-blocking) so a
        later :meth:`pin` finds the transfer already in flight — the
        scheduler calls this for the waiting queue while the current step's
        matmuls run.  No pool slot is taken yet."""
        if tid == 0 or tid in self.slot_of or not self.known(tid):
            return False
        return self._ensure_prefetcher().prefetch(self._idx_of[tid])

    # -- pinning / LRU ------------------------------------------------------

    def resident(self, tid: int) -> bool:
        return tid == 0 or tid in self.slot_of

    def _evictable(self) -> Optional[int]:
        """The LRU resident tenant no in-flight request holds (deterministic:
        oldest last-use, tid breaks ties)."""
        candidates = [
            t for t in self.slot_of if self.refcount.get(t, 0) == 0
        ]
        if not candidates:
            return None
        return min(candidates, key=lambda t: (self._last_use.get(t, 0), t))

    def can_pin(self, tid: int) -> bool:
        """Could :meth:`pin` succeed right now (resident, or a free /
        LRU-evictable slot exists)?  The admission gate — checked before a
        request is scheduled so admission never half-commits."""
        if tid == 0 or tid in self.slot_of:
            return True
        return self.known(tid) and bool(self.free_slots or self._evictable() is not None)

    def pin(self, tid: int) -> tuple[int, bool]:
        """Make tenant ``tid``'s adapter resident and hold it (refcount).

        Returns ``(pool_slot, swapped)`` — ``swapped`` is True when a cold
        adapter was streamed in (the measured pool-miss).  Id 0 pins
        nothing and always maps to the null slot."""
        if tid == 0:
            return 0, False
        self._use_seq += 1
        self._last_use[tid] = self._use_seq
        if tid in self.slot_of:
            self.refcount[tid] = self.refcount.get(tid, 0) + 1
            self.hits += 1
            return self.slot_of[tid], False
        if not self.known(tid):
            raise KeyError(f"adapter {tid} was never published")
        if self.free_slots:
            slot = self.free_slots.pop(0)
        else:
            victim = self._evictable()
            if victim is None:
                raise AdapterPoolFullError(
                    f"adapter {tid}: all {self.plugin.pool_slots} pool slots "
                    "are pinned by in-flight requests"
                )
            slot = self.slot_of.pop(victim)
            del self.tid_of[slot]
        staged = self._ensure_prefetcher().get(self._idx_of[tid])
        self.pool = self._insert(self.pool, staged, jnp.asarray(slot, jnp.int32))
        self.slot_of[tid] = slot
        self.tid_of[slot] = tid
        self.refcount[tid] = self.refcount.get(tid, 0) + 1
        self.swaps += 1
        return slot, True

    def unpin(self, tid: int) -> None:
        """Release one in-flight hold on ``tid`` (retire/evict of a request
        — the adapter STAYS hot until LRU pressure claims its slot)."""
        if tid == 0:
            return
        n = self.refcount.get(tid, 0)
        if n <= 1:
            self.refcount.pop(tid, None)
        else:
            self.refcount[tid] = n - 1

    def slot(self, tid: int) -> int:
        return 0 if tid == 0 else self.slot_of[tid]

    # -- accounting ---------------------------------------------------------

    @property
    def swap_bytes(self) -> int:
        """H2D bytes streamed by hot-swaps (the prefetcher's exact leaf
        accounting)."""
        return int(self.stats.h2d_bytes)

    def hit_rate(self) -> float:
        total = self.hits + self.swaps
        return round(self.hits / total, 4) if total else 0.0

    def pool_report(self) -> dict:
        return {
            "pool_slots": self.plugin.pool_slots,
            "resident": len(self.slot_of),
            "hits": self.hits,
            "swaps": self.swaps,
            "hit_rate": self.hit_rate(),
            "swap_bytes": self.swap_bytes,
        }


def predicted_adapter_hit_rate(adapter_ids, pool_slots: int) -> float:
    """CheckFreq-style *predicted* twin of the measured pool hit rate: a
    model-free LRU replay over the trace's adapter ids in arrival order
    (one pin per request, no refcount pinning — the prediction error vs
    the measured twin is exactly the in-flight-pin and eviction-reorder
    traffic the arrival sequence cannot know about)."""
    resident: dict[int, int] = {}
    seq = hits = misses = 0
    for tid in adapter_ids:
        tid = int(tid)
        if tid == 0:
            continue
        seq += 1
        if tid in resident:
            hits += 1
        else:
            misses += 1
            if len(resident) >= pool_slots:
                victim = min(resident, key=lambda t: (resident[t], t))
                del resident[victim]
        resident[tid] = seq
    total = hits + misses
    return round(hits / total, 4) if total else 0.0


def adapter_pool_accounting(spec: dict, *, rank: int, pool_slots: int,
                            dtype_bytes: int = 2, pcie_rate_gibs: float = 8.0,
                            decode_step_s: Optional[float] = None) -> dict:
    """Predicted device-pool ladder + swap-bandwidth envelope (the
    multi-tenant row of docs/serving.md's sizing tables; measured twins:
    :meth:`AdapterStore.pool_report` + ``bench --serve --adapters``).

    ``bytes_per_slot`` is one adapter's stacked A+B footprint; the swap
    envelope uses the PR 2 transfer accounting — a swap is hidden when its
    PCIe time fits under the decode step it rides beneath
    (:func:`~accelerate_tpu.ops.streaming.predicted_overlap`)."""
    n_params = adapter_param_count(spec, rank)
    per_slot = n_params * dtype_bytes
    total = per_slot * (pool_slots + 1)  # + the null slot
    swap_s = per_slot / (pcie_rate_gibs * 2**30)
    gib = lambda b: round(b / 2**30, 6)
    out = {
        "rank": rank,
        "pool_slots": pool_slots,
        "params_per_adapter": n_params,
        "bytes_per_slot": per_slot,
        "pool_bytes": total,
        "pool_gib": gib(total),
        "hbm_frac": {
            "v5e_16GiB": round(total / (16 * 2**30), 8),
            "v5p_95GiB": round(total / (95 * 2**30), 8),
            "v6e_32GiB": round(total / (32 * 2**30), 8),
        },
        "swap_s_pred": round(swap_s, 9),
        "kind": "predicted",
    }
    if decode_step_s is not None:
        out["swap_overlap_frac_pred"] = round(
            predicted_overlap(swap_s, decode_step_s), 4
        )
    return out


# ---------------------------------------------------------------------------
# Fine-tuning: batched multi-adapter step, per-adapter host state
# ---------------------------------------------------------------------------


class LoraTrainer:
    """Fine-tune many tenants' adapters against one frozen base model.

    Each step takes a mixed-tenant batch (per-row ``adapter_ids`` are
    TENANT ids) and runs ONE batched forward/backward through the gathered
    einsum — gradients land in the stacked factors, get sliced per tenant,
    and update each tenant's optimizer state under a
    :func:`~accelerate_tpu.optimizer.make_optimizer` recipe.  State lives
    **host-side** per adapter (``np`` trees between steps): with the
    int8-SR recipes the whole per-tenant footprint is
    ``adapter_state_accounting``-tiny, so tenant count scales with host
    DRAM, not HBM.

    The training stack is fixed at ``plugin.pool_slots + 1`` rows (like
    the serving pool), so the jitted step never re-specializes on how many
    tenants a batch mixes — the GL305 discipline applied to training.
    """

    def __init__(self, model, base_params, plugin: Optional[LoraPlugin] = None,
                 *, learning_rate: Optional[float] = None, seed: int = 0):
        from ..optimizer import make_optimizer

        self.model = model
        self.base_params = base_params
        self.plugin = plugin or LoraPlugin()
        p = self.plugin
        self.spec = lora_spec(base_params, p.targets or DEFAULT_LORA_TARGETS)
        dtype = getattr(model.config, "dtype", jnp.bfloat16)
        self.dtype = dtype
        self.tx = make_optimizer(p.optimizer, learning_rate, seed=seed)
        self.adapters: dict[int, dict] = {}      # tid -> host adapter tree
        self.opt_states: dict[int, Any] = {}     # tid -> host optax state
        # one UNstacked zero adapter — the null row every training stack
        # leads with, and the zeros template batch padding copies
        self._null = _nest({
            path: {"a": jnp.zeros((d_in, p.rank), dtype),
                   "b": jnp.zeros((p.rank, d_out), dtype)}
            for path, (d_in, d_out) in self.spec.items()
        })
        self._grad_step = jax.jit(jax.value_and_grad(self._loss, argnums=1))
        self._update = jax.jit(self._apply_update)

    def _loss(self, base_params, pool, batch, slot_ids):
        from ..models.llama import causal_lm_loss

        logits = self.model.apply(
            {**base_params, "lora": pool}, batch["input_ids"],
            adapter_ids=slot_ids,
        )
        return causal_lm_loss(logits, batch["labels"])

    def _apply_update(self, grads, opt_state, params):
        import optax

        updates, new_state = self.tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state

    def add_adapter(self, tid: int, rng=None) -> dict:
        if tid < 1:
            raise ValueError(f"adapter id must be >= 1, got {tid}")
        rng = rng if rng is not None else jax.random.PRNGKey(tid)
        tree = init_adapter_params(
            rng, self.spec, self.plugin.rank, alpha=self.plugin.alpha,
            dtype=self.dtype,
        )
        self.adapters[tid] = tree
        self.opt_states[tid] = self.tx.init(tree)
        return tree

    def _stack(self, tids: list[int]) -> dict:
        """Stacked training pool: slot 0 null, slot i+1 = ``tids[i]``,
        padded with zero rows to the fixed ``pool_slots + 1`` width — the
        jitted step never re-specializes on how many tenants a batch mixes."""
        p = self.plugin
        if len(tids) > p.pool_slots:
            raise ValueError(
                f"batch mixes {len(tids)} tenants > pool_slots={p.pool_slots}"
            )

        def build(null_leaf, *adapter_leaves):
            pad = [null_leaf] * (p.pool_slots - len(adapter_leaves))
            rows = [jnp.asarray(l, null_leaf.dtype) for l in adapter_leaves]
            return jnp.stack([null_leaf, *rows, *pad])

        return jax.tree_util.tree_map(
            build, self._null, *[self.adapters[t] for t in tids]
        )

    def step(self, batch, adapter_ids) -> float:
        """One batched multi-adapter step.  ``adapter_ids``: per-row TENANT
        ids (0 = base rows contribute loss but no adapter gradient).
        Returns the mixed-batch loss."""
        ids = [int(t) for t in np.asarray(adapter_ids)]
        tids = sorted({t for t in ids if t != 0})
        for t in tids:
            if t not in self.adapters:
                raise KeyError(f"adapter {t} not added")
        slot_of = {t: i + 1 for i, t in enumerate(tids)}
        slot_ids = jnp.asarray([slot_of.get(t, 0) for t in ids], jnp.int32)
        pool = self._stack(tids)
        loss, grads = self._grad_step(self.base_params, pool, batch, slot_ids)
        for t in tids:
            g = jax.tree_util.tree_map(lambda x, t=t: x[slot_of[t]].astype(jnp.float32),
                                       grads)
            new_params, new_state = self._update(
                g, self.opt_states[t], self.adapters[t]
            )
            # host residency between steps: per-adapter state parks in DRAM
            self.adapters[t] = jax.tree_util.tree_map(
                lambda x: np.asarray(x), new_params
            )
            self.opt_states[t] = jax.device_get(new_state)
        return float(loss)

    def sequential_loss(self, batch, adapter_ids) -> float:
        """Reference schedule for the parity pin: loss computed per tenant
        group (each group's rows through a single-adapter pass), combined
        by token weight — must match :meth:`step`'s batched loss."""
        ids = np.asarray(adapter_ids)
        input_ids = np.asarray(batch["input_ids"])
        labels = np.asarray(batch["labels"])
        total, weight = 0.0, 0
        for t in sorted(set(int(x) for x in ids)):
            rows = np.nonzero(ids == t)[0]
            sub = {"input_ids": jnp.asarray(input_ids[rows]),
                   "labels": jnp.asarray(labels[rows])}
            tids = [t] if t != 0 else []
            slot_ids = jnp.full((len(rows),), 1 if t != 0 else 0, jnp.int32)
            loss = float(self._loss(self.base_params, self._stack(tids), sub, slot_ids))
            n_tok = int((labels[rows][:, 1:] != -100).sum())
            total += loss * n_tok
            weight += n_tok
        return total / max(weight, 1)

    # -- verified checkpointing --------------------------------------------

    def save(self, ckpt_dir: str) -> str:
        """Atomic, verified save of every tenant's (weights, optimizer
        state): stage under ``<dir>.tmp``, write the size+crc32 manifest
        LAST, publish with ONE ``os.replace`` — the resilience layer's
        checkpoint discipline (``checkpointing._finalize_checkpoint``)
        applied to adapters.  Re-saving over an existing directory (or a
        crashed save's stale ``.tmp``) republishes cleanly: both are
        cleared first, so a deleted tenant's shard can never resurrect
        into a fresh manifest."""
        from ..checkpointing import _finalize_checkpoint

        final = str(ckpt_dir)
        tmp = final + ".tmp"
        if os.path.isdir(tmp):
            import shutil

            shutil.rmtree(tmp)  # a crashed prior save must not leak shards
        os.makedirs(tmp)
        for tid in sorted(self.adapters):
            np.savez(
                os.path.join(tmp, f"adapter_{tid}.npz"),
                **{f"w/{k}": self._npz_safe(v)
                   for k, v in _flatten(self.adapters[tid]).items()},
                **{f"s/{i}": self._npz_safe(leaf)
                   for i, leaf in enumerate(
                       jax.tree_util.tree_leaves(self.opt_states[tid]))},
            )
        _finalize_checkpoint(tmp, final)
        return final

    @staticmethod
    def _npz_safe(leaf):
        """npz-representable view of a leaf: typed PRNG keys become their
        key_data, and non-native float dtypes (bf16 & co — ``np.savez``
        degrades them to raw void bytes) upcast to fp32, which is EXACT for
        every <=16-bit float; the loader casts back to the template dtype,
        reconstructing the original bits."""
        if isinstance(leaf, jax.Array) and jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(leaf))
        arr = np.asarray(leaf)
        if arr.dtype.kind == "V":  # ml_dtypes (bf16/fp8): not npz-native
            return arr.astype(np.float32)
        return arr

    def load(self, ckpt_dir: str) -> list[int]:
        """Verified restore (``verify_checkpoint`` gate first — a torn or
        bit-flipped save raises ``CheckpointCorruptError`` instead of
        silently resuming wrong tenants).  Returns the restored tids."""
        from ..checkpointing import CheckpointCorruptError, verify_checkpoint

        ok, problems = verify_checkpoint(ckpt_dir)
        if not ok:
            raise CheckpointCorruptError(
                f"adapter checkpoint {ckpt_dir} failed verification: {problems}"
            )
        restored = []
        for name in sorted(os.listdir(ckpt_dir)):
            if not (name.startswith("adapter_") and name.endswith(".npz")):
                continue
            tid = int(name[len("adapter_"):-len(".npz")])
            with np.load(os.path.join(ckpt_dir, name)) as z:
                weights = _nest({k[2:]: jnp.asarray(z[k]).astype(self.dtype)
                                 for k in z.files if k.startswith("w/")})
                state_leaves = [z[f"s/{i}"] for i in range(
                    sum(1 for k in z.files if k.startswith("s/")))]
            self.adapters[tid] = weights
            template = self.tx.init(weights)
            t_leaves, treedef = jax.tree_util.tree_flatten(template)
            rebuilt = [
                jax.random.wrap_key_data(jnp.asarray(loaded))
                if isinstance(t, jax.Array) and jnp.issubdtype(t.dtype, jax.dtypes.prng_key)
                else jnp.asarray(loaded, getattr(t, "dtype", None))
                for t, loaded in zip(t_leaves, state_leaves)
            ]
            self.opt_states[tid] = jax.tree_util.tree_unflatten(treedef, rebuilt)
            restored.append(tid)
        return restored

    def host_state_report(self) -> dict:
        """Measured twin of :func:`~accelerate_tpu.ops.lora.adapter_state_accounting`."""
        from ..ops.streaming import tree_bytes

        return {
            "n_adapters": len(self.adapters),
            "optimizer": self.plugin.optimizer,
            "weight_bytes": sum(tree_bytes(t) for t in self.adapters.values()),
            "state_bytes": sum(tree_bytes(s) for s in self.opt_states.values()),
            "kind": "measured",
        }
