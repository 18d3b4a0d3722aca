"""N-D parallelism configuration → :class:`jax.sharding.Mesh`.

TPU-native re-design of reference ``parallelism_config.py`` (398 LoC):
``ParallelismConfig`` (:34) validates per-axis sizes and ``build_device_mesh``
(:211) produces the device mesh with canonical dim order
``dp_replicate, dp_shard, cp, sp, tp`` (:267) plus the flattened joint dims
``dp``/``dp_shard_cp``/``dp_cp`` (:157-164, :239-240).

On JAX the "flattened joint dims" need no physical flattening: a
:class:`jax.sharding.PartitionSpec` entry can name a *tuple* of mesh axes, so
``dp`` is simply ``("dp_replicate", "dp_shard")``.  We expose the same names as
spec-tuple properties.

ICI/DCN mapping: ``dp_replicate`` is the outermost (slowest) mesh dim so that
under multi-slice it lands on DCN while ``dp_shard/cp/sp/tp`` ride ICI — the
canonical layout from the scaling playbook.  ``jax.make_mesh`` picks a
topology-aware device order for the ICI dims.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

# Canonical axis order — mirrors reference parallelism_config.py:267 with the
# TPU-native additions of an expert-parallel axis (reference has no first-class
# EP; SURVEY §2.4 P10 calls for one) and a pipeline axis (reference PP is
# inference-only via PiPPy, inference.py:126, or Megatron pp_degree).  ``pp``
# sits next to ``dp_replicate`` at the outside: stage hand-offs are infrequent
# point-to-point transfers, so like replicate traffic they can ride DCN while
# dp_shard/cp/sp/tp stay on ICI.
#
# ``dcn`` is the OUTERMOST axis: the explicit cross-slice data-parallel
# dimension of a multi-host/multi-slice launch (`accelerate_tpu launch`).
# Devices that differ only in their dcn coordinate sit in different slices —
# traffic across it rides the datacenter network, not ICI.  The hierarchical
# gradient-sync path (parallel/hierarchical.py) keys off this axis name:
# reduce-scatter inside the slice over ICI, one cross-slice all-reduce of the
# sharded slab over DCN, all-gather back.  ``dcn`` is pure data parallelism
# like ``dp_replicate`` (params replicate across it, batch shards over it);
# the distinct name exists so the launcher, the mesh, the sync path and the
# accounting twins all agree on which hops are expensive.
MESH_AXIS_ORDER = ("dcn", "dp_replicate", "pp", "dp_shard", "cp", "sp", "tp", "ep")

# The axes the batch dim (pure data parallelism, ``dcn`` outermost) and the
# sequence dim (CP ring / SP Ulysses) shard over: one list for the batch as it
# arrives (``batch_spec``, ``Accelerator._default_batch_spec``) and for the
# layout the training path states for its activations
# (``parallel/sharding.constrain_activation``, ``mesh_flash_attention``).
BATCH_AXES = ("dcn", "dp_replicate", "dp_shard")
SEQ_AXES = ("cp", "sp")

# The per-axis size fields / env vars are derived from the axis list so a new
# axis cannot silently miss one of the transport surfaces (launcher flags,
# PARALLELISM_CONFIG_* env, from_env/to_env).
AXIS_SIZE_FIELDS = tuple(f"{name}_size" for name in MESH_AXIS_ORDER)


@dataclass
class ParallelismConfig:
    """Validated sizes for each parallelism axis.

    Mirrors reference ``ParallelismConfig`` (parallelism_config.py:34):
    the product of all enabled sizes must equal the device count; any axis can
    be left at its default of 1.  ``dp_shard_size=-1`` infers the remainder
    (reference :120-130 behavior).
    """

    dcn_size: int = 1
    dp_replicate_size: int = 1
    dp_shard_size: int = 1
    cp_size: int = 1
    sp_size: int = 1
    tp_size: int = 1
    ep_size: int = 1
    pp_size: int = 1

    # Advanced: override the device list (testing / explicit topology)
    devices: Optional[Sequence] = field(default=None, repr=False, compare=False)

    @classmethod
    def from_env(cls) -> "ParallelismConfig":
        """Re-hydrate from ``PARALLELISM_CONFIG_*`` env vars, the launcher's
        transport channel (reference parallelism_config.py:274-289)."""

        return cls(**{
            field: int(os.environ.get(f"PARALLELISM_CONFIG_{field.upper()}", "1"))
            for field in AXIS_SIZE_FIELDS
        })

    def to_env(self) -> dict[str, str]:
        return {
            f"PARALLELISM_CONFIG_{field.upper()}": str(getattr(self, field))
            for field in AXIS_SIZE_FIELDS
        }

    # -- size accessors ----------------------------------------------------

    def _sizes(self) -> dict[str, int]:
        return {
            "dcn": self.dcn_size,
            "dp_replicate": self.dp_replicate_size,
            "dp_shard": self.dp_shard_size,
            "cp": self.cp_size,
            "sp": self.sp_size,
            "tp": self.tp_size,
            "ep": self.ep_size,
            "pp": self.pp_size,
        }

    @property
    def total_size(self) -> int:
        total = 1
        for v in self._sizes().values():
            total *= v
        return total

    @property
    def non_data_parallel_size(self) -> int:
        """reference parallelism_config.py — cp*sp*tp*ep*pp: the factor by
        which dataloader ranks are collapsed so non-DP ranks see identical
        batches (reference data_loader.py:1109-1145; all pipeline stages of
        one replica consume the same batch)."""
        return self.cp_size * self.sp_size * self.tp_size * self.ep_size * self.pp_size

    @property
    def data_parallel_size(self) -> int:
        return self.dcn_size * self.dp_replicate_size * self.dp_shard_size

    @property
    def has_dcn(self) -> bool:
        """True when the mesh carries a non-trivial cross-slice axis — the
        trigger for the hierarchical ICI→DCN gradient-sync path."""
        return self.dcn_size > 1

    # -- joint dims as PartitionSpec tuples (reference flattened mesh dims) --

    @property
    def dp_dim_names(self) -> tuple[str, ...]:
        return self._enabled(BATCH_AXES)

    @property
    def dp_shard_cp_dim_names(self) -> tuple[str, ...]:
        """FSDP sharding dim under CP (reference ``dp_shard_cp`` :157-164)."""
        return self._enabled(("dp_shard", "cp"))

    @property
    def dp_cp_dim_names(self) -> tuple[str, ...]:
        """Loss-averaging dims (reference ``dp_cp`` :146-155)."""
        return self._enabled(("dcn", "dp_replicate", "dp_shard", "cp"))

    @property
    def fsdp_dim_names(self) -> tuple[str, ...]:
        """Axes parameters shard over under FULL/HYBRID shard
        (reference fsdp_dim_names :157-164)."""
        return self.dp_shard_cp_dim_names

    @property
    def batch_dim_names(self) -> tuple[str, ...]:
        """Axes the batch dimension of input data shards over.  ``dcn`` is
        outermost so each slice's hosts feed a contiguous block of the
        global batch (the per-host dataloader sharding contract)."""
        return self._enabled(BATCH_AXES)

    @property
    def seq_dim_names(self) -> tuple[str, ...]:
        """Axes the sequence dimension shards over (CP ring / SP Ulysses)."""
        return self._enabled(SEQ_AXES)

    def _enabled(self, names: Sequence[str]) -> tuple[str, ...]:
        sizes = self._sizes()
        return tuple(n for n in names if sizes[n] > 1)

    @property
    def active_mesh_dims(self) -> tuple[str, ...]:
        return self._enabled(MESH_AXIS_ORDER)

    # -- validation + mesh build ------------------------------------------

    def _validate(self, num_devices: int) -> None:
        sizes = self._sizes()
        for name, v in sizes.items():
            if name == "dp_shard" and v == -1:
                continue
            if v < 1:
                raise ValueError(f"{name}_size must be >= 1, got {v}")
        if self.cp_size > 1 and self.sp_size > 1:
            # reference parallelism_config.py:328-334 — CP and SP are mutually
            # exclusive ways to shard the sequence dimension.
            raise ValueError("cp_size and sp_size cannot both be > 1 (pick ring CP or Ulysses SP)")
        if self.dp_shard_size == -1:
            rest = (
                self.dcn_size * self.dp_replicate_size * self.cp_size * self.sp_size
                * self.tp_size * self.ep_size * self.pp_size
            )
            if num_devices % rest != 0:
                raise ValueError(
                    f"cannot infer dp_shard_size: {num_devices} devices not divisible by {rest}"
                )
            self.dp_shard_size = num_devices // rest
        if self.total_size != num_devices:
            raise ValueError(
                f"ParallelismConfig total size {self.total_size} "
                f"({self._sizes()}) != available devices {num_devices}"
            )

    def build_device_mesh(self, devices: Optional[Sequence] = None) -> Mesh:
        """Build the N-D :class:`Mesh` (reference build_device_mesh :211).

        Always materializes *all seven* axes (size-1 axes are free) so partition
        specs can reference any axis name regardless of config — XLA treats
        size-1 mesh dims as no-ops.  ``dp_replicate`` is outermost so
        multi-slice replication maps to DCN.
        """
        devices = list(devices if devices is not None else (self.devices or jax.devices()))
        self._validate(len(devices))
        sizes = self._sizes()
        shape = tuple(sizes[name] for name in MESH_AXIS_ORDER)
        # Auto axis types = classic GSPMD propagation from in_shardings.
        # (jax>=0.9 make_mesh defaults to the new Explicit sharding-in-types
        # mode, which changes jit semantics — not what a prepare()-style
        # framework wants.)
        axis_types = (jax.sharding.AxisType.Auto,) * len(MESH_AXIS_ORDER)
        if self.devices is None and devices == list(jax.devices()):
            # Topology-aware assignment (ICI-ring friendly) over the whole
            # backend; a mesh jax cannot build is an error, never a silent
            # plain reshape.
            return jax.make_mesh(shape, MESH_AXIS_ORDER, devices=devices,
                                 axis_types=axis_types)
        mesh_devices = np.asarray(devices).reshape(shape)
        return Mesh(mesh_devices, MESH_AXIS_ORDER, axis_types=axis_types)

    # -- convenience specs -------------------------------------------------

    def batch_spec(self, seq_axis: Optional[int] = 1, ndim: int = 2) -> PartitionSpec:
        """PartitionSpec for an input batch: batch dim over dp axes, sequence
        dim over cp/sp axes."""
        entries: list = [self.batch_dim_names or None]
        for dim in range(1, ndim):
            if seq_axis is not None and dim == seq_axis and self.seq_dim_names:
                entries.append(self.seq_dim_names)
            else:
                entries.append(None)
        return PartitionSpec(*entries)

    def __str__(self):
        sizes = self._sizes()
        active = {k: v for k, v in sizes.items() if v > 1}
        return f"ParallelismConfig({active or 'single-device'})"
