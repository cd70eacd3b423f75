"""Device-mesh topology: the TPU-native replacement for process groups.

Capability parity with the reference's ``deepspeed/utils/groups.py`` (process-group
factory) and ``runtime/pipe/topology.py:9,232,249`` (``ProcessTopology``,
``PipeDataParallelTopology``, ``PipelineParallelGrid``). On TPU there are no NCCL
communicators to build: every parallel dimension is an axis of one
``jax.sharding.Mesh`` and XLA derives the "groups" from sharding annotations. This
module owns the axis algebra:

- canonical axes: ``pp`` (pipeline), ``dp`` (data/ZeRO), ``ep`` (expert), ``sp``
  (sequence/context), ``tp`` (tensor). Unused axes have size 1 and cost nothing.
- the batch is sharded over ``(dp, ep, )`` jointly (expert parallelism carves its
  groups out of data parallelism, exactly like the reference's EP x DP algebra at
  ``utils/groups.py:109,163,209``).
- ZeRO partitions over the full data-parallel extent ``dp*ep`` — matching the
  reference, where ZeRO shards across the whole DP world.

``ProcessTopology`` here is the same pure rank<->coordinate math as the reference's
(axes + cartesian grid), kept because launcher code and tests reason about ranks;
the Mesh is constructed from it.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.logging import logger

# Canonical mesh axis order, outermost first. pp outermost so stages are contiguous
# over the slowest interconnect dimension; tp innermost so tensor-parallel collectives
# ride the fastest ICI links (same reasoning as the reference's
# PipeModelDataParallelTopology axis order ``runtime/pipe/topology.py:243``).
MESH_AXES: Tuple[str, ...] = ("pp", "dp", "ep", "sp", "tp")

# Axes over which the global batch is sharded.
BATCH_AXES: Tuple[str, ...] = ("dp", "ep")
# Axes over which ZeRO partitions params/grads/optimizer state (the DP world).
ZERO_AXES: Tuple[str, ...] = ("dp", "ep")


class ProcessTopology:
    """Pure rank <-> coordinate algebra over a cartesian axis grid.

    Parity: ``runtime/pipe/topology.py:9``. Axis order is outermost-first: the last
    axis varies fastest with rank.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        assert len(axes) == len(dims)
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)

    @property
    def world_size(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_rank(self, **coords: int) -> int:
        missing = [a for a in self.axes if a not in coords]
        if missing:
            raise ValueError(f"get_rank() requires all axes; missing {missing}")
        rank = 0
        for axis, dim in zip(self.axes, self.dims):
            c = coords[axis]
            if not 0 <= c < dim:
                raise ValueError(f"coord {axis}={c} out of range [0,{dim})")
            rank = rank * dim + c
        return rank

    def get_coord(self, rank: int):
        coords = {}
        for axis, dim in zip(reversed(self.axes), reversed(self.dims)):
            coords[axis] = rank % dim
            rank //= dim
        Coord = dataclasses.make_dataclass("Coord", self.axes, frozen=True)
        return Coord(**{a: coords[a] for a in self.axes})

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """All rank-groups that vary only along ``axis`` (the reference's
        per-axis process groups)."""
        others = [a for a in self.axes if a != axis]
        lists = []
        for combo in itertools.product(*[range(self.get_dim(a)) for a in others]):
            fixed = dict(zip(others, combo))
            group = [self.get_rank(**{**fixed, axis: i}) for i in range(self.get_dim(axis))]
            lists.append(group)
        return lists

    def filter_match(self, **filter_kwargs: int) -> List[int]:
        out = []
        for rank in range(self.world_size):
            c = self.get_coord(rank)
            if all(getattr(c, a) == v for a, v in filter_kwargs.items()):
                out.append(rank)
        return out

    def get_axis_names(self) -> List[str]:
        return list(self.axes)

    def __str__(self):
        return f"ProcessTopology(axes={self.axes}, dims={self.dims})"


def PipeDataParallelTopology(num_pp: int, num_dp: int) -> ProcessTopology:
    """Parity: ``runtime/pipe/topology.py:232``."""
    return ProcessTopology(axes=["pipe", "data"], dims=[num_pp, num_dp])


def PipeModelDataParallelTopology(num_pp: int, num_mp: int, num_dp: int) -> ProcessTopology:
    """Parity: ``runtime/pipe/topology.py:243``."""
    return ProcessTopology(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Requested parallel extents. ``dp=-1`` means "everything left over"."""

    dp: int = -1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        fixed = self.tp * self.pp * self.ep * self.sp
        dp = self.dp
        if dp == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by tp*pp*ep*sp={fixed}")
            dp = n_devices // fixed
        total = dp * fixed
        if total != n_devices:
            raise ValueError(
                f"mesh {dict(pp=self.pp, dp=dp, ep=self.ep, sp=self.sp, tp=self.tp)} "
                f"needs {total} devices, have {n_devices}")
        return {"pp": self.pp, "dp": dp, "ep": self.ep, "sp": self.sp, "tp": self.tp}


class MeshTopology:
    """One ``jax.sharding.Mesh`` plus the axis bookkeeping the runtime needs.

    Replaces the reference's ``PipelineParallelGrid`` (``runtime/pipe/topology.py:249``)
    and the global group registry in ``utils/groups.py:45``.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.axes: Dict[str, int] = dict(zip(mesh.axis_names, mesh.devices.shape))
        for ax in MESH_AXES:
            self.axes.setdefault(ax, 1)

    # ------------------------------------------------------------- constructors
    @classmethod
    def create(
        cls,
        dp: int = -1,
        tp: int = 1,
        pp: int = 1,
        ep: int = 1,
        sp: int = 1,
        devices: Optional[Sequence] = None,
    ) -> "MeshTopology":
        devices = list(devices) if devices is not None else jax.devices()
        sizes = MeshConfig(dp=dp, tp=tp, pp=pp, ep=ep, sp=sp).resolve(len(devices))
        shape = tuple(sizes[a] for a in MESH_AXES)
        dev_array = np.asarray(devices).reshape(shape)
        mesh = Mesh(dev_array, MESH_AXES)
        logger.info(f"MeshTopology: {dict(zip(MESH_AXES, shape))} over {len(devices)} devices")
        return cls(mesh)

    @classmethod
    def single_device(cls, device=None) -> "MeshTopology":
        device = device or jax.devices()[0]
        return cls.create(dp=1, devices=[device])

    # ------------------------------------------------------------- sizes
    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.axes.values())))

    @property
    def data_parallel_size(self) -> int:
        """The full DP extent ZeRO partitions over (dp * ep, like the reference)."""
        return int(np.prod([self.axes[a] for a in ZERO_AXES]))

    @property
    def expert_parallel_size(self) -> int:
        return self.axes["ep"]

    @property
    def model_parallel_size(self) -> int:
        return self.axes["tp"]

    @property
    def pipe_parallel_size(self) -> int:
        return self.axes["pp"]

    @property
    def sequence_parallel_size(self) -> int:
        return self.axes["sp"]

    # ------------------------------------------------------------- specs
    def batch_spec(self, extra_dims: int = 0) -> P:
        """PartitionSpec for a [batch, ...] array: batch sharded over the DP world."""
        return P(BATCH_AXES, *([None] * extra_dims))

    def batch_sharding(self, extra_dims: int = 0) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(extra_dims))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    @property
    def zero_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in ZERO_AXES if self.axes[a] > 1) or ("dp",)

    # ------------------------------------------------------------- topology view
    def process_topology(self) -> ProcessTopology:
        return ProcessTopology(axes=list(MESH_AXES), dims=[self.axes[a] for a in MESH_AXES])

    def __repr__(self):
        return f"MeshTopology({self.axes})"


def mesh_context(mesh: Mesh):
    """Context manager binding ``mesh`` (``jax.set_mesh``) so bare
    ``PartitionSpec`` sharding constraints resolve and trace-time code can
    read the axis extents from ``jax.sharding.get_abstract_mesh()``."""
    return jax.set_mesh(mesh)


def bound_mesh():
    """The (abstract) mesh bound by the innermost :func:`mesh_context`, or
    None. Single source of truth for trace-time mesh discovery: kernels
    shard_map against it, models read axis extents from it."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


_default_topology: Optional[MeshTopology] = None


def get_topology() -> MeshTopology:
    global _default_topology
    if _default_topology is None:
        _default_topology = MeshTopology.create()
    return _default_topology


def set_topology(topo: MeshTopology) -> None:
    global _default_topology
    _default_topology = topo
