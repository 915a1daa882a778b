"""The Comms veneer: reference comms_t surface → XLA collectives.

Method-by-method mapping to the reference (core/comms.hpp:242-530):

| reference comms_t         | here (inside shard_map)            |
|---------------------------|------------------------------------|
| allreduce(SUM/MIN/MAX)    | allreduce / psum, pmin, pmax       |
| bcast(root)               | bcast — select root shard + psum   |
| reduce(root)              | reduce — psum, value kept at root  |
| allgather / allgatherv    | allgather (lax.all_gather)         |
| gather(v)(root)           | allgather (XLA has no rooted tree; |
|                           | rooted variants return full copy)  |
| reducescatter             | reducescatter (lax.psum_scatter)   |
| device_send/recv, sendrecv| ppermute (lax.ppermute)            |
| comm_split                | sub-axis Comms over the same mesh  |
| barrier                   | barrier — psum of a scalar 1       |
| sync_stream               | host-side block_until_ready        |
| get_rank / get_size       | rank() / size() via axis_index     |
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.errors import expects

__all__ = ["Comms", "shard_along", "replicated"]


def _payload_bytes(x) -> int:
    """Per-shard payload bytes of a collective operand — works on tracers
    (shape/dtype are known at trace time; scalars count their promoted
    size)."""
    shape = getattr(x, "shape", ())
    dtype = getattr(x, "dtype", None)
    itemsize = dtype.itemsize if dtype is not None else 4
    return int(math.prod(shape)) * itemsize


def shard_along(mesh: Mesh, axis: str, x, dim: int = 0):
    """Place ``x`` row-sharded along a mesh axis (the user-side data
    distribution step that raft-dask leaves to Dask partitioning)."""
    spec = [None] * jnp.asarray(x).ndim
    spec[dim] = axis
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def replicated(mesh: Mesh, x):
    """Place ``x`` fully replicated over the mesh."""
    return jax.device_put(x, NamedSharding(mesh, P()))


@dataclasses.dataclass(frozen=True)
class Comms:
    """Communicator bound to one mesh axis (reference: comms_t, core/comms.hpp:242).

    Collective methods must be called inside a ``shard_map`` whose mesh
    includes ``self.axis`` — the same way comms_t methods must run on the
    handle's stream. Use :meth:`shard_map` to enter that region.
    """

    mesh: Mesh
    axis: str = "data"

    def __post_init__(self):
        expects(self.axis in self.mesh.axis_names, "axis %r not in mesh %s", self.axis, self.mesh)

    # -- observability ------------------------------------------------------
    def _record(self, op: str, x) -> None:
        """Per-collective counters (docs/observability.md). Collectives run
        inside jitted shard_map programs, so this fires at TRACE time: the
        counters measure the comms volume of each newly staged program (per
        shard), not per-execution traffic — re-running a cached program adds
        nothing. That is the zero-overhead contract: nothing rides the
        executed hot path, and a program's collective footprint is exactly
        what a capacity planner needs alongside its QPS."""
        from ..obs import metrics as _m

        if not _m._enabled:
            return
        lbl = dict(op=op, axis=self.axis, size=self.size())
        _m.counter("raft_tpu_collective_calls_total",
                   "collectives staged per traced program").inc(1, **lbl)
        _m.counter("raft_tpu_collective_bytes_total",
                   "per-shard payload bytes of staged collectives",
                   unit="bytes").inc(_payload_bytes(x), **lbl)

    # -- topology ----------------------------------------------------------
    def size(self) -> int:
        """Static clique size (reference: get_size)."""
        return self.mesh.shape[self.axis]

    def rank(self):
        """Traced rank of the calling shard (reference: get_rank)."""
        return lax.axis_index(self.axis)

    def comm_split(self, axis: str) -> "Comms":
        """Sub-communicator over another mesh axis (reference: comm_split
        :329 — here sub-cliques are mesh axes, declared not negotiated)."""
        return Comms(self.mesh, axis)

    # -- collectives (inside shard_map) ------------------------------------
    def allreduce(self, x, op: str = "sum"):
        """Reference: allreduce :371 with op_t{SUM,PROD,MIN,MAX} :34."""
        self._record("allreduce", x)
        return self._allreduce(x, op)

    def _allreduce(self, x, op: str):
        if op == "sum":
            return lax.psum(x, self.axis)
        if op == "min":
            return lax.pmin(x, self.axis)
        if op == "max":
            return lax.pmax(x, self.axis)
        if op == "prod":
            # exp(psum(log|x|)) with sign and zero handled explicitly so
            # arbitrary reals reduce correctly (reference op_t::PROD).
            x = jnp.asarray(x)
            has_zero = lax.psum((x == 0).astype(jnp.int32), self.axis) > 0
            neg = lax.psum((x < 0).astype(jnp.int32), self.axis)
            sign = jnp.where(neg % 2 == 1, -1.0, 1.0)
            mag = jnp.exp(lax.psum(jnp.log(jnp.where(x == 0, 1.0, jnp.abs(x))), self.axis))
            return jnp.where(has_zero, 0.0, sign * mag).astype(x.dtype)
        from ..core.errors import fail

        fail("unknown reduction op %s", op)

    def bcast(self, x, root: int = 0):
        """Reference: bcast :391 — zero out non-root shards, sum."""
        self._record("bcast", x)
        return lax.psum(jnp.where(self.rank() == root, x, jnp.zeros_like(x)), self.axis)

    def reduce(self, x, root: int = 0, op: str = "sum"):
        """Reference: reduce :411 — XLA collectives are all-to-all by nature;
        the reduced value lands everywhere and non-root shards may ignore it."""
        self._record("reduce", x)
        return self._allreduce(x, op)

    def allgather(self, x, tiled: bool = False):
        """Reference: allgather :431 (allgatherv is the ragged variant — on
        TPU pad to the max shard size first; static shapes are the contract)."""
        self._record("allgather", x)
        return lax.all_gather(x, self.axis, tiled=tiled)

    def gather(self, x, root: int = 0, tiled: bool = False):
        """Reference: gather :451 — implemented as allgather (no rooted tree
        on ICI; root semantics are a host-side concern)."""
        self._record("gather", x)
        return lax.all_gather(x, self.axis, tiled=tiled)

    def reducescatter(self, x, op: str = "sum"):
        """Reference: reducescatter :511 → psum_scatter (rides ICI as a ring)."""
        expects(op == "sum", "reducescatter supports sum (XLA psum_scatter)")
        self._record("reducescatter", x)
        return lax.psum_scatter(x, self.axis, tiled=True)

    def ppermute(self, x, perm: Sequence[tuple[int, int]]):
        """Point-to-point pattern (reference: device_send/device_recv
        :530-570 pairs, device_sendrecv) — one lax.ppermute, the ICI-native
        form of neighbor exchange."""
        self._record("ppermute", x)
        return lax.ppermute(x, self.axis, perm)

    def shift(self, x, offset: int = 1):
        """Ring shift helper (send to rank+offset) — the common sendrecv use."""
        self._record("shift", x)
        n = self.size()
        perm = [(i, (i + offset) % n) for i in range(n)]
        return lax.ppermute(x, self.axis, perm)

    def alltoall(self, x):
        """Reference: device_multicast_sendrecv :590 generalization — XLA
        all_to_all over the leading dim (must be divisible by size())."""
        self._record("alltoall", x)
        return lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0, tiled=True)

    def barrier(self):
        """Reference: barrier :620 — a collective no shard can pass alone."""
        self._record("barrier", jnp.ones((), jnp.int32))
        return lax.psum(jnp.ones((), jnp.int32), self.axis)

    # -- host-side helpers --------------------------------------------------
    def shard_map(self, fn, in_specs, out_specs, check_vma: bool = False):
        """Enter the SPMD region this communicator's collectives live in."""
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma
        )

    def sync_stream(self, *arrays):
        """Reference: sync_stream (core/comms.hpp:290) incl. the NCCL
        async-error surface — XLA raises on a failed collective here."""
        jax.block_until_ready(arrays)
