"""Collective operations over the XLA data plane.

TPU-native replacement for the reference's operation stack: the
chain-of-responsibility op classes (reference
horovod/common/ops/collective_operations.h:31-159), the MPI/NCCL/Gloo
backends (mpi_operations.cc, nccl_operations.cc, gloo_operations.cc), and
the fusion-buffer memcpys, all collapse into XLA collective HLOs —
``lax.psum`` / ``lax.all_gather`` / ``lax.psum_scatter`` /
``lax.all_to_all`` / ``lax.ppermute`` — which XLA schedules onto ICI
directly.  There is no fusion-buffer copy: XLA's all-reduce combiner plus
our gradient bucketing (ops/fusion.py) play that role.

Each function works in two planes:

* **in-SPMD** (inside :func:`horovod_tpu.spmd` / a ``rank_context``): emits
  the collective over the mesh axis — the hot path, compiled by XLA.
* **eager / host-level** (outside): operates on a rank-sharded global array
  (see :func:`horovod_tpu.spmd.put_per_rank`) by jit-compiling a tiny SPMD
  program on the fly — the analog of Horovod's enqueue-to-background-thread
  eager path (reference operations.cc:795 EnqueueTensorAllreduce), with the
  jit cache standing in for the response cache.

``process_set`` arguments take a :class:`ProcessSet` (a subset of ranks) and
map to ``axis_index_groups`` — the analog of Horovod's sub-communicator
``hvd.init(comm=...)`` (reference operations.cc:655-663).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import core
from .. import metrics as _metrics
from ..core import Average, Sum, Adasum, Min, Max
from .compression import Compression


class ProcessSet:
    """A subset of ranks forming their own collective group.

    Analog of Horovod's restricted communicator (reference
    horovod/common/operations.cc:655-663, basics.py:33-65 ``init(comm=...)``)
    — implemented as ``axis_index_groups``, so XLA lowers a group-local
    collective with no extra bootstrap.
    """

    def __init__(self, ranks: Sequence[int]):
        self.ranks = tuple(sorted(int(r) for r in ranks))
        if not self.ranks:
            raise ValueError("process set must contain at least one rank")
        if len(set(self.ranks)) != len(self.ranks):
            raise ValueError("duplicate ranks in process set")

    def groups(self) -> list:
        """axis_index_groups covering the whole mesh: this set plus the
        complement (XLA requires groups to partition the axis).

        When the complement is a multiple of the set size it is split into
        equal-size groups so shape-changing collectives (``all_gather``,
        ``psum_scatter``, ``all_to_all``) — which XLA only lowers for
        equal-size groups — take the fast path.  Complement ranks reduce
        among themselves; their results are ignored by callers that gate
        on membership.
        """
        world = set(range(core.size()))
        if not set(self.ranks) <= world:
            raise ValueError(
                f"process set ranks {self.ranks} exceed world size "
                f"{core.size()}"
            )
        rest = sorted(world - set(self.ranks))
        groups = [list(self.ranks)]
        k = len(self.ranks)
        if rest:
            if len(rest) % k == 0:
                groups += [rest[i:i + k] for i in range(0, len(rest), k)]
            else:
                groups.append(rest)
        return groups

    def equal_groups(self) -> Optional[list]:
        """:meth:`groups` if every group has the same size (the only layout
        XLA's shape-changing collectives accept), else None."""
        g = self.groups()
        return g if len({len(x) for x in g}) == 1 else None

    def member_position(self):
        """(is_member, position-in-set) for the current rank — traced
        values inside an SPMD region.  Non-members get a position that
        scatter-drops (== set size when their rank sorts past the set)."""
        r = core.rank()
        ranks = jnp.asarray(self.ranks)
        member = jnp.any(jnp.asarray(r) == ranks)
        pos = jnp.searchsorted(ranks, jnp.asarray(r))
        return member, pos

    def size(self) -> int:
        return len(self.ranks)


def _axes() -> tuple:
    axes = core._spmd_axes()
    if axes is None:
        raise RuntimeError(
            "not inside an SPMD region; use the eager API (allreduce_ on a "
            "per-rank sharded array) or wrap your step in hvd.spmd"
        )
    return axes


def _group_args(process_set: Optional[ProcessSet]):
    if process_set is None:
        return None, core.size()
    return process_set.groups(), process_set.size()


# --------------------------------------------------------------------------
# allreduce
# --------------------------------------------------------------------------
def allreduce(
    tensor,
    *,
    op: str = Average,
    name: Optional[str] = None,
    compression=Compression.none,
    process_set: Optional[ProcessSet] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    hierarchical: bool = False,
    two_level: bool = False,
):
    """Allreduce a per-rank tensor across all ranks.

    Mirrors ``hvd.allreduce`` (reference horovod/torch/mpi_ops.py:94-129 /
    horovod/tensorflow/mpi_ops.py): ``op`` is Average / Sum / Adasum /
    Min / Max; ``compression`` casts before the wire and back after
    (reference horovod/torch/compression.py).  ``hierarchical`` selects the
    two-level local/cross decomposition (the reference's
    HOROVOD_HIERARCHICAL_ALLREDUCE knob, common.h:72).  ``two_level``
    selects the compressed two-level path instead — reduce-scatter on
    ICI, ``compression`` applied to the cross-stage payload only
    (parallel/hierarchical.py two_level_allreduce, HVD_TWO_LEVEL_ALLREDUCE).
    """
    axes = _axes()
    groups, group_size = _group_args(process_set)
    # Executes once per compile (tracing), not per step: the traced-
    # collective inventory a scrape can compare against the step cadence.
    _metrics.record_traced("allreduce", tensor)

    if two_level and op in (Average, Sum, Adasum) and len(axes) == 1:
        if process_set is not None:
            raise ValueError(
                "two-level allreduce over a process subset is unsupported"
            )
        from ..parallel.hierarchical import two_level_allreduce

        t = tensor * prescale_factor if prescale_factor != 1.0 else tensor
        out = two_level_allreduce(t, op=op, compression=compression)
        return out * postscale_factor if postscale_factor != 1.0 else out

    if op == Adasum:
        from .adasum import adasum_allreduce

        # prescale BEFORE the wire cast: scaling a quantized int8/fp8
        # payload would silently promote its dtype (and re-bias the
        # quantization grid)
        if prescale_factor != 1.0:
            tensor = tensor * prescale_factor
        compressed, ctx = compression.compress_for(tensor, group_size) \
            if hasattr(compression, "compress_for") \
            else compression.compress(tensor)
        out = adasum_allreduce(
            compressed, process_set=process_set, hierarchical=hierarchical
        )
        if postscale_factor != 1.0:
            out = out * postscale_factor
        return compression.decompress(out, ctx)

    if hierarchical and op in (Min, Max):
        raise ValueError("hierarchical allreduce supports Sum/Average/Adasum")

    if prescale_factor != 1.0:
        tensor = tensor * prescale_factor     # before the wire cast, ditto
    compressed, ctx = compression.compress_for(tensor, group_size) \
        if hasattr(compression, "compress_for") \
        else compression.compress(tensor)

    if hierarchical and op in (Average, Sum) and len(axes) == 1:
        if process_set is not None:
            raise ValueError(
                "hierarchical allreduce over a process subset is unsupported"
            )
        from ..parallel.hierarchical import hierarchical_allreduce

        out = hierarchical_allreduce(compressed, op=op)
    elif op in (Average, Sum):
        if len(axes) == 1:
            out = lax.psum(compressed, axes[0], axis_index_groups=groups)
        else:
            out = lax.psum(compressed, axes)
        if op == Average:
            out = out / group_size
    elif op == Min:
        out = lax.pmin(compressed, axes if len(axes) > 1 else axes[0],
                       axis_index_groups=groups if len(axes) == 1 else None)
    elif op == Max:
        out = lax.pmax(compressed, axes if len(axes) > 1 else axes[0],
                       axis_index_groups=groups if len(axes) == 1 else None)
    else:
        raise ValueError(f"unknown reduce op: {op!r}")

    if postscale_factor != 1.0:
        out = out * postscale_factor
    return compression.decompress(out, ctx)


def grouped_allreduce(
    tensors: Sequence[Any],
    *,
    op: str = Average,
    compression=Compression.none,
    process_set: Optional[ProcessSet] = None,
    threshold_bytes: Optional[int] = None,
):
    """Allreduce a list of tensors as one fused operation.

    The explicit-fusion API: the analog of the tensor-fusion buffer pass
    (reference controller.cc:665 FuseResponses + the MemcpyInFusionBuffer /
    MemcpyOutFusionBuffer pair in ops/collective_operations.cc) — but here
    "fusion" is a flatten/concat in HLO that XLA folds into its all-reduce
    combiner, with no staging copy through a persistent buffer.
    """
    from .fusion import fused_allreduce

    return fused_allreduce(
        list(tensors), op=op, compression=compression,
        process_set=process_set, threshold_bytes=threshold_bytes,
    )


def allreduce_gradients(grads, *, op: str = Average, compression=Compression.none):
    """Allreduce every leaf of a gradient pytree (fused by dtype buckets).

    The hot-path entry used by DistributedOptimizer/DistributedGradientTape
    (reference horovod/tensorflow/__init__.py:231-252
    ``_make_allreduce_grads_fn``).
    """
    from .fusion import allreduce_pytree

    return allreduce_pytree(grads, op=op, compression=compression)


# --------------------------------------------------------------------------
# allgather
# --------------------------------------------------------------------------
def allgather(tensor, *, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None):
    """Concatenate each rank's tensor along axis 0 and replicate the result.

    Mirrors ``hvd.allgather`` (reference
    horovod/common/ops/collective_operations.cc allgather output allocation
    + displacement math).  In-SPMD requires equal shapes per rank (static
    SPMD program); for Horovod's varying-first-dimension contract use
    :func:`allgatherv`.
    """
    axes = _axes()
    _metrics.record_traced("allgather", tensor)
    if len(axes) != 1:
        return lax.all_gather(tensor, axes, axis=0, tiled=True)
    if process_set is None:
        return lax.all_gather(tensor, axes[0], axis=0, tiled=True)
    eq = process_set.equal_groups()
    if eq is not None:
        return lax.all_gather(
            tensor, axes[0], axis=0, tiled=True, axis_index_groups=eq
        )
    # Uneven groups: XLA all_gather requires equal-size groups, but psum
    # accepts any partition — embed each member's shard at its position in
    # a zero buffer and sum over the set (complement ranks sum zeros).
    return _psum_embed_gather(tensor, axes[0], process_set)


def _psum_embed_gather(tensor, axis_name, process_set: "ProcessSet"):
    k = process_set.size()
    member, pos = process_set.member_position()
    contrib = jnp.where(member, tensor, jnp.zeros_like(tensor))
    buf = jnp.zeros((k,) + tuple(tensor.shape), tensor.dtype)
    buf = buf.at[pos].set(contrib)  # OOB pos (non-member) drops the update
    out = lax.psum(buf, axis_name, axis_index_groups=process_set.groups())
    return out.reshape((k * tensor.shape[0],) + tuple(tensor.shape[1:]))


def allgatherv(tensor, *, valid_rows, max_rows: int,
               process_set: Optional[ProcessSet] = None):
    """Allgather with per-rank varying first dimension.

    Horovod negotiates per-rank sizes at runtime through the coordinator
    (reference controller.cc:377 ConstructResponse collects tensor sizes
    into the Response).  A static SPMD program can't have per-rank shapes,
    so the TPU-native contract is pad-to-``max_rows`` + a ``valid_rows``
    scalar; returns ``(gathered, row_counts)`` where ``gathered`` is
    ``[size * max_rows, ...]`` with invalid rows zeroed, and ``row_counts``
    the per-rank valid counts — callers slice out valid rows on host.
    """
    pad_width = [(0, max_rows - tensor.shape[0])] + [(0, 0)] * (tensor.ndim - 1)
    padded = jnp.pad(tensor, pad_width)
    mask = (jnp.arange(max_rows) < valid_rows).reshape(
        (max_rows,) + (1,) * (tensor.ndim - 1)
    )
    padded = jnp.where(mask, padded, jnp.zeros_like(padded))
    counts_in = jnp.asarray(valid_rows, jnp.int32)
    return (
        allgather(padded, process_set=process_set),
        allgather(counts_in[None], process_set=process_set),
    )


# --------------------------------------------------------------------------
# broadcast
# --------------------------------------------------------------------------
def broadcast(tensor, root_rank: int = 0, *, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None):
    """Every rank receives ``root_rank``'s value.

    Mirrors ``hvd.broadcast`` (reference horovod/common/ops/
    mpi_operations.cc MPIBroadcast / nccl_operations.cc NCCLBroadcast).
    Implemented as a masked psum — XLA has no one-to-all HLO, and of the
    expressible schedules this is the deliberate choice: a ring psum
    moves 2(n-1)/n x bytes over ICI (~2x a textbook broadcast's
    (n-1)/n) in ONE collective, vs n x bytes for all_gather-and-index or
    (n-1) serial latency hops for a ppermute pipeline.  On ICI the 2x is
    noise (broadcast traffic is start-up parameter sync); across DCN
    prefer the host-plane ``eager.process_broadcast``, which sends the
    payload once.
    """
    axes = _axes()
    _metrics.record_traced("broadcast", tensor)
    groups, _ = _group_args(process_set)
    r = core.rank()
    masked = jnp.where(r == root_rank, tensor, jnp.zeros_like(tensor))
    if len(axes) == 1:
        return lax.psum(masked, axes[0], axis_index_groups=groups)
    return lax.psum(masked, axes)


# --------------------------------------------------------------------------
# alltoall / reducescatter
# --------------------------------------------------------------------------
def alltoall(tensor, *, process_set: Optional[ProcessSet] = None):
    """Equal-split all-to-all: rank i's j-th chunk (along axis 0) goes to
    rank j.  Requires ``tensor.shape[0] % size == 0``.

    (Beyond-parity: upstream Horovod grew alltoall in 0.20; included here
    because sequence-parallel attention — parallel/ring_attention.py — and
    MoE expert dispatch are built on it.)
    """
    axes = _axes()
    _metrics.record_traced("alltoall", tensor)
    if len(axes) != 1:
        raise NotImplementedError("alltoall over hierarchical mesh")
    n = core.size() if process_set is None else process_set.size()
    if tensor.shape[0] % n:
        raise ValueError(
            f"alltoall first dim {tensor.shape[0]} not divisible by {n}"
        )
    groups = None
    if process_set is not None:
        groups = process_set.equal_groups()
        if groups is None:
            # XLA all_to_all needs equal-size groups; psum accepts any
            # partition — same embed trick as allgather's uneven path
            return _psum_embed_alltoall(tensor, axes[0], process_set)
    split = tensor.reshape((n, tensor.shape[0] // n) + tensor.shape[1:])
    out = lax.all_to_all(split, axes[0], split_axis=0, concat_axis=0,
                         axis_index_groups=groups, tiled=False)
    return out.reshape((-1,) + tensor.shape[1:])


def _psum_embed_alltoall(tensor, axis_name, process_set: "ProcessSet"):
    """alltoall for uneven ProcessSets: member at position p embeds its k
    chunks at row p of a zero [k, k, chunk, ...] buffer; after a psum
    over the set, every member holds the full exchange matrix and takes
    column p (its incoming chunks).  Wire cost is k× the minimal
    alltoall — acceptable at ProcessSet control sizes, and the only
    schedule XLA can express for ragged groups (reference keeps uneven
    sets on MPI sub-communicators instead, operations.cc:655-663)."""
    k = process_set.size()
    chunk = tensor.shape[0] // k
    member, pos = process_set.member_position()
    split = tensor.reshape((k, chunk) + tuple(tensor.shape[1:]))
    contrib = jnp.where(member, split, jnp.zeros_like(split))
    buf = jnp.zeros((k,) + split.shape, tensor.dtype)
    buf = buf.at[pos].set(contrib)  # OOB pos (non-member) drops the update
    full = lax.psum(buf, axis_name, axis_index_groups=process_set.groups())
    out = jnp.take(full, jnp.minimum(pos, k - 1), axis=1)  # [k, chunk, ...]
    return out.reshape((-1,) + tuple(tensor.shape[1:]))


def reducescatter(tensor, *, op: str = Sum,
                  process_set: Optional[ProcessSet] = None):
    """Reduce across ranks and scatter equal chunks of axis 0.

    The building block of hierarchical allreduce (reference
    nccl_operations.cc:241-246 uses ncclReduceScatter for exactly this).
    """
    axes = _axes()
    _metrics.record_traced("reducescatter", tensor)
    if len(axes) != 1:
        raise NotImplementedError("reducescatter over hierarchical mesh")
    if process_set is None:
        out = lax.psum_scatter(tensor, axes[0], scatter_dimension=0,
                               tiled=True)
        if op == Average:
            out = out / core.size()
        return out
    k = process_set.size()
    if tensor.shape[0] % k:
        raise ValueError(
            f"reducescatter first dim {tensor.shape[0]} not divisible by "
            f"process set size {k}"
        )
    eq = process_set.equal_groups()
    if eq is not None:
        out = lax.psum_scatter(tensor, axes[0], scatter_dimension=0,
                               tiled=True, axis_index_groups=eq)
    else:
        # Uneven groups: full psum over the set (psum accepts any
        # partition), then each member slices out its own chunk.
        full = lax.psum(tensor, axes[0],
                        axis_index_groups=process_set.groups())
        chunk = tensor.shape[0] // k
        _, pos = process_set.member_position()
        out = lax.dynamic_slice_in_dim(
            full, jnp.minimum(pos, k - 1) * chunk, chunk, axis=0
        )
    if op == Average:
        out = out / k
    return out
