"""Tensor fusion: bucketing many small tensors into few large collectives.

TPU-native re-design of the fusion buffer (reference
horovod/common/fusion_buffer_manager.cc/.h — persistent 64 MB buffers per
(device, framework, stream) — plus the response-fusion pass in
controller.cc:665 FuseResponses and the MemcpyIn/OutFusionBuffer kernels in
ops/collective_operations.cc).

On TPU there is no persistent staging buffer and no memcpy kernel: we
flatten each gradient leaf, group leaves of the same dtype into buckets of
at most ``HVD_FUSION_THRESHOLD`` bytes (reference default 64 MB,
common.h:69), concatenate each bucket, run ONE ``psum`` per bucket, and
split back.  XLA fuses the concat/split with neighbors, and its own
all-reduce combiner provides a second level of batching — the autotuner
(optim/autotune.py) owns both knobs, as SURVEY §7.3(2) requires.

Bucketing is a *trace-time* planner (shapes are static under jit), which is
exactly the negotiated-once-then-cached steady state of the reference's
response cache — except the "cache" is the compiled executable.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .. import core
from .. import metrics as _metrics
from ..core import Average, Sum
from ..utils import env as env_util
from .compression import Compression


def dispatch_group_label(process_set=None) -> str:
    """The communication-group label a dispatch reduces over — ``world``
    for the flat mesh, ``process_set:<ranks>`` for a restricted
    communicator.  The label vocabulary is a protocol string documented
    in docs/analysis.md: the traced inventory
    (metrics.record_traced_group), the runtime sanitizer fingerprints
    (analysis/sanitizer.py), and the static schedule checker
    (analysis/schedule/ir.py) all spell the same family names."""
    if process_set is None:
        return "world"
    return "process_set:" + ",".join(str(r) for r in process_set.ranks)


class FusionPlan:
    """A static bucketing of a fixed list of (shape, dtype) leaves.

    Two construction modes:

    * **threshold** (the default, the reference's single global knob):
      greedy same-dtype packing under ``threshold_bytes``;
    * **explicit** (``explicit_buckets`` — the profile-guided planner's
      vector-of-buckets knob, optim/profile_guided.py): the caller names
      exactly which leaves fuse together, in which dispatch order.
      Buckets are split by dtype where members mix (one ``concatenate``
      per dtype), and leaves no bucket claims ride as singletons
      appended after the plan — an explicit plan can therefore never
      drop a gradient.

    ``buckets`` is the dispatch order: ``fused_allreduce`` launches
    bucket 0's collective first, which under XLA's latency-hiding
    scheduler is the overlap hook — the planner orders buckets so early
    gradients go on the wire while later compute still runs.
    """

    def __init__(self, leaves: Sequence[Any],
                 threshold_bytes: Optional[int] = None,
                 explicit_buckets: Optional[Sequence[Sequence[int]]] = None,
                 bucket_compression: Optional[Sequence[Optional[str]]] = None):
        if threshold_bytes is None:
            threshold_bytes = env_util.fusion_threshold_bytes()
        self.threshold_bytes = max(int(threshold_bytes), 1)
        self.explicit = explicit_buckets is not None
        self.buckets: List[List[int]] = []
        #: per final bucket: compression registry name or None (global
        #: compression applies) — the planner's per-bucket wire-format
        #: knob (optim/profile_guided.py FusionPlanSpec.compression)
        self.bucket_compression: List[Optional[str]] = []
        if explicit_buckets is not None:
            self._build_explicit(leaves, explicit_buckets,
                                 bucket_compression)
        else:
            self._build_threshold(leaves)

    def _build_threshold(self, leaves: Sequence[Any]) -> None:
        # bucket := list of leaf indices, all same dtype, total bytes <= threshold
        current: dict = {}  # dtype -> (bucket_idx, bytes_so_far)
        for i, leaf in enumerate(leaves):
            dt = jnp.result_type(leaf)
            nbytes = leaf.size * dt.itemsize
            slot = current.get(dt)
            if slot is not None and slot[1] + nbytes <= self.threshold_bytes:
                self.buckets[slot[0]].append(i)
                current[dt] = (slot[0], slot[1] + nbytes)
            else:
                self.buckets.append([i])
                current[dt] = (len(self.buckets) - 1, nbytes)
        self.bucket_compression = [None] * len(self.buckets)

    def _build_explicit(self, leaves: Sequence[Any],
                        explicit: Sequence[Sequence[int]],
                        compression: Optional[Sequence[Optional[str]]] = None
                        ) -> None:
        n = len(leaves)
        seen: set = set()
        for bi, bucket in enumerate(explicit):
            comp = compression[bi] if compression is not None \
                and bi < len(compression) else None
            by_dtype: dict = {}  # dtype -> list of indices, order kept
            for i in bucket:
                i = int(i)
                if not 0 <= i < n:
                    raise ValueError(
                        f"fusion plan references leaf {i} but only {n} "
                        "leaves exist")
                if i in seen:
                    raise ValueError(
                        f"fusion plan assigns leaf {i} to two buckets")
                seen.add(i)
                by_dtype.setdefault(jnp.result_type(leaves[i]),
                                    []).append(i)
            for b in by_dtype.values():
                if b:
                    # dtype-split halves inherit the source bucket's
                    # compression choice
                    self.buckets.append(b)
                    self.bucket_compression.append(comp)
        # unclaimed leaves: singletons, appended in leaf order, no
        # plan-level compression (the global compressor still applies)
        for i in range(n):
            if i not in seen:
                self.buckets.append([i])
                self.bucket_compression.append(None)

    @classmethod
    def from_named_buckets(cls, leaves: Sequence[Any],
                           names: Sequence[str],
                           named_buckets: Sequence[Sequence[str]],
                           bucket_compression:
                           Optional[Sequence[Optional[str]]] = None
                           ) -> "FusionPlan":
        """Explicit plan from tensor NAMES (the vocabulary of the replay
        plan payload) matched against this call's leaf names: exact
        match first, then path-suffix either way (trace span names are
        often the trailing component of ``a/b/kernel`` manifest names).
        Unmatched plan names are ignored — the trace may mention tensors
        this step doesn't carry — and unmatched leaves fall out as
        appended singletons (explicit-plan semantics above)."""
        index: dict = {str(nm): i for i, nm in enumerate(names)}

        def match(name: str) -> Optional[int]:
            if name in index:
                return index[name]
            for nm, i in index.items():
                if nm.endswith("/" + name) or name.endswith("/" + nm):
                    return i
            return None

        used: set = set()
        explicit: List[List[int]] = []
        comps: List[Optional[str]] = []
        for bi, bucket in enumerate(named_buckets):
            idxs = []
            for name in bucket:
                i = match(str(name))
                if i is not None and i not in used:
                    used.add(i)
                    idxs.append(i)
            if idxs:
                explicit.append(idxs)
                comps.append(bucket_compression[bi]
                             if bucket_compression is not None
                             and bi < len(bucket_compression) else None)
        return cls(leaves, explicit_buckets=explicit,
                   bucket_compression=comps)

    def num_buckets(self) -> int:
        return len(self.buckets)

    def describe(self, leaves: Sequence[Any],
                 names: Sequence[str]) -> List[dict]:
        """For each bucket, in dispatch order: its number (the ``<k>`` of
        the ``hvd_bucket_<k>`` scope its ops carry), the names of its
        leaves, its dtype and its bytes — what ties a collective in a
        device trace to tensors."""
        out = []
        for k, bucket in enumerate(self.buckets):
            dt = jnp.result_type(leaves[bucket[0]])
            out.append({
                "bucket": k, "scope": bucket_scope(k),
                "leaves": [str(names[i]) for i in bucket],
                "dtype": str(dt),
                "bytes": int(sum(leaves[i].size for i in bucket)
                             * dt.itemsize),
            })
        return out


def bucket_scope(k: int) -> str:
    """The ``jax.named_scope`` bucket ``k``'s pack, reduce and unpack run
    under."""
    return f"hvd_bucket_{k}"


def tree_leaf_names(tree, *, is_leaf=None) -> List[str]:
    """Slash-joined key paths of a pytree's leaves (``params/dense/kernel``
    vocabulary — matches the Recorder's gradient manifest names)."""
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]

    def key_str(k) -> str:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)

    return ["/".join(key_str(k) for k in path) for path, _leaf in paths]


def _reduce_flat(flat, *, op, axes, groups, group_size):
    if len(axes) == 1:
        out = lax.psum(flat, axes[0], axis_index_groups=groups)
    else:
        out = lax.psum(flat, axes)
    if op == Average:
        out = out / group_size
    return out


def _compress_with(comp, tensor, group_size: int):
    """One compressor call, via ``compress_for`` when the compressor has
    it (quantizers need the reducing-group headroom) with a fallback to
    the legacy two-method interface for user subclasses."""
    fn = getattr(comp, "compress_for", None)
    if fn is not None:
        return fn(tensor, group_size)
    return comp.compress(tensor)


def fused_allreduce(
    tensors: List[Any],
    *,
    op: str = Average,
    compression=Compression.none,
    process_set=None,
    threshold_bytes: Optional[int] = None,
    plan: Optional[FusionPlan] = None,
    residuals: Optional[List[Any]] = None,
):
    """Allreduce a list of tensors with static bucketing; returns the list in
    the original order (reference semantics: grouped allreduce results are
    per-input, horovod/common/controller.cc FuseResponses).  ``plan``
    overrides the threshold bucketing with an explicit
    :class:`FusionPlan` (profile-guided tuning); buckets dispatch in plan
    order, which is the overlap schedule under XLA's latency-hiding
    scheduler.  A plan may carry per-bucket ``bucket_compression``
    (registry names) overriding the global ``compression`` for its
    members — the planner's wire-format knob.

    ``residuals`` (a list aligned with ``tensors``) switches on error
    feedback: each float tensor reduces ``t + r`` and the call returns
    ``(outputs, new_residuals)`` with ``r' = (t + r) - dequantized local
    contribution`` (docs/compression.md) — the residual list is the
    explicit state the caller must thread to the next step."""
    from .compression import _compressible

    axes = core._spmd_axes()
    if axes is None:
        raise RuntimeError("fused_allreduce must run inside an SPMD region")
    if process_set is None:
        groups, group_size = None, core.size()
    else:
        groups, group_size = process_set.groups(), process_set.size()
    # group identity surfaced to dispatch: restricted-communicator
    # reductions ride the group-labelled traced inventory (the flat
    # world is the unlabelled default, counted at the collectives seam)
    group_label = dispatch_group_label(process_set)
    if group_label != "world":
        for _ in tensors:
            _metrics.record_traced_group("allreduce", group_label)
    if residuals is not None and len(residuals) != len(tensors):
        raise ValueError(
            f"error-feedback residual list has {len(residuals)} entries "
            f"for {len(tensors)} tensors")

    # per-tensor compressor: the plan's per-bucket choice where given,
    # the global compression elsewhere.  Resolution happens BEFORE the
    # compress pass so each tensor is quantized exactly once, with its
    # own scale, in its bucket's wire format.
    comps = [compression] * len(tensors)
    if plan is not None and plan.bucket_compression:
        for bi, bucket in enumerate(plan.buckets):
            name = plan.bucket_compression[bi] \
                if bi < len(plan.bucket_compression) else None
            if name:
                comp = Compression.lookup(name)
                for i in bucket:
                    comps[i] = comp

    compressed = []
    ctxs = []
    new_res: Optional[List[Any]] = list(residuals) \
        if residuals is not None else None
    for i, t in enumerate(tensors):
        x = t
        ef = residuals is not None and _compressible(t)
        if ef:
            x = t + residuals[i].astype(t.dtype)
        c, ctx = _compress_with(comps[i], x, group_size)
        if ef:
            # this rank's dequantized contribution to the sum; what the
            # wire dropped is carried to the next step
            new_res[i] = (x - comps[i].decompress(c, ctx)).astype(
                residuals[i].dtype)
        compressed.append(c)
        ctxs.append(ctx)

    if plan is None:
        plan = FusionPlan(compressed, threshold_bytes)
    elif {i for b in plan.buckets for i in b} != set(range(len(compressed))):
        # exact coverage both ways: a stale plan (model gained or lost a
        # parameter since it was built) must fail loudly, not silently
        # return None in place of the uncovered gradients
        raise ValueError(
            f"fusion plan covers {sum(len(b) for b in plan.buckets)} "
            f"tensors but the call passed {len(compressed)}")
    out: List[Any] = [None] * len(tensors)

    def reduce(flat):
        with jax.named_scope("reduce"):
            return _reduce_flat(flat, op=op, axes=axes, groups=groups,
                                group_size=group_size)

    # hvd_bucket_<k>/{pack,reduce,unpack}: the bucket's number on every
    # op's metadata, so a device trace puts each collective and each copy
    # down to a bucket, and FusionPlan.describe puts the bucket down to
    # its tensors (docs/profiling.md)
    for k, bucket in enumerate(plan.buckets):
        with jax.named_scope(bucket_scope(k)):
            if len(bucket) == 1:
                i = bucket[0]
                out[i] = comps[i].decompress(reduce(compressed[i]), ctxs[i])
                continue
            with jax.named_scope("pack"):
                fused = jnp.concatenate(
                    [compressed[i].reshape(-1) for i in bucket])
            red = reduce(fused)
            with jax.named_scope("unpack"):
                offset = 0
                for i in bucket:
                    n = compressed[i].size
                    piece = lax.dynamic_slice_in_dim(red, offset, n).reshape(
                        compressed[i].shape
                    )
                    out[i] = comps[i].decompress(piece, ctxs[i])
                    offset += n
    if new_res is not None:
        return out, new_res
    return out


def allreduce_pytree(
    tree,
    *,
    op: str = Average,
    compression=Compression.none,
    process_set=None,
    threshold_bytes: Optional[int] = None,
    sparse_as_dense: bool = False,
    named_buckets: Optional[Sequence[Sequence[str]]] = None,
    bucket_compression: Optional[Sequence[Optional[str]]] = None,
    residual=None,
):
    """Fused allreduce over every array leaf of a pytree (gradients).

    ``IndexedSlices`` leaves take the sparse allgather path (reference
    tensorflow/__init__.py:75-90) unless ``sparse_as_dense`` (reference
    DistributedOptimizer option) densifies them first.

    ``named_buckets`` applies an explicit profile-guided fusion plan
    (lists of tensor names in dispatch order, the replay plan payload's
    vocabulary) matched against the tree's slash-joined leaf paths —
    see :meth:`FusionPlan.from_named_buckets` for the matching rules.
    ``bucket_compression`` (registry names aligned with
    ``named_buckets``) selects a wire format per bucket — the
    profile-guided compression decision (docs/compression.md).

    ``residual`` (a pytree shaped like ``tree``) switches on error
    feedback: the call reduces ``tree + residual`` and returns
    ``(reduced, new_residual)``; the caller owns the residual state
    (``TrainState.residual``, ``DistributedOptimizer`` state).  Sparse
    leaves keep their residual untouched (the allgather path is
    exact)."""
    from .sparse import (
        allreduce_indexed_slices, is_indexed_slices, to_dense,
    )

    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=is_indexed_slices
    )
    res_leaves = None
    if residual is not None:
        res_leaves = jax.tree_util.tree_flatten(
            residual, is_leaf=is_indexed_slices)[0]
        if len(res_leaves) != len(leaves):
            raise ValueError(
                "error-feedback residual pytree does not match the "
                f"gradient pytree ({len(res_leaves)} vs {len(leaves)} "
                "leaves) — initialize it with ErrorFeedback.init_state")
    names = tree_leaf_names(tree, is_leaf=is_indexed_slices) \
        if named_buckets else [""] * len(leaves)
    dense_idx = []
    dense_leaves = []
    dense_names = []
    dense_res = [] if res_leaves is not None else None
    out: list = [None] * len(leaves)
    res_out: list = list(res_leaves) if res_leaves is not None else []
    for i, leaf in enumerate(leaves):
        if is_indexed_slices(leaf):
            if sparse_as_dense:
                dense_idx.append(i)
                dense_leaves.append(to_dense(leaf))
                dense_names.append(names[i])
                if dense_res is not None:
                    # sparse residuals are dense zero trees; EF on the
                    # densified form is well defined
                    dense_res.append(res_leaves[i])
            else:
                out[i] = allreduce_indexed_slices(
                    leaf, op=op, process_set=process_set
                )
        else:
            dense_idx.append(i)
            dense_leaves.append(leaf)
            dense_names.append(names[i])
            if dense_res is not None:
                dense_res.append(res_leaves[i])
    plan = FusionPlan.from_named_buckets(
        dense_leaves, dense_names, named_buckets,
        bucket_compression=bucket_compression) if named_buckets else None
    reduced = fused_allreduce(
        dense_leaves, op=op, compression=compression,
        process_set=process_set, threshold_bytes=threshold_bytes,
        plan=plan, residuals=dense_res,
    )
    if dense_res is not None:
        reduced, new_dense_res = reduced
        for i, r in zip(dense_idx, new_dense_res):
            res_out[i] = r
    for i, r in zip(dense_idx, reduced):
        out[i] = r
    result = jax.tree_util.tree_unflatten(treedef, out)
    if residual is not None:
        return result, jax.tree_util.tree_unflatten(treedef, res_out)
    return result
