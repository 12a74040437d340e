"""Recorder: model-DAG / tensor-shape / gradient-manifest dumps + step hook.

Re-design of the fork's auto-profiling recorders (the byteprofile/dPRO
layer): TF ``Recorder``/``TimelineHook`` (reference
horovod/tensorflow/recorder.py:339-521 dumps per-step Chrome traces,
partition GraphDefs, a networkx DAG as ``dag.gml``, ``tensor_shapes.json``,
``metadata.json``, ``gradient_name_list.json``; :165-193 gradient name
registration) and MXNet ``Recorder`` (reference mxnet/recorder.py:187-302,
DAG from ``symbol.debug_str()``).

TPU-native sources replace framework graph introspection:

* the **DAG** comes from the step function's jaxpr (the XLA-input graph —
  strictly more faithful than TF's partition graphs, since it is exactly
  what gets compiled);
* **tensor shapes** come from jaxpr avals;
* **gradient names** come from pytree paths;
* **per-step device traces** come from ``jax.profiler`` (XLA's own
  profiler), started/stopped by the step window — replacing the patched
  NCCL name-tagging (reference nccl_operations.cc:149-152): collective HLOs
  in the XLA trace already carry source metadata.

Outputs land in ``<dir>/<rank>/`` next to the timeline's ``comm.json``
(fork layout, reference timeline.cc:216).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from .. import core
from ..utils import env as env_util
from ..utils.logging import get_logger
from .timeline import timeline

log = get_logger(__name__)


def _gml_escape(s: str) -> str:
    return s.replace('"', "'")


def jaxpr_dag(closed_jaxpr) -> tuple:
    """(nodes, edges) from a ClosedJaxpr: nodes are primitives/inputs/
    outputs with shape/dtype attributes; edges follow var def→use."""
    jaxpr = closed_jaxpr.jaxpr
    nodes: List[Dict[str, Any]] = []
    edges: List[tuple] = []
    var_producer: Dict[Any, int] = {}

    def add_node(label: str, kind: str, aval=None) -> int:
        nid = len(nodes)
        node = {"id": nid, "label": label, "kind": kind}
        if aval is not None and hasattr(aval, "shape"):
            node["shape"] = list(aval.shape)
            node["dtype"] = str(getattr(aval, "dtype", ""))
        nodes.append(node)
        return nid

    for i, v in enumerate(jaxpr.invars):
        nid = add_node(f"input{i}", "input", v.aval)
        var_producer[v] = nid

    for eqn in jaxpr.eqns:
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        label = str(eqn.primitive.name)
        nid = add_node(label, "op", out_aval)
        for v in eqn.invars:
            if hasattr(v, "aval") and v in var_producer:
                edges.append((var_producer[v], nid))
        for v in eqn.outvars:
            var_producer[v] = nid

    for i, v in enumerate(jaxpr.outvars):
        nid = add_node(f"output{i}", "output",
                       v.aval if hasattr(v, "aval") else None)
        if v in var_producer:
            edges.append((var_producer[v], nid))
    return nodes, edges


def write_gml(nodes: Sequence[dict], edges: Sequence[tuple], path: str) -> None:
    """Minimal GML writer (the reference writes dag.gml via networkx,
    recorder.py:516-521; format kept compatible with nx.read_gml)."""
    with open(path, "w") as f:
        f.write("graph [\n  directed 1\n")
        for n in nodes:
            f.write(f'  node [\n    id {n["id"]}\n'
                    f'    label "{_gml_escape(str(n["label"]))}"\n')
            if "shape" in n:
                f.write(f'    shape "{tuple(n["shape"])}"\n')
            if "dtype" in n:
                f.write(f'    dtype "{n["dtype"]}"\n')
            f.write(f'    kind "{n["kind"]}"\n  ]\n')
        for s, t in edges:
            f.write(f"  edge [\n    source {s}\n    target {t}\n  ]\n")
        f.write("]\n")


def structure_dag(names: Sequence[str]) -> tuple:
    """(nodes, edges) for the aggregation step's own dataflow —
    grad_i → allreduce_i → var_i.  The eager-binding fallback DAG when
    no traced graph is available (TF eager mode, the mxnet fake); same
    node vocabulary as ``jaxpr_dag`` so dag.gml consumers see one
    format."""
    nodes, edges = [], []
    for name in names:
        g = len(nodes)
        nodes.append({"id": g, "label": f"grad/{name}", "kind": "input"})
        a = len(nodes)
        nodes.append({"id": a, "label": f"allreduce/{name}", "kind": "op"})
        v = len(nodes)
        nodes.append({"id": v, "label": name, "kind": "output"})
        edges.extend([(g, a), (a, v)])
    return nodes, edges


def write_gradient_manifest(rec: "Recorder", names: Sequence[str],
                            shapes: Dict[str, list]) -> None:
    """gradient_name_list.json + tensor_shapes.json — the shared artifact
    format both eager bindings dump (reference recorder.py:176-193
    gradient name registration)."""
    with open(rec._path("gradient_name_list.json"), "w") as f:
        json.dump(list(names), f, indent=1)
    with open(rec._path("tensor_shapes.json"), "w") as f:
        json.dump(shapes, f, indent=1)


class Recorder:
    """Capture and dump the model/step structure.

    Usage (mirrors the reference's mandatory Recorder wiring in the fork's
    DistributedTrainer, mxnet/__init__.py:92-134)::

        rec = Recorder(trace_dir)           # or env HVD_TRACE_DIR
        rec.record_step_function(step, state, x, y)   # dag.gml + shapes
        rec.register_gradients(grads_pytree)          # gradient_name_list
        rec.dump_metadata(model="ResNet50", batch=64)
    """

    def __init__(self, trace_dir: Optional[str] = None,
                 rank: Optional[int] = None):
        trace_dir = trace_dir or env_util.get_str(env_util.HVD_TRACE_DIR) \
            or env_util.get_str(env_util.HVD_TIMELINE)
        self.enabled = bool(trace_dir) and env_util.get_bool(
            env_util.HVD_TRACE_ON, True
        )
        self.rank = rank if rank is not None else (
            core.process_rank() if core.is_initialized() else 0
        )
        self.dir = os.path.join(trace_dir, str(self.rank)) if trace_dir else None
        if self.enabled and self.dir:
            os.makedirs(self.dir, exist_ok=True)

    def _path(self, name: str) -> str:
        assert self.dir is not None
        return os.path.join(self.dir, name)

    def record_step_function(self, fn: Callable, *example_args,
                             **example_kwargs) -> None:
        """Trace ``fn`` to a jaxpr and dump dag.gml + tensor_shapes.json
        (reference recorder.py:339-521 equivalents)."""
        if not self.enabled:
            return
        closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
        nodes, edges = jaxpr_dag(closed)
        write_gml(nodes, edges, self._path("dag.gml"))
        shapes = {
            f'{n["label"]}.{n["id"]}': n["shape"]
            for n in nodes if "shape" in n
        }
        with open(self._path("tensor_shapes.json"), "w") as f:
            json.dump(shapes, f, indent=1)
        log.debug("recorder: dag.gml with %d nodes, %d edges",
                  len(nodes), len(edges))

    def register_gradients(self, grads: Any) -> None:
        """gradient_name_list.json from pytree paths (reference
        recorder.py:176-193 register_tensors / gradient name manifest).

        Also merges each gradient's shape into ``tensor_shapes.json`` and
        its dtype into ``tensor_dtypes.json``, keyed by manifest name —
        the byte counts the replay engine's what-if cost model
        (timeline/replay/stitcher.py) joins comm events against.

        And ``gradient_buckets.json``: the fused all-reduce's plan for
        these gradients (``FusionPlan.describe``: bucket number, leaf
        names, dtype, bytes), so ``hvd_bucket_<k>`` on an op of a device
        trace can be put down to tensors (docs/profiling.md)."""
        if not self.enabled:
            return
        from ..ops.fusion import FusionPlan, tree_leaf_names

        leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
        flat = [leaf for _, leaf in leaves]
        if flat and all(hasattr(leaf, "shape") for leaf in flat):
            with open(self._path("gradient_buckets.json"), "w") as f:
                json.dump(FusionPlan(flat).describe(
                    flat, tree_leaf_names(grads)), f, indent=1)
        paths = [
            "gradients/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                    for k in path)
            for path, _ in leaves
        ]
        with open(self._path("gradient_name_list.json"), "w") as f:
            json.dump(paths, f, indent=1)
        shapes: Dict[str, list] = {}
        dtypes: Dict[str, str] = {}
        for name, (_, leaf) in zip(paths, leaves):
            if hasattr(leaf, "shape"):
                shapes[name] = list(leaf.shape)
                dtypes[name] = str(getattr(leaf, "dtype", "float32"))
        if shapes:
            # merge, don't overwrite: record_step_function and earlier
            # register_gradients calls (second param group, elastic
            # rejoin) contribute keys too — losing a dtype silently
            # falls the stitcher back to the 4-byte default
            for name, payload in (("tensor_shapes.json", shapes),
                                  ("tensor_dtypes.json", dtypes)):
                path = self._path(name)
                if os.path.isfile(path):
                    with open(path) as f:
                        existing = json.load(f)
                    existing.update(payload)
                    payload = existing
                with open(path, "w") as f:
                    json.dump(payload, f, indent=1)

    def dump_metadata(self, **meta: Any) -> None:
        """metadata.json (reference recorder.py metadata dump: model name,
        dtypes, cluster shape...)."""
        if not self.enabled:
            return
        base = {
            "rank": self.rank,
            "size": core.size() if core.is_initialized() else 1,
            "local_size": core.local_size() if core.is_initialized() else 1,
            "platform": core._state.platform,
        }
        base.update(meta)
        with open(self._path("metadata.json"), "w") as f:
            json.dump(base, f, indent=1)


class TimelineHook:
    """Step-driven trace controller (reference tensorflow/recorder.py
    TimelineHook, a ProfilerHook subclass: collects traces only inside the
    [start_step, end_step] window).

    Wrap the training loop::

        hook = TimelineHook(recorder)
        for batch in data:
            with hook.step():
                state, loss = train_step(state, batch)
    """

    def __init__(self, recorder: Recorder,
                 start_step: Optional[int] = None,
                 end_step: Optional[int] = None,
                 xla_profile: bool = False):
        self.recorder = recorder
        self.start_step = start_step if start_step is not None else \
            env_util.get_int(env_util.HVD_TRACE_START_STEP, 0)
        self.end_step = end_step if end_step is not None else \
            env_util.get_int(env_util.HVD_TRACE_END_STEP, 1 << 62)
        self.xla_profile = xla_profile
        self._step = 0
        self._profiling = False
        if self.recorder.enabled:
            timeline.initialize(os.path.dirname(self.recorder.dir))

    def _in_window(self) -> bool:
        return self.start_step <= self._step <= self.end_step

    def step(self):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            self._step = timeline.record_step(owner="timeline_hook")
            enabled = self.recorder.enabled and self._in_window()
            if enabled and self.xla_profile and not self._profiling:
                jax.profiler.start_trace(self.recorder._path("xla_trace"))
                self._profiling = True
            with timeline.span(f"step_{self._step}", "STEP"):
                yield self._step
            if self._profiling and (
                not self._in_window() or self._step >= self.end_step
            ):
                jax.profiler.stop_trace()
                self._profiling = False

        return ctx()
