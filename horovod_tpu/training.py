"""High-level data-parallel training step builder.

The glue the reference spreads across DistributedOptimizer +
BroadcastGlobalVariablesHook + the example boilerplate (reference
examples/tensorflow2_synthetic_benchmark.py:72-97), packaged as one
TPU-native entry: build a jitted SPMD train step where the global batch is
sharded across ranks, parameters are replicated, and gradients flow through
the fused allreduce.  What the backward pass recomputes is the model's
decision, not the step's: a model wraps what it chooses to in ``nn.remat``
(as the decoders do for each layer through ``models/recompute.recomputed``,
which keeps the kernels' residuals and what else fits the device).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import core
from .core import Average
from .elastic import faults as _faults
from .elastic import heartbeat as _heartbeat
from .ops.compression import Compression, ErrorFeedback
from .ops.fusion import allreduce_pytree
from .spmd import spmd


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    model_state: Any  # mutable collections (e.g. batch_stats); may be {}
    step: jnp.ndarray
    #: error-feedback residual pytree (docs/compression.md) — ``()`` (no
    #: leaves) when compression is stateless.  Living in the state, it
    #: is checkpointed and elastic-rebuilt with params/opt_state.
    residual: Any = ()


class TrailingLossFetcher:
    """The async-host-pipeline loss fetch.

    ``push(loss)`` is called with every dispatched step's loss handle;
    every ``every`` steps ONE handle is retained, and the retained
    handle from the PREVIOUS cadence — by then ``every`` dispatches
    old, long since complete — is fetched.  The fetch therefore never
    drains the dispatch pipeline the way a per-step ``device_get``
    does; the freshest fetched value is ``.value`` (a float,
    ``every``..2×``every`` steps behind) and is exported as the
    ``hvd_train_loss`` gauge.  ``every <= 0`` disables entirely."""

    def __init__(self, every: int):
        self.every = max(int(every), 0)
        self._pending: list = []
        self._n = 0
        self.value: Optional[float] = None
        self.step: Optional[int] = None

    def push(self, loss) -> None:
        if self.every <= 0:
            return
        self._n += 1
        if self._n % self.every:
            return
        self._pending.append((self._n, loss))
        if len(self._pending) > 1:
            self._fetch(*self._pending.pop(0))

    def _fetch(self, n, loss) -> None:
        import numpy as np

        from .timeline.timeline import host_span

        with host_span("loss_fetch", step_num=self._n, fetched_step=n):
            self.value = float(np.asarray(jax.device_get(loss)))
        self.step = n
        from . import metrics

        if metrics.on():
            metrics.TRAIN_LOSS.set(self.value)

    def flush(self) -> Optional[float]:
        """Drain every retained handle (end-of-training); returns the
        final fetched value."""
        while self._pending:
            self._fetch(*self._pending.pop(0))
        return self.value


def scan_steps(step_fn: Callable, k: int) -> Callable:
    """Compile ``k`` optimizer steps into one program via ``lax.scan``
    (amortizes per-step host dispatch; the ResNet and transformer
    benches share it).  ``step_fn(carry,
    *args) -> (carry, loss)``; the returned fn has the same signature and
    yields the LAST step's loss.  ``k <= 1``: identity."""
    if k <= 1:
        return step_fn

    def scanned(carry, *args):
        def body(c, _):
            return step_fn(c, *args)

        carry, losses = jax.lax.scan(body, carry, None, length=k)
        return carry, losses[-1]

    return scanned


def make_train_step(
    *,
    apply_fn: Callable,
    loss_fn: Callable,
    optimizer,
    op: str = Average,
    compression=None,
    has_batch_stats: bool = False,
    threshold_bytes: Optional[int] = None,
    donate: bool = True,
    hierarchical: bool = False,
    two_level: Optional[bool] = None,
    autotune: Optional[bool] = None,
    autotune_log_file: Optional[str] = None,
    profile_guided: Optional[bool] = None,
    in_graph_steps: int = 1,
    loss_fetch_steps: Optional[int] = None,
):
    """Returns ``step(state, batch, labels) -> (state, loss)`` compiled SPMD
    over the global mesh.  ``batch`` and ``labels`` are arrays or pytrees of
    arrays (a model that takes several arrays a row): every leaf is sharded
    across ranks on dim 0.

    * ``apply_fn(variables, x, train=True, **mutable_kw)`` — flax-style.
    * ``loss_fn(logits, labels) -> scalar`` (per-rank mean).
    * gradients are bucket-fused and allreduced with ``op``/``compression``;
      the loss is also averaged across ranks for reporting (matching
      MetricAverageCallback semantics, reference _keras/callbacks.py:46-60).
    * ``compression`` (default: the ``HVD_COMPRESSION`` /
      ``HVD_COMPRESSION_ERROR_FEEDBACK`` env knobs, docs/compression.md)
      selects the wire format; an
      :class:`~horovod_tpu.ops.compression.ErrorFeedback` instance
      threads the quantization residual through ``TrainState.residual``
      (initialize it via ``init_train_state(..., compression=...)``;
      with ``in_graph_steps == 1`` an uninitialized residual is created
      lazily at first trace).  A residual-norm convergence guard
      (``HVD_COMPRESSION_GUARD_STEPS``/``_FACTOR``) samples the
      ``hvd_compression_residual_norm`` gauge and, if the residual
      diverges, falls back to uncompressed allreduce
      (``hvd_compression_fallbacks_total``) — training continues.
    * ``two_level`` (default: ``HVD_TWO_LEVEL_ALLREDUCE``) reduces each
      gradient with the compressed two-level path — ICI reduce-scatter,
      ``compression`` on the cross/DCN stage only
      (parallel/hierarchical.py ``two_level_allreduce``).
    * ``autotune`` (default: the HVD_AUTOTUNE env, reference run.py:490-521
      --autotune) drives a live ParameterManager: it scores each step as
      bytes/sec, moves the fusion-threshold / hierarchical knobs, and
      re-jits the step when they change — the compiled-world analog of the
      reference's "new parameters take effect next cycle"
      (parameter_manager.cc Update/Tune).  The returned function exposes
      the manager as ``step.parameter_manager``.
    * ``profile_guided`` (default: the HVD_AUTOTUNE_PROFILE_GUIDED env)
      closes the replay→autotune loop (docs/autotune.md): every
      ``HVD_AUTOTUNE_WINDOW_STEPS`` steps the job's own trace window is
      stitched + replayed, the winning what-if becomes an explicit
      fusion-bucket plan applied through the same re-jit seam, and the
      next window verifies realized against predicted speedup (rollback
      past the guard band).  Exposed as ``step.profile_guided_tuner``.
      The GP prior is warm-started from the α–β cost model
      (HVD_AUTOTUNE_WARM_START=0 disables).
    * ``in_graph_steps > 1`` compiles a ``lax.scan`` of that many
      optimizer steps over the SAME batch into one program, so host
      dispatch is amortized away (the synthetic-benchmark mode: the
      reference's timed inner loop also re-feeds one synthetic batch,
      examples/tensorflow2_synthetic_benchmark.py:72-97).  Real data
      pipelines keep the default 1.
    * ``loss_fetch_steps`` (default ``HVD_LOSS_FETCH_STEPS``, 16)
      fetches loss/metrics through a TRAILING async handle every N
      steps (``step.loss_fetcher.value``) instead of a per-step
      ``device_get`` — the dispatch pipeline stays deep; the forced
      per-step sync survives only inside the tuners' measuring
      windows, which need it for honest timing.  0 disables.
    """
    from .ops import collectives
    from .parallel.hierarchical import (
        hierarchical_allreduce, two_level_allreduce, use_two_level_default,
    )
    from .utils import env as env_util
    from .utils.logging import get_logger

    log = get_logger(__name__)

    if compression is None:
        from .ops.compression import from_env as _compression_from_env

        compression = _compression_from_env()
    if two_level is None:
        two_level = use_two_level_default()

    if loss_fetch_steps is None:
        loss_fetch_steps = env_util.get_int(
            env_util.HVD_LOSS_FETCH_STEPS,
            env_util.DEFAULT_LOSS_FETCH_STEPS)
    fetcher = TrailingLossFetcher(loss_fetch_steps)

    def _build(threshold_b, hier, named_buckets=None, comp=None,
               bucket_compression=None, tlvl=None):
        comp = comp if comp is not None else compression
        tlvl = two_level if tlvl is None else tlvl
        # error feedback threads TrainState.residual — only on the fused
        # pytree path (the per-leaf hier/two-level paths carry their own
        # compression semantics; two_level_allreduce documents why EF
        # degrades there)
        plan_comp = bucket_compression is not None \
            and any(bucket_compression) \
            and env_util.get_bool(
                env_util.HVD_COMPRESSION_ERROR_FEEDBACK, True) \
            and in_graph_steps <= 1
        ef = (isinstance(comp, ErrorFeedback) or plan_comp) \
            and not hier and not tlvl

        # The step's blocks as helpers for their named scopes:
        # jax.named_scope threads hvd_forward / hvd_loss /
        # hvd_grad_allreduce / hvd_optimizer_update into HLO op metadata,
        # so any jax.profiler capture attributes device time to them.
        def _compute_loss(params, model_state, x, y):
            with jax.named_scope("hvd_forward"):
                variables = {"params": params, **model_state}
                if has_batch_stats:
                    logits, updates = apply_fn(
                        variables, x, train=True, mutable=["batch_stats"]
                    )
                else:
                    logits, updates = apply_fn(variables, x), {}
                with jax.named_scope("hvd_loss"):
                    return loss_fn(logits, y), updates

        def _reduce_grads(grads, residual):
            with jax.named_scope("hvd_grad_allreduce"):
                if tlvl:
                    grads = jax.tree_util.tree_map(
                        lambda g: two_level_allreduce(g, op=op,
                                                      compression=comp),
                        grads,
                    )
                elif hier:
                    grads = jax.tree_util.tree_map(
                        lambda g: hierarchical_allreduce(g, op=op), grads
                    )
                elif ef:
                    if not jax.tree_util.tree_leaves(residual):
                        if in_graph_steps > 1:
                            raise ValueError(
                                "error-feedback compression with "
                                "in_graph_steps > 1 needs an initialized "
                                "residual (lax.scan carries must keep one "
                                "structure) — build the state with "
                                "init_train_state(..., compression=...)")
                        # lazy init at trace time: the first compiled step
                        # returns the full residual structure, later calls
                        # carry it (one extra re-trace, no extra step work)
                        residual = jax.tree_util.tree_map(
                            jnp.zeros_like, grads)
                    grads, residual = allreduce_pytree(
                        grads, op=op, compression=comp,
                        threshold_bytes=threshold_b,
                        named_buckets=named_buckets,
                        bucket_compression=bucket_compression,
                        residual=residual,
                    )
                else:
                    grads = allreduce_pytree(
                        grads, op=op, compression=comp,
                        threshold_bytes=threshold_b,
                        named_buckets=named_buckets,
                        bucket_compression=bucket_compression,
                    )
            return grads, residual

        def _apply_update(state, grads, new_model_state, residual):
            with jax.named_scope("hvd_optimizer_update"):
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                import optax

                params = optax.apply_updates(state.params, updates)
            return TrainState(params, opt_state, new_model_state,
                              state.step + 1, residual)

        def per_rank_step(state: TrainState, x, y):
            (loss, new_model_state), grads = jax.value_and_grad(
                lambda p: _compute_loss(p, state.model_state, x, y),
                has_aux=True,
            )(state.params)
            grads, residual = _reduce_grads(grads, state.residual)
            with jax.named_scope("hvd_loss_allreduce"):
                loss = collectives.allreduce(loss, op=Average)
            return (
                _apply_update(state, grads, new_model_state, residual),
                loss,
            )

        per_rank_entry = scan_steps(per_rank_step, in_graph_steps)

        # params/opt_state replicated; batch sharded across ranks on dim 0.
        state_spec = TrainState(
            params=P(), opt_state=P(), model_state=P(), step=P(),
            residual=P(),
        )
        fn = spmd(
            per_rank_entry,
            in_specs=(state_spec, P(core.AXIS), P(core.AXIS)),
            out_specs=(state_spec, P()),
            donate_argnums=(0,) if donate else (),
        )

        return fn, ef

    if autotune is None:
        autotune = env_util.get_bool(env_util.HVD_AUTOTUNE)

    from . import metrics
    from .metrics import timeseries as _timeseries
    from .timeline.timeline import host_span, timeline

    pm = None
    box = {}
    #: host dispatches so far: the ``step_num`` every host span of one
    #: step carries, and the step the cadence series and events name
    step_count = [0]

    def _rebuild(threshold_b, hier, plan=None, reason="first build"):
        """(Re)compile the SPMD step and remember the knobs + the core
        mesh epoch it was built against, so a later elastic membership
        change (core.reinit bumps the epoch and swaps the mesh) can
        rebuild with the same knobs.  ``plan`` is a profile-guided
        FusionPlanSpec: its explicit bucket vector overrides the scalar
        threshold and its per-bucket ``compression`` names override the
        wire format (optim/profile_guided.py).  ``reason`` (first build |
        epoch | plan | guard) is what the ``hvd_rebuild`` host span says
        of a rebuild that builds a new program."""
        named = plan.buckets if plan is not None and plan.buckets \
            else None
        bucket_comp = getattr(plan, "compression", None) \
            if plan is not None else None
        if bucket_comp is not None and box.get("guard_tripped"):
            # the convergence guard already condemned compression in
            # this job; later plans keep their fusion layout but ship
            # uncompressed
            bucket_comp = None
        if bucket_comp is not None and any(bucket_comp) \
                and in_graph_steps > 1:
            # plan compression rides error feedback, and a lax.scan
            # carry can't grow a residual mid-job — keep the fusion
            # layout, ship it uncompressed rather than silently
            # quantizing without the residual carry
            log.info("profile-guided plan carries per-bucket compression "
                     "but in_graph_steps > 1 has no residual carry — "
                     "applying the fusion layout uncompressed")
            bucket_comp = None
        comp = box.get("compression", compression)
        # An explicit bucket plan owns the comm layout: the hierarchical
        # path reduces per leaf and would silently drop named_buckets
        # while the tuner reports the plan applied.  box keeps the
        # original hier so rollback (plan=None) restores it.
        with host_span("rebuild", reason=reason, step_num=step_count[0]):
            fn, ef = _build(
                threshold_b, hier and named is None, named,
                comp, bucket_comp, two_level and named is None)
        box.update(
            fn=fn, threshold=threshold_b, hier=hier, plan=plan,
            ef_active=ef, compression=comp,
            core_epoch=core._require_init().epoch,
            # a new jitted function: its first call compiles, which
            # _count_compiles sees as the cache growing from nothing
            cache_size=0,
        )

    if autotune:
        from .optim.autotune import ParameterManager, TunableParams

        initial = TunableParams(
            fusion_threshold_bytes=threshold_bytes
            or env_util.fusion_threshold_bytes(),
            hierarchical_allreduce=hierarchical,
        )
        pm = ParameterManager(
            enabled=True, log_file=autotune_log_file, initial=initial,
        )
        pm.on_update = lambda p: _rebuild(p.fusion_threshold_bytes,
                                          p.hierarchical_allreduce,
                                          p.fusion_plan,
                                          reason="plan")
        _rebuild(initial.fusion_threshold_bytes,
                 initial.hierarchical_allreduce)
    else:
        _rebuild(threshold_bytes, hierarchical)

    import time as _time

    # Step-cadence metrics: blocking on the result every step would
    # serialize the async dispatch pipeline (the very thing the compiled
    # plane buys), so the histogram records the interval between
    # successive dispatches — in steady state the host is throttled by
    # the device queue, making dispatch-to-dispatch time the real step
    # time without a single synchronization.
    last_dispatch = [0.0]

    def _record_step_metrics(x):
        now = _time.perf_counter()
        if last_dispatch[0]:
            dt = now - last_dispatch[0]
            metrics.STEP_SECONDS.observe(dt)
            # always-on cadence history (one ring-buffer append): the
            # watchdog's step-time and straggler detectors read this
            if _timeseries.on():
                _timeseries.record(_timeseries.STEP_SECONDS, dt,
                                   step=step_count[0])
        last_dispatch[0] = now
        metrics.STEPS_TOTAL.inc(max(in_graph_steps, 1))
        try:
            # a batch of several arrays (a tuple, a dict) counts its rows
            # once: every leaf is sharded on dim 0 alike
            rows = jax.tree_util.tree_leaves(x)[0].shape[0]
            metrics.SAMPLES_TOTAL.inc(int(rows) * max(in_graph_steps, 1))
        except (AttributeError, IndexError, TypeError):
            pass  # batch without a leading dim: samples stay uncounted

    # Error-feedback convergence guard (docs/compression.md): every
    # HVD_COMPRESSION_GUARD_STEPS steps read the residual norm off the
    # returned state (one device sync per guard window — not per step),
    # export the gauge, and fall back to uncompressed allreduce when the
    # norm diverges.  The residual is replicated and the guard logic is
    # deterministic host float math, so every process trips identically.
    guard_steps = env_util.get_int(env_util.HVD_COMPRESSION_GUARD_STEPS,
                                   env_util.DEFAULT_COMPRESSION_GUARD_STEPS)
    guard_box = {"n": 0, "guard": None}

    def _maybe_guard(new_state):
        if not box.get("ef_active") or guard_steps <= 0:
            return
        guard_box["n"] += 1
        if guard_box["n"] % guard_steps:
            return
        from .ops.compression import ErrorFeedbackGuard, residual_norm

        norm = residual_norm(new_state.residual)
        if metrics.on():
            metrics.COMPRESSION_RESIDUAL_NORM.set(norm)
        if _timeseries.on():
            _timeseries.record(_timeseries.RESIDUAL_NORM_SERIES, norm,
                               step=step_count[0])
        if guard_box["guard"] is None:
            guard_box["guard"] = ErrorFeedbackGuard()
        if not guard_box["guard"].observe(norm):
            return
        log.warning(
            "error-feedback residual norm %.3g diverged past %gx its "
            "baseline — falling back to uncompressed allreduce; the "
            "diverged residual is DISCARDED (it is garbage by "
            "construction) and stays frozen in TrainState.residual",
            norm, guard_box["guard"].factor)
        if metrics.on():
            metrics.COMPRESSION_FALLBACKS.inc()
        try:
            from .observe import events as events_mod

            events_mod.record_event(
                "compression.fallback", severity="warning",
                payload={"residual_norm": float(norm),
                         "factor": guard_box["guard"].factor,
                         "step": step_count[0]})
        except Exception:  # noqa: BLE001 — recording is best-effort
            pass
        box["guard_tripped"] = True
        box["compression"] = Compression.none
        plan = box.get("plan")
        if plan is not None and getattr(plan, "compression", None):
            plan = dataclasses_replace_plan(plan)
        _rebuild(box["threshold"], box["hier"], plan, reason="guard")

    def dataclasses_replace_plan(plan):
        """The applied plan minus its compression decision — fusion
        layout survives the fall-back, wire format does not."""
        import dataclasses as _dc

        try:
            return _dc.replace(plan, compression=None)
        except TypeError:
            return plan

    def _count_compiles(n, x, y):
        """``hvd_step_compiles_total``: the jitted step's cache grew
        across the call — a rebuild's first call, or a silent retrace on
        a new batch shape.  One integer compare a step; the event names
        the step and the shapes, so "which step recompiled" has an
        answer."""
        size = box["fn"]._cache_size()
        if size == box["cache_size"]:
            return
        box["cache_size"] = size
        if metrics.on():
            metrics.STEP_COMPILES.inc()
        try:
            from .observe import events as events_mod

            events_mod.record_event(
                "step.compile", payload={
                    "step": n, "programs": size,
                    "args": [f"{a.dtype}{list(a.shape)}" for a in
                             jax.tree_util.tree_leaves((x, y))]})
        except Exception:  # noqa: BLE001 — recording is best-effort
            pass

    def _invoke(state, x, y, _under_trace=None):
        # Skipped while under a jax trace (e.g. Recorder.record_step_function
        # running make_jaxpr) so abstract evaluation doesn't consume window
        # steps or emit phantom spans.  The autotuned wrapper passes its
        # already-computed verdict so big pytrees are scanned once.
        under_trace = _under_trace if _under_trace is not None else any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree_util.tree_leaves((state, x, y))
        )
        if under_trace:
            return box["fn"](state, x, y)
        step_count[0] += 1
        n = step_count[0]
        if timeline.active:
            # advances the trace window (reference
            # BYTEPS_TRACE_START/END_STEP semantics) before the step's
            # spans ask whether they are inside it
            timeline.record_step(owner="train_step")
            timeline.mark_cycle_start()
        # Host-side step record (docs/profiling.md): hvd_step is the
        # profiler's step (device ops are grouped under its number) and
        # the timeline's STEP span; the spans beneath it carry the same
        # step_num.  On the compiled path collective timing lives inside
        # XLA; these record the per-step cadence the tracer windows key on.
        with host_span("step", annotation=jax.profiler.StepTraceAnnotation,
                       step_num=n):
            with host_span("preflight", step_num=n):
                # Failure-domain seam (docs/fault_tolerance.md): a
                # coordinated abort raises HorovodAbortError here — before
                # this rank dispatches a step its dead peer will never
                # join — and the HVD_FAULT_SPEC harness injects its
                # step-seam faults.
                _heartbeat.maybe_raise_abort()
                _faults.on_step()
                # Elastic rebuild seam: after a membership epoch the mesh
                # is new (core.reinit) and the compiled step — shard_map
                # captured the old mesh at build — must re-trace over it.
                if box["core_epoch"] != core._require_init().epoch:
                    _rebuild(box["threshold"], box["hier"], box.get("plan"),
                             reason="epoch")
            if metrics.on():
                _record_step_metrics(x)
            with host_span("call", step_num=n):
                result = box["fn"](state, x, y)
            _count_compiles(n, x, y)
            with host_span("guard", step_num=n):
                _maybe_guard(result[0])
            fetcher.push(result[1])
        return result

    # Profile-guided loop (optim/profile_guided.py): analyze the job's
    # own trace window, apply the winning bucket plan through the same
    # rebuild seam, verify realized-vs-predicted next window.
    if profile_guided is None:
        profile_guided = env_util.get_bool(
            env_util.HVD_AUTOTUNE_PROFILE_GUIDED)
    tuner = None
    if profile_guided:
        from .optim.profile_guided import tuner_from_env

        trace_dir = env_util.get_str(env_util.HVD_TIMELINE) or \
            env_util.get_str(env_util.HVD_TRACE_DIR)

        def _analyze():
            if not trace_dir:
                return None
            from .timeline.replay import analyze

            # latest step only: SPMD steps share one DAG shape, and a
            # per-window caller must not replay the whole accumulated
            # trace history (it grows with the job)
            return analyze(trace_dir, last_steps=1).summary

        def _apply_plan(plan):
            if pm is not None:
                if plan is not None:
                    pm.apply_plan(plan)
                else:
                    pm.clear_plan()
            else:
                _rebuild(box["threshold"], box["hier"], plan, reason="plan")

        tuner = tuner_from_env(_analyze, _apply_plan)
        if not trace_dir:
            from .utils.logging import get_logger

            get_logger(__name__).warning(
                "profile-guided tuning enabled without HVD_TIMELINE/"
                "HVD_TRACE_DIR: no trace window to analyze, the tuner "
                "will idle in its baseline phase")

    if pm is None and tuner is None:
        _invoke.loss_fetcher = fetcher
        return _invoke

    warm_start = env_util.get_bool(env_util.HVD_AUTOTUNE_WARM_START, True)
    pg_last = [0.0]

    def step_autotuned(state, x, y):
        under_trace = any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree_util.tree_leaves((state, x, y))
        )
        if tuner is not None and tuner.active and not under_trace:
            # dispatch-to-dispatch interval: real step time in steady
            # state with zero added synchronization (same honesty
            # argument as hvd_step_seconds)
            now = _time.perf_counter()
            if pg_last[0]:
                tuner.on_step(now - pg_last[0])
            pg_last[0] = now
        if pm is None or pm.frozen:
            state, loss = _invoke(state, x, y, _under_trace=under_trace)
            if tuner is not None and tuner.measuring and not under_trace:
                # honest timing while the PG loop measures: the GP path
                # below blocks on the result every step, so without this
                # the baseline window (GP active) would measure serialized
                # step time but the verify window (apply_plan froze the
                # GP) pipelined dispatch time — a "speedup" any plan
                # would pass.  Gated on the MEASURING phases: a steady
                # (plan-pinned) window only counts steps and must keep
                # the async dispatch pipeline the plan bought.
                jax.device_get(loss)
            return state, loss
        if "grad_bytes" not in box:
            import math

            # per-call allreduce volume = the gradient pytree's bytes,
            # once per scanned in-graph step
            box["grad_bytes"] = float(sum(
                math.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(state.params)
            )) * max(in_graph_steps, 1)
        if warm_start and not under_trace and not box.get("warm_started"):
            # seed the GP with the α–β model's predicted scores so
            # exploration starts near the simulator's optimum.  Gated on
            # its own flag, not the grad_bytes cache: the first call is
            # often a jax trace (Recorder.record_step_function), which
            # fills grad_bytes from tracer leaves but must not burn the
            # only warm-start opportunity.
            box["warm_started"] = True
            from .optim.profile_guided import warm_start_manager

            warm_start_manager(pm, box["grad_bytes"])
        t0 = _time.perf_counter()
        state, loss = _invoke(state, x, y, _under_trace=under_trace)
        # honest timing while tuning: fetching the loss forces the whole
        # step chain to complete
        jax.device_get(loss)
        dt = _time.perf_counter() - t0
        if core.process_size() > 1:
            # Synchronize the measurement instead of the decision: every
            # process scores the same averaged step time, and the
            # deterministic tuner (fixed seed) then moves every process's
            # knobs identically — the analog of the reference's
            # SynchronizeParameters broadcast (controller.cc:33-47).
            import numpy as _np

            from . import eager

            dt = float(eager.process_allreduce(
                _np.asarray([dt], _np.float64), op=Average,
                name="autotune.step_time",
            )[0])
        pm.record_step(box["grad_bytes"], dt)
        return state, loss

    step_autotuned.parameter_manager = pm
    step_autotuned.profile_guided_tuner = tuner
    step_autotuned.loss_fetcher = fetcher
    return step_autotuned


def init_train_state(model, optimizer, sample_input, *, rngs=None,
                    has_batch_stats: bool = False,
                    compression=None) -> TrainState:
    """Initialize replicated TrainState on the mesh (rank-0-initializes +
    broadcast in Horovod terms; under a single controller, replication by
    construction plus hvd.broadcast_parameters for multi-host).

    Pass the same ``compression`` the train step uses: an
    :class:`~horovod_tpu.ops.compression.ErrorFeedback` wrapper gets its
    zero residual pytree here (required for ``in_graph_steps > 1``,
    where ``lax.scan`` needs the carry structure fixed up front)."""
    import numpy as np

    rngs = rngs if rngs is not None else jax.random.PRNGKey(0)
    variables = model.init(rngs, sample_input)
    params = variables["params"]
    model_state = {
        k: v for k, v in variables.items() if k != "params"
    } if has_batch_stats else {}
    opt_state = optimizer.init(params)
    residual = ErrorFeedback.init_state(params) \
        if isinstance(compression, ErrorFeedback) else ()
    state = TrainState(
        params=params, opt_state=opt_state, model_state=model_state,
        step=jnp.zeros((), jnp.int32), residual=residual,
    )
    # Replicate across the mesh explicitly so the donated buffers live on
    # every device before step 1 (no lazy broadcast inside the hot loop).
    mesh = core.mesh()
    repl = NamedSharding(mesh, P())
    state = jax.device_put(state, repl)
    from .optim.distributed import broadcast_parameters

    return broadcast_parameters(state)


def shard_batch(batch):
    """Place a host batch so dim 0 is split across ranks (the per-rank
    shards), without a host-side reshape."""
    mesh = core.mesh()
    return jax.device_put(batch, NamedSharding(mesh, P(core.AXIS)))
