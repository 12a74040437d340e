"""Per-rank metrics plane.

The live counterpart of the timeline/ post-mortem traces: a process-wide
registry of counters/gauges/histograms (registry.py), the instrument
inventory every layer reports into (this module), and the pusher that
ships JSON snapshots to the launcher's rendezvous server for cross-rank
aggregation (push.py → run/http_server.py ``GET /metrics``).

Metric families (all prefixed ``hvd_``; the launcher injects a ``rank``
label when it aggregates):

==============================  =========  ==================================
name                            kind       meaning
==============================  =========  ==================================
hvd_eager_collective_calls_total counter   eager dispatches, by ``op``
hvd_eager_collective_bytes_total counter   per-rank payload bytes, by ``op``
hvd_eager_collective_seconds    histogram  dispatch wall time, by ``op``
hvd_negotiation_seconds         histogram  controller negotiate(), by ``op``
hvd_host_collective_calls_total counter    host-plane ops, by ``op``/``transport``
hvd_host_collective_bytes_total counter    host-plane bytes, by ``op``/``transport``
hvd_host_collective_seconds     histogram  host-plane wall time, by ``transport``
hvd_collectives_traced_total    counter    collectives emitted at trace time
hvd_collectives_traced_bytes_total counter traced payload bytes, by ``op``
hvd_flash_tiles_traced_total    counter    flash score tiles per traced kernel
                                           call, by ``kernel``/``kind``/``mask``
hvd_flash_grid_steps_traced_total counter  streamed grid steps per traced flash
                                           kernel call, by ``kernel``/``kind``/
                                           ``mask``
hvd_flash_kv_group_traced_total counter    traced flash kernel calls, by
                                           ``kernel``/``q_heads``/``kv_heads``
hvd_moe_layers_traced_total     counter    routed expert layers traced, by
                                           ``held``/``top_k``/``rule``/``groups``
hvd_mla_layers_traced_total     counter    latent-attention layers traced, by
                                           ``qk``/``v``/``latent``
hvd_ssm_layers_traced_total     counter    Mamba-2 state-space mixers traced, by
                                           ``heads``/``head_dim``/``state``/
                                           ``groups``/``chunk``
hvd_bd_layers_traced_total      counter    block-diffusion attention layers
                                           traced, by ``block``
hvd_step_seconds                histogram  train-step cadence (dispatch-to-
                                           dispatch interval — honest under
                                           async dispatch, see training.py)
hvd_steps_total                 counter    train steps dispatched
hvd_samples_total               counter    global samples dispatched
hvd_train_loss                  gauge      trailing async loss fetch (N
                                           steps old by construction —
                                           never a pipeline stall)
hvd_ring_ops_total              counter    ring-plane transfers, by ``op``
hvd_ring_bytes_total            counter    ring-plane payload bytes
hvd_ring_active                 gauge      1 when the peer ring is up
hvd_inflight_ops                gauge      stall-inspector watchdog entries
hvd_stalled_ops                 gauge      entries past the warning threshold
hvd_stall_warnings_total        counter    cumulative stall warnings
hvd_controller_cycles           gauge      coordinator negotiation cycles
hvd_controller_cache_hits       gauge      coordinator response-cache hits
hvd_controller_stall_warnings   gauge      coordinator-side stall warnings
hvd_join_events_total           counter    elastic host-plane join() calls
hvd_sanitizer_checks_total      counter    sanitizer fingerprints verified
hvd_sanitizer_mismatches_total  counter    sanitizer divergences raised
hvd_heartbeats_total            counter    lease renewals pushed to /health
hvd_aborts_total                counter    coordinated aborts, by ``source``
hvd_http_retries_total          counter    rendezvous HTTP requests retried
hvd_faults_injected_total       counter    HVD_FAULT_SPEC faults, by ``kind``
hvd_restarts_total              counter    supervised job relaunches (launcher)
hvd_membership_epochs_total     counter    elastic membership epochs committed
hvd_ranks_removed_total         counter    workers removed from the world
hvd_ranks_admitted_total        counter    workers admitted into the world
hvd_autotune_predicted_speedup  gauge      replay-predicted speedup of the
                                           applied fusion plan (percent)
hvd_autotune_realized_speedup   gauge      realized speedup of the applied
                                           plan vs its baseline window (pct)
hvd_autotune_plans_applied_total counter   profile-guided plans applied live
hvd_autotune_rollbacks_total    counter    plans rolled back past guard band
hvd_serve_requests_total        counter    inference requests, by ``outcome``
hvd_serve_latency_seconds       histogram  request submit→complete latency
hvd_serve_queue_wait_seconds    histogram  request submit→pull queue wait
hvd_serve_batch_fill            histogram  real (pre-padding) batch sizes
hvd_serve_queue_depth           gauge      pending requests in the broker
hvd_serve_replicas              gauge      live inference replicas
hvd_serve_p99_ms                gauge      windowed p99 request latency
hvd_serve_autoscale_events_total counter   autoscale actions, by ``direction``
hvd_serve_drains_total          counter    lossless drain handshakes done
hvd_serve_requeues_total        counter    in-flight requests requeued after
                                           a replica died uncleanly
hvd_projection_step_us          gauge      digital-twin projected step time,
                                           by target ``world``
hvd_projection_efficiency       gauge      projected scaling efficiency vs
                                           the source replay baseline
hvd_projection_err_pct          gauge      tracked projected-vs-measured
                                           step-time error of the twin
hvd_alerts_total                counter    watchdog alerts raised, by
                                           ``signal``/``severity``
                                           (horovod_tpu/observe/)
hvd_watch_arms_total            counter    trace+profile windows auto-armed
                                           by a confirmed alert
hvd_timeseries_flushes_total    counter    time-series history flushes, by
                                           ``mode`` (delta/full/resync)
hvd_events_total                counter    flight-recorder events emitted,
                                           by ``kind``/``severity``
                                           (observe/events.py)
hvd_events_dropped_total        counter    events dropped on per-process
                                           ring overflow (oldest evicted)
hvd_snapshots_total             counter    peer-tier snapshot generations
                                           committed (elastic/peerstate.py)
hvd_snapshot_bytes_total        counter    serialized snapshot bytes pushed
                                           to peers
hvd_snapshot_failures_total     counter    async snapshot attempts that died
                                           before their commit marker
hvd_snapshot_stall_us           gauge      step-path stall of the last
                                           snapshot enqueue, microseconds
hvd_snapshot_gen                gauge      newest own generation committed
                                           to the peer tier
hvd_snapshot_reprotected_total  counter    shards re-pushed to restore
                                           K-redundancy after a shrink
hvd_restores_total              counter    state restores completed, by
                                           ``source`` (peer/storage)
==============================  =========  ==================================
"""

from __future__ import annotations

import numpy as np

from ..utils import env as env_util
from .registry import (  # noqa: F401
    BYTES_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    MetricsRegistry,
    exponential_buckets,
    latency_buckets_from_env,
    registry,
    render_prometheus,
)

#: serving-request latency scheme: the default floor (100 µs) is tuned
#: for dispatch spans; request latencies live in the 0.25 ms..30 s range
#: (HVD_SERVE_LATENCY_BUCKET_FLOOR moves the floor; factor/count shared
#: with the job-wide HVD_METRICS_BUCKET_{FACTOR,COUNT})
SERVE_LATENCY_BUCKETS = latency_buckets_from_env(
    env_util.HVD_SERVE_LATENCY_BUCKET_FLOOR,
    env_util.DEFAULT_SERVE_LATENCY_BUCKET_FLOOR)

# -- instrument inventory ----------------------------------------------------
EAGER_CALLS = registry.counter(
    "hvd_eager_collective_calls_total",
    "Eager collective dispatches by op type.", ("op",))
EAGER_BYTES = registry.counter(
    "hvd_eager_collective_bytes_total",
    "Per-rank payload bytes moved by eager collectives.", ("op",))
EAGER_SECONDS = registry.histogram(
    "hvd_eager_collective_seconds",
    "Eager collective dispatch wall time.", ("op",))
NEGOTIATE_SECONDS = registry.histogram(
    "hvd_negotiation_seconds",
    "Controller negotiation (submit+wait) wall time.", ("op",))

HOST_CALLS = registry.counter(
    "hvd_host_collective_calls_total",
    "Host-plane collective ops by transport (ring/star/mesh).",
    ("op", "transport"))
HOST_BYTES = registry.counter(
    "hvd_host_collective_bytes_total",
    "Host-plane collective payload bytes by transport.",
    ("op", "transport"))
HOST_SECONDS = registry.histogram(
    "hvd_host_collective_seconds",
    "Host-plane collective wall time by transport.", ("transport",))

TRACED_CALLS = registry.counter(
    "hvd_collectives_traced_total",
    "Collective HLOs emitted during SPMD tracing (per compile, not per "
    "step).", ("op",))
TRACED_BYTES = registry.counter(
    "hvd_collectives_traced_bytes_total",
    "Per-rank payload bytes of traced collectives.", ("op",))
TRACED_GROUP_CALLS = registry.counter(
    "hvd_collectives_traced_group_total",
    "Traced collectives dispatched over a restricted communication "
    "group (two-level local/cross stages, process sets) — the group-"
    "labelled inventory the schedule checker and sanitizer reason "
    "about.", ("op", "group"))
FLASH_TILES = registry.counter(
    "hvd_flash_tiles_traced_total",
    "Score tiles of each traced flash-attention kernel call (per compile, "
    "not per step): skipped (past the diagonal, not computed), full "
    "(no key masked), crossed (the mask's edge passes through); dynamic "
    "(all of the grid's) when the offsets are traced; mask none, causal or "
    "block_diffusion_b<block>.", ("kernel", "kind", "mask"))
FLASH_GRID_STEPS = registry.counter(
    "hvd_flash_grid_steps_traced_total",
    "Steps of the streamed grid axis of each traced flash-attention kernel "
    "call (per compile, not per step): launched (the grid's extent), "
    "live (the step's block holds a tile some row sees) and idle (launched "
    "- live: visited to compute and fetch nothing; 0 on the flattened grid "
    "of a causal or block-diffusion call with static offsets); live and "
    "idle are not counted when the offsets are traced; mask as "
    "hvd_flash_tiles_traced_total's.",
    ("kernel", "kind", "mask"))
FLASH_KV_GROUP = registry.counter(
    "hvd_flash_kv_group_traced_total",
    "Traced flash-attention kernel calls (per compile, not per step) by "
    "the heads q has and the heads k and v have: a kv head serves q_heads "
    "/ kv_heads q heads by the kernels' index maps (equal counts: every "
    "head its own).", ("kernel", "q_heads", "kv_heads"))
GDN_SCAN_CHUNKS = registry.counter(
    "hvd_gdn_scan_chunks_traced_total",
    "Chunks (of every value head) each traced gated-delta-rule kernel call "
    "walks (per compile, not per step): kernel fwd or bwd, path mosaic "
    "(compiled for the TPU) or interpret (Pallas interpreter mode).",
    ("kernel", "path"))
SSM_SCAN_CHUNKS = registry.counter(
    "hvd_ssm_scan_chunks_traced_total",
    "Chunks (of every head) each traced call of the state-space scan "
    "(ops/ssd.py) walks (per compile, not per step): kernel fwd, states "
    "(the backward rule's pass that makes the chunks' start states again) "
    "or bwd; path mosaic (the Pallas kernels compiled for the TPU), "
    "interpret (Pallas interpreter mode) or xla (the chunked form as XLA "
    "ops: off a TPU, or shapes that do not tile; it has no states pass).",
    ("kernel", "path"))
KERNEL_RESIDUAL_BYTES = registry.counter(
    "hvd_kernel_residual_bytes_traced_total",
    "Bytes each traced differentiated forward of a Pallas kernel hands its "
    "backward beyond its own inputs (per compile, not per step): kernel "
    "flash (the output and the rows' log-sum-exp) or gdn_scan (the output, "
    "the chunks' start states and inverses) -- what a recomputed layer "
    "that keeps them pays in memory.", ("kernel",))
RECOMPUTE_KEPT_BYTES = registry.counter(
    "hvd_recompute_kept_bytes_traced_total",
    "Bytes a traced model's recomputed decoder layers keep beyond the "
    "Pallas kernels' residuals (models/recompute.py; per compile, not per "
    "step, over all the model's layers), by the checkpoint name kept; "
    "name=\"skipped\" is what the byte budget refused and the layers "
    "still make a second time.", ("name",))
BD_LAYERS = registry.counter(
    "hvd_bd_layers_traced_total",
    "Block-diffusion attention layers traced (models/sdar.py; per compile, "
    "not per step), by the block length of their mask.", ("block",))
MOE_LAYERS = registry.counter(
    "hvd_moe_layers_traced_total",
    "Routed expert layers (parallel/moe.routed_experts) traced (per "
    "compile, not per step), by how many experts the layer holds here, "
    "how many a token picks, the routing rule's name (route_top_k: "
    "softmax; route_sigmoid_top_k: sigmoid scores with a selection bias; "
    "with '+relu2' after it where the experts are down(relu(up x)^2) and "
    "not the gated SiLU pair) and the groups of rows the call carries one accumulator of the "
    "experts' gradients through (1: nothing to carry).",
    ("held", "top_k", "rule", "groups"))
MLA_LAYERS = registry.counter(
    "hvd_mla_layers_traced_total",
    "Latent-attention layers traced (models/kanana2.py; per compile, not "
    "per step), by the q.k head size, the v head size and the width of the "
    "compressed kv.", ("qk", "v", "latent"))
ATTN_LAYERS = registry.counter(
    "hvd_attn_layers_traced_total",
    "Softmax-attention layers traced whose kind is the layer's own "
    "(models/mellum2.py; per compile, not per step), by the kind, the keys a "
    "row of a window layer sees (0: all before it) and the kind's rotary "
    "rule.", ("kind", "window", "rope"))
SSM_LAYERS = registry.counter(
    "hvd_ssm_layers_traced_total",
    "Mamba-2 state-space mixers traced (models/nemotron_h.py; per compile, "
    "not per step), by the heads, a head's channels, the state's size, the "
    "groups B and C come in and the scan's chunk (ops/ssd.py).",
    ("heads", "head_dim", "state", "groups", "chunk"))
SCONV_LAYERS = registry.counter(
    "hvd_sconv_layers_traced_total",
    "Gated short-convolution operators traced (models/lfm2.py; per compile, "
    "not per step), by the convolution's taps and the channels it runs "
    "over.", ("taps", "channels"))

STEP_SECONDS = registry.histogram(
    "hvd_step_seconds",
    "Train-step cadence: interval between successive step dispatches "
    "(equals real step time in steady state under async dispatch).")
STEPS_TOTAL = registry.counter(
    "hvd_steps_total", "Train steps dispatched.")
SAMPLES_TOTAL = registry.counter(
    "hvd_samples_total", "Global samples dispatched into train steps.")
STEP_COMPILES = registry.counter(
    "hvd_step_compiles_total",
    "Times the compiled train step traced and compiled: a (re)build's "
    "first call, or a silent retrace on a new batch shape.  The flight "
    "recorder's step.compile event names the step and the shapes.")
TRAIN_LOSS = registry.gauge(
    "hvd_train_loss",
    "Most recently fetched training loss — fetched on the trailing "
    "HVD_LOSS_FETCH_STEPS cadence (training.py), so the value is N "
    "steps old and the fetch never drains the dispatch pipeline.")

RING_OPS = registry.counter(
    "hvd_ring_ops_total", "Peer-ring transfers executed.", ("op",))
RING_BYTES = registry.counter(
    "hvd_ring_bytes_total", "Peer-ring payload bytes transferred.")
RING_ACTIVE = registry.gauge(
    "hvd_ring_active", "1 while the peer-ring data plane is established.")

INFLIGHT_OPS = registry.gauge(
    "hvd_inflight_ops", "Operations currently in the stall-inspector "
    "watchdog table (negotiation/dispatch queue depth).")
STALLED_OPS = registry.gauge(
    "hvd_stalled_ops", "Watchdog entries past the warning threshold.")
STALL_WARNINGS = registry.counter(
    "hvd_stall_warnings_total", "Cumulative stall warnings emitted.")

CONTROLLER_CYCLES = registry.gauge(
    "hvd_controller_cycles", "Coordinator negotiation cycles completed.")
CONTROLLER_CACHE_HITS = registry.gauge(
    "hvd_controller_cache_hits", "Coordinator response-cache hits.")
CONTROLLER_STALLS = registry.gauge(
    "hvd_controller_stall_warnings", "Coordinator-side stall warnings.")

JOIN_EVENTS = registry.counter(
    "hvd_join_events_total", "Elastic host-plane join() barriers entered.")

SANITIZER_CHECKS = registry.counter(
    "hvd_sanitizer_checks_total",
    "Collective-sanitizer fingerprint checks that verified clean.")
SANITIZER_MISMATCHES = registry.counter(
    "hvd_sanitizer_mismatches_total",
    "Collective-sanitizer divergences detected (signature mismatch or "
    "silent peer).")

HEARTBEATS = registry.counter(
    "hvd_heartbeats_total",
    "Heartbeat lease renewals pushed to the rendezvous /health scope.")
ABORTS = registry.counter(
    "hvd_aborts_total",
    "Coordinated aborts by source plane (launcher/stall_inspector/api) "
    "plus 'observed' on ranks whose heartbeat saw the flag.", ("source",))
HTTP_RETRIES = registry.counter(
    "hvd_http_retries_total",
    "Rendezvous HTTP requests retried after a transient failure "
    "(URLError or 5xx).")
HTTP_REUSE = registry.counter(
    "hvd_http_reuse_total",
    "Rendezvous HTTP requests served over a pooled keep-alive "
    "connection instead of a fresh TCP connect (run/http_client.py).")
CP_FAILOVERS = registry.counter(
    "hvd_cp_failovers_total",
    "Requests that abandoned a dead rendezvous address for the next "
    "entry of the HVD_RENDEZVOUS_ADDRS failover list.")
RELAY_FLUSHES = registry.counter(
    "hvd_relay_flushes_total",
    "Per-host relay upstream batch flushes (run/relay.py; one PUT "
    "/batch each, replacing one request per buffered key).")
RELAY_ENTRIES = registry.counter(
    "hvd_relay_entries_total",
    "KV entries the per-host relay aggregated into upstream batches.")
RELAY_FALLBACKS = registry.counter(
    "hvd_relay_fallbacks_total",
    "Control-plane clients that fell back from an unreachable per-host "
    "relay to the primary rendezvous (pass-through mode).")
METRICS_DELTA_PUSHES = registry.counter(
    "hvd_metrics_delta_pushes_total",
    "Metric snapshot pushes sent as family deltas instead of full "
    "snapshots (metrics/push.py), by outcome.", ("outcome",))
FAULTS_INJECTED = registry.counter(
    "hvd_faults_injected_total",
    "Faults injected by the HVD_FAULT_SPEC harness, by kind.", ("kind",))
RESTARTS = registry.counter(
    "hvd_restarts_total",
    "Supervised job relaunches performed by the tpurun restart policy "
    "(launcher-side).")
MEMBERSHIP_EPOCHS = registry.counter(
    "hvd_membership_epochs_total",
    "Elastic membership epochs committed by the driver (launcher-side; "
    "includes the initial world).")
RANKS_REMOVED = registry.counter(
    "hvd_ranks_removed_total",
    "Workers removed from the elastic world (crashes, lease expiries, "
    "partitions).")
RANKS_ADMITTED = registry.counter(
    "hvd_ranks_admitted_total",
    "Workers admitted into the elastic world at epoch boundaries "
    "(rejoins and spare hosts).")

SNAPSHOTS_TOTAL = registry.counter(
    "hvd_snapshots_total",
    "Peer-tier snapshot generations committed by this rank "
    "(elastic/peerstate.py).")
SNAPSHOT_BYTES = registry.counter(
    "hvd_snapshot_bytes_total",
    "Serialized snapshot bytes this rank pushed to its replica peers.")
SNAPSHOT_FAILURES = registry.counter(
    "hvd_snapshot_failures_total",
    "Async snapshot attempts that failed before writing their commit "
    "marker (the generation stays unrestorable; storage tier covers).")
SNAPSHOT_STALL_US = registry.gauge(
    "hvd_snapshot_stall_us",
    "Step-path stall of the last snapshot enqueue in microseconds — "
    "the ONLY checkpoint cost the training step pays on the peer tier.")
SNAPSHOT_GEN = registry.gauge(
    "hvd_snapshot_gen",
    "Newest generation (= step) this rank committed to the peer tier.")
SNAPSHOT_REPROTECTED = registry.counter(
    "hvd_snapshot_reprotected_total",
    "Shards re-pushed to new peers to restore K-redundancy after a "
    "world shrink orphaned their replicas.")
RESTORES = registry.counter(
    "hvd_restores_total",
    "State restores completed, by source tier (peer/storage).",
    ("source",))

AUTOTUNE_PREDICTED_SPEEDUP = registry.gauge(
    "hvd_autotune_predicted_speedup",
    "Replay-predicted speedup (percent) of the currently applied "
    "profile-guided fusion plan (optim/profile_guided.py).")
AUTOTUNE_REALIZED_SPEEDUP = registry.gauge(
    "hvd_autotune_realized_speedup",
    "Realized speedup (percent) of the applied plan's verify window "
    "against its baseline window.")
AUTOTUNE_PLANS_APPLIED = registry.counter(
    "hvd_autotune_plans_applied_total",
    "Profile-guided fusion plans applied live through the re-jit seam.")
AUTOTUNE_ROLLBACKS = registry.counter(
    "hvd_autotune_rollbacks_total",
    "Applied plans rolled back because realized speedup lagged the "
    "prediction past the guard band.")

SERVE_REQUESTS = registry.counter(
    "hvd_serve_requests_total",
    "Inference requests by outcome (ok/error/timeout/rejected) — "
    "serving plane, horovod_tpu/serving/.", ("outcome",))
SERVE_LATENCY = registry.histogram(
    "hvd_serve_latency_seconds",
    "Inference request latency, submit to complete (the number the SLO "
    "is written against).", buckets=SERVE_LATENCY_BUCKETS)
SERVE_QUEUE_WAIT = registry.histogram(
    "hvd_serve_queue_wait_seconds",
    "Time a request waited in the broker queue before a replica pulled "
    "it (queueing delay component of hvd_serve_latency_seconds).",
    buckets=SERVE_LATENCY_BUCKETS)
SERVE_BATCH_FILL = registry.histogram(
    "hvd_serve_batch_fill",
    "Real (pre-padding) batch sizes formed by the continuous batcher.",
    buckets=exponential_buckets(1.0, 2.0, 9))
SERVE_QUEUE_DEPTH = registry.gauge(
    "hvd_serve_queue_depth",
    "Requests pending in the serving broker queue (the autoscaler's "
    "primary load signal).")
SERVE_REPLICAS = registry.gauge(
    "hvd_serve_replicas",
    "Live inference replicas pulling from the broker.")
SERVE_P99_MS = registry.gauge(
    "hvd_serve_p99_ms",
    "Windowed p99 request latency in milliseconds (compared against "
    "HVD_SERVE_SLO_MS by the autoscaler).")
SERVE_AUTOSCALE_EVENTS = registry.counter(
    "hvd_serve_autoscale_events_total",
    "Membership epochs committed by the serving autoscaler, by "
    "direction (grow/shrink).", ("direction",))
SERVE_DRAINS = registry.counter(
    "hvd_serve_drains_total",
    "Lossless drain handshakes completed before a scale-down removal "
    "(elastic/driver.py).")
SERVE_REQUEUES = registry.counter(
    "hvd_serve_requeues_total",
    "In-flight requests returned to the queue after a replica died "
    "without completing them.")

PROJECTION_STEP_US = registry.gauge(
    "hvd_projection_step_us",
    "Digital-twin projected step time in µs for one target topology "
    "(timeline/replay/projection.py; labeled by target world size).",
    ("world",))
PROJECTION_EFFICIENCY = registry.gauge(
    "hvd_projection_efficiency",
    "Projected scaling efficiency (source replay baseline over projected "
    "step) for one target topology, by target world size.", ("world",))
PROJECTION_ERR_PCT = registry.gauge(
    "hvd_projection_err_pct",
    "Projected-vs-measured step-time error of the digital twin on a "
    "world that was actually run (the twin's tracked accuracy — "
    "docs/projection.md validation contract).")

ALERTS_TOTAL = registry.counter(
    "hvd_alerts_total",
    "Online-watchdog alerts raised by the observe/ detectors, by signal "
    "(step_time_regression/straggler/comm_beta_drift/slo_burn) "
    "and severity (warning/critical) — docs/observe.md.",
    ("signal", "severity"))
WATCH_ARMS = registry.counter(
    "hvd_watch_arms_total",
    "Trace+profile windows auto-armed by a confirmed step-time or "
    "straggler alert (observe/watchdog.py KV broadcast).")
TIMESERIES_FLUSHES = registry.counter(
    "hvd_timeseries_flushes_total",
    "Time-series history flushes shipped to the launcher, by mode "
    "(delta/full/resync) — metrics/timeseries.py.", ("mode",))
EVENTS_TOTAL = registry.counter(
    "hvd_events_total",
    "Control-plane flight-recorder events emitted, by kind "
    "(epoch.commit/abort.publish/restart.attempt/...) and severity "
    "(observe/events.py, docs/observe.md).", ("kind", "severity"))
EVENTS_DROPPED = registry.counter(
    "hvd_events_dropped_total",
    "Flight-recorder events dropped on per-process ring overflow "
    "(oldest evicted; raise HVD_EVENTS_RING_CAP if nonzero).")

COMPRESSION_RESIDUAL_NORM = registry.gauge(
    "hvd_compression_residual_norm",
    "Global L2 norm of the error-feedback residual pytree, sampled every "
    "HVD_COMPRESSION_GUARD_STEPS steps (ops/compression.py; a healthy EF "
    "loop keeps this bounded by the per-step quantization error).")
COMPRESSION_FALLBACKS = registry.counter(
    "hvd_compression_fallbacks_total",
    "Automatic fall-backs to uncompressed allreduce after the error-"
    "feedback residual diverged (training.py convergence guard).")
TWO_LEVEL_FALLBACKS = registry.counter(
    "hvd_two_level_fallbacks_total",
    "two_level_allreduce degradations to flat allreduce (non-power-of-two "
    "cross-host group or trivial topology); counted per compiled program, "
    "not per step.")


def on() -> bool:
    """The hot-path gate: one attribute read."""
    return registry.enabled


def payload_bytes(shape, dtype) -> int:
    """Best-effort byte count of one rank's payload; never raises (the
    metrics plane must not take down a dispatch over an exotic dtype)."""
    try:
        n = 1
        for d in shape:
            n *= int(d)
        return n * np.dtype(dtype).itemsize
    except Exception:  # noqa: BLE001
        try:
            import ml_dtypes  # bfloat16/fp8 names numpy doesn't know

            n = 1
            for d in shape:
                n *= int(d)
            return n * np.dtype(getattr(ml_dtypes, str(dtype))).itemsize
        except Exception:  # noqa: BLE001
            return 0


def record_eager(op: str, nbytes: int, negotiate_s: float,
                 total_s: float) -> None:
    """One eager collective dispatch (eager._dispatch_guard)."""
    EAGER_CALLS.labels(op).inc()
    if nbytes:
        EAGER_BYTES.labels(op).inc(nbytes)
        # dispatch cost density (µs per MiB moved): the series the
        # observe/ comm-β drift detector compares against the α–β model
        if timeseries.on():
            timeseries.record(timeseries.DISPATCH_US_PER_MIB,
                              total_s * 1e6 / (nbytes / 2**20))
    EAGER_SECONDS.labels(op).observe(total_s)
    NEGOTIATE_SECONDS.labels(op).observe(negotiate_s)


def record_host(op: str, transport: str, nbytes: int, seconds: float) -> None:
    """One host-plane collective (eager.process_* transports)."""
    HOST_CALLS.labels(op, transport).inc()
    if nbytes:
        HOST_BYTES.labels(op, transport).inc(nbytes)
    HOST_SECONDS.labels(transport).observe(seconds)


def record_traced(op: str, tensor) -> None:
    """A collective primitive emitted during SPMD tracing
    (ops/collectives.py) — compile-time cost only, never per-step."""
    if not registry.enabled:
        return
    try:
        TRACED_CALLS.labels(op).inc()
        nb = payload_bytes(getattr(tensor, "shape", ()),
                           getattr(tensor, "dtype", "float32"))
        if nb:
            TRACED_BYTES.labels(op).inc(nb)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def _count_by_kind(counter, kernel: str, counts, mask: str) -> None:
    if not registry.enabled:
        return
    try:
        for kind, n in counts.items():
            counter.labels(kernel, kind, mask).inc(n)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_flash_tiles(kernel: str, counts, mask: str) -> None:
    """Score tiles by kind of one traced flash kernel call
    (ops/flash_attention.py) under the mask ``mask`` — how often the
    unmasked body engages, how much of the grid is skipped."""
    _count_by_kind(FLASH_TILES, kernel, counts, mask)


def record_flash_grid_steps(kernel: str, counts, mask: str) -> None:
    """Launched, live and idle steps of the streamed grid axis of one traced
    flash kernel call (ops/flash_attention.py) under the mask ``mask`` —
    how closely the grid fits the blocks the mask leaves live."""
    _count_by_kind(FLASH_GRID_STEPS, kernel, counts, mask)


def record_flash_kv_group(kernel: str, q_heads: int, kv_heads: int) -> None:
    """One traced flash kernel call (ops/flash_attention.py) — whether k
    and v came at their own head count, and how many q heads share one."""
    if not registry.enabled:
        return
    try:
        FLASH_KV_GROUP.labels(kernel, str(q_heads), str(kv_heads)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_gdn_scan_chunks(kernel: str, path: str, chunks: int) -> None:
    """One traced call of a gated-delta-rule kernel (ops/gated_delta.py)
    — that the kernels engaged, on which path, over how many chunks."""
    if not registry.enabled:
        return
    try:
        GDN_SCAN_CHUNKS.labels(kernel, path).inc(chunks)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_ssm_scan_chunks(kernel: str, path: str, chunks: int) -> None:
    """One traced call of the state-space scan (ops/ssd.py) — which path
    it took, over how many chunks."""
    if not registry.enabled:
        return
    try:
        SSM_SCAN_CHUNKS.labels(kernel, path).inc(chunks)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_kernel_residual_bytes(kernel: str, nbytes: int) -> None:
    """One traced differentiated forward of a Pallas kernel
    (ops/flash_attention.py, ops/gated_delta.py): what it keeps for its
    backward beyond its own inputs."""
    if not registry.enabled:
        return
    try:
        KERNEL_RESIDUAL_BYTES.labels(kernel).inc(nbytes)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_recompute_kept(kept: dict, skipped: int) -> None:
    """One traced model with recomputed layers (models/recompute.py):
    ``kept`` is ``{checkpoint name: bytes over all the layers}`` of what
    the budget let the layers keep, ``skipped`` the bytes it refused."""
    if not registry.enabled:
        return
    try:
        for name, nbytes in kept.items():
            RECOMPUTE_KEPT_BYTES.labels(name).inc(nbytes)
        RECOMPUTE_KEPT_BYTES.labels("skipped").inc(skipped)
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_bd_layer(block: int) -> None:
    """One traced block-diffusion attention layer (models/sdar.py)."""
    if not registry.enabled:
        return
    try:
        BD_LAYERS.labels(str(block)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_moe_layer(held: int, top_k: int, rule: str, groups: int) -> None:
    """One traced call of ``parallel/moe.routed_experts`` over ``groups``
    groups of rows."""
    if not registry.enabled:
        return
    try:
        MOE_LAYERS.labels(str(held), str(top_k), rule, str(groups)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_mla_layer(qk: int, v: int, latent: int) -> None:
    """One traced latent-attention layer (models/kanana2.py)."""
    if not registry.enabled:
        return
    try:
        MLA_LAYERS.labels(str(qk), str(v), str(latent)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_attn_layer(kind: str, window: int, rope: str) -> None:
    """One traced attention layer of a decoder whose layers differ by kind
    (models/mellum2.py)."""
    if not registry.enabled:
        return
    try:
        ATTN_LAYERS.labels(kind, str(window), rope).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_ssm_layer(heads: int, head_dim: int, state: int, groups: int,
                     chunk: int) -> None:
    """One traced Mamba-2 mixer (models/nemotron_h.py)."""
    if not registry.enabled:
        return
    try:
        SSM_LAYERS.labels(str(heads), str(head_dim), str(state), str(groups),
                          str(chunk)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_sconv_layer(taps: int, channels: int) -> None:
    """One traced gated short convolution (models/lfm2.py)."""
    if not registry.enabled:
        return
    try:
        SCONV_LAYERS.labels(str(taps), str(channels)).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def record_traced_group(op: str, group: str) -> None:
    """Group-labelled traced-collective inventory (two-level local/cross
    stages, process sets) — rides its own counter so the user-visible
    per-op dispatch (already counted by :func:`record_traced` at the
    call seam) is not double-counted.  ``group`` here is the group
    *family* (``local`` / ``cross`` / ``process_set:…``): tracing emits
    one program for every device, so there is no single concrete group
    instance to name — the sanitizer's runtime fingerprints key the
    concrete instances (``local:<node>``, ``cross:<chunk>``)."""
    if not registry.enabled:
        return
    try:
        TRACED_GROUP_CALLS.labels(op, group).inc()
    except Exception:  # noqa: BLE001 — tracing must never fail on metrics
        pass


def dump_metrics_json(path: str) -> None:
    """Write the per-rank snapshot (called by timeline shutdown so
    ``metrics.json`` lands next to ``comm.json``)."""
    registry.dump(path)


from . import timeseries  # noqa: E402  (ring-buffer history plane)
from .push import (  # noqa: E402,F401  (import after instruments exist)
    start_pusher,
    start_pusher_from_env,
    stop_pusher,
)
