"""Environment-variable knob inventory and parsing.

TPU-native analog of the ``HOROVOD_*`` env system (reference inventory at
horovod/common/common.h:62-87, parsing in horovod/common/operations.cc:392-492
and horovod/common/utils/env_parser.cc:41-106).  Same three-layer contract:
(1) ``HVD_*`` env vars consumed by the runtime, (2) ``tpurun`` CLI flags that
set them for workers (horovod_tpu/run/config_parser.py), (3) optional YAML
config file overriding CLI.
"""

from __future__ import annotations

import os
from typing import Optional

# -- knob names (HOROVOD_* → HVD_*) ------------------------------------------
HVD_FUSION_THRESHOLD = "HVD_FUSION_THRESHOLD"          # bytes; HOROVOD_FUSION_THRESHOLD
HVD_CYCLE_TIME = "HVD_CYCLE_TIME"                      # ms; HOROVOD_CYCLE_TIME
HVD_TIMELINE = "HVD_TIMELINE"                          # trace output dir
HVD_TIMELINE_MARK_CYCLES = "HVD_TIMELINE_MARK_CYCLES"
HVD_TRACE_START_STEP = "HVD_TRACE_START_STEP"          # fork: BYTEPS_TRACE_START_STEP
HVD_TRACE_END_STEP = "HVD_TRACE_END_STEP"              # fork: BYTEPS_TRACE_END_STEP
HVD_TRACE_ON = "HVD_TRACE_ON"                          # fork: BYTEPS_TRACE_ON
HVD_TRACE_DIR = "HVD_TRACE_DIR"                        # fork: BYTEPS_TRACE_DIR
HVD_STALL_CHECK_DISABLE = "HVD_STALL_CHECK_DISABLE"
HVD_STALL_CHECK_TIME_SECONDS = "HVD_STALL_CHECK_TIME_SECONDS"
HVD_STALL_SHUTDOWN_TIME_SECONDS = "HVD_STALL_SHUTDOWN_TIME_SECONDS"
HVD_AUTOTUNE = "HVD_AUTOTUNE"
HVD_AUTOTUNE_LOG = "HVD_AUTOTUNE_LOG"
HVD_AUTOTUNE_WARMUP_SAMPLES = "HVD_AUTOTUNE_WARMUP_SAMPLES"
HVD_AUTOTUNE_STEPS_PER_SAMPLE = "HVD_AUTOTUNE_STEPS_PER_SAMPLE"
HVD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HVD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
HVD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "HVD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
# profile-guided tuning loop (optim/profile_guided.py, docs/autotune.md):
# replay what-ifs planned into explicit fusion buckets, applied live and
# verified predicted-vs-realized with automatic rollback
HVD_AUTOTUNE_PROFILE_GUIDED = "HVD_AUTOTUNE_PROFILE_GUIDED"  # 1 enables the loop
HVD_AUTOTUNE_WINDOW_STEPS = "HVD_AUTOTUNE_WINDOW_STEPS"      # steps per measure/verify window (default 20)
HVD_AUTOTUNE_GUARD_BAND_PCT = "HVD_AUTOTUNE_GUARD_BAND_PCT"  # realized-vs-predicted tolerance (default 10)
HVD_AUTOTUNE_ROLLBACK = "HVD_AUTOTUNE_ROLLBACK"              # 0 keeps regressed plans (debug; default 1)
HVD_AUTOTUNE_WARM_START = "HVD_AUTOTUNE_WARM_START"          # 0 skips the α–β GP prior (default 1)
HVD_AUTOTUNE_CYCLE_FLUSH_STEPS = "HVD_AUTOTUNE_CYCLE_FLUSH_STEPS"  # re-plan a verified plan every N steps (0 = pin forever)
HVD_BENCH_AUTOTUNE = "HVD_BENCH_AUTOTUNE"                    # 0 skips bench.py's autotuned second run
HVD_LOG_LEVEL = "HVD_LOG_LEVEL"
HVD_LOG_HIDE_TIME = "HVD_LOG_HIDE_TIME"
HVD_HIERARCHICAL_ALLREDUCE = "HVD_HIERARCHICAL_ALLREDUCE"
HVD_HIERARCHICAL_ALLGATHER = "HVD_HIERARCHICAL_ALLGATHER"
# wire-efficiency tier (ops/compression.py, parallel/hierarchical.py;
# docs/compression.md): gradient compression + two-level reduction
HVD_COMPRESSION = "HVD_COMPRESSION"                    # none|bf16|int8|fp8|fp8_e5m2 wire format
HVD_COMPRESSION_ERROR_FEEDBACK = "HVD_COMPRESSION_ERROR_FEEDBACK"  # 0 drops the residual carry (default 1)
HVD_COMPRESSION_GUARD_STEPS = "HVD_COMPRESSION_GUARD_STEPS"  # residual-norm check cadence (default 25; 0 off)
HVD_COMPRESSION_GUARD_FACTOR = "HVD_COMPRESSION_GUARD_FACTOR"  # divergence = norm > factor x baseline (default 10)
HVD_TWO_LEVEL_ALLREDUCE = "HVD_TWO_LEVEL_ALLREDUCE"    # 1 = compressed two-level (ICI RS + DCN AR) gradient path
HVD_BENCH_COMPRESSION = "HVD_BENCH_COMPRESSION"        # 0 skips bench.py's compressed comparison leg
HVD_CACHE_CAPACITY = "HVD_CACHE_CAPACITY"
# host-plane ring/star crossover: payloads >= this ride the peer ring
# (calibrate per fabric: scripts/host_plane_bench.py --crossover)
HVD_RING_MIN_BYTES = "HVD_RING_MIN_BYTES"
HVD_BATCH_D2D_MEMCOPIES = "HVD_BATCH_D2D_MEMCOPIES"
HVD_NUM_NCCL_STREAMS = "HVD_NUM_NCCL_STREAMS"          # parity stub
# comma list of NIC names the host data plane advertises on (reference
# --network-interface / HOROVOD_GLOO_IFACE + NCCL_SOCKET_IFNAME)
HVD_NETWORK_INTERFACE = "HVD_NETWORK_INTERFACE"
# launcher-set topology vars (analog of HOROVOD_RANK/SIZE/LOCAL_RANK/... set
# by gloo_run, reference run/gloo_run.py:210-216)
HVD_RANK = "HVD_RANK"
HVD_SIZE = "HVD_SIZE"
HVD_LOCAL_RANK = "HVD_LOCAL_RANK"
HVD_LOCAL_SIZE = "HVD_LOCAL_SIZE"
HVD_CROSS_RANK = "HVD_CROSS_RANK"
HVD_CROSS_SIZE = "HVD_CROSS_SIZE"
HVD_COORDINATOR_ADDR = "HVD_COORDINATOR_ADDR"
HVD_NUM_PROCESSES = "HVD_NUM_PROCESSES"
HVD_PROCESS_ID = "HVD_PROCESS_ID"
HVD_CONTROLLER = "HVD_CONTROLLER"
HVD_CPU_OPERATIONS = "HVD_CPU_OPERATIONS"
# native controller wiring (set by the launcher; runtime/eager_controller.py)
HVD_CONTROLLER_ADDR = "HVD_CONTROLLER_ADDR"            # host:port of the coordinator
HVD_CONTROLLER_SERVER = "HVD_CONTROLLER_SERVER"        # "external" = launcher hosts it
HVD_COORD_PORT = "HVD_COORD_PORT"                      # jax.distributed coordinator port
# peer-ring data plane (runtime/ring.py)
HVD_RING = "HVD_RING"                                  # 0 disables the ring (debug aid)
HVD_RING_CHUNK_BYTES = "HVD_RING_CHUNK_BYTES"          # ring pipeline chunk size
HVD_RING_HOST = "HVD_RING_HOST"                        # launcher-known address peers dial
# function-mode plumbing (run/run.py run() ↔ run/task_fn.py)
HVD_RUN_KV_ADDR = "HVD_RUN_KV_ADDR"
HVD_RUN_KV_PORT = "HVD_RUN_KV_PORT"
HVD_RUN_SECRET = "HVD_RUN_SECRET"
HVD_RUN_PID = "HVD_RUN_PID"
HVD_RUN_NP = "HVD_RUN_NP"
# TPU pod host discovery (run/discovery.py)
HVD_TPU_HOSTS = "HVD_TPU_HOSTS"
HVD_TPU_SLOTS = "HVD_TPU_SLOTS"
# force the pure-Python fallbacks over the native csrc paths
HVD_TIMELINE_PYTHON = "HVD_TIMELINE_PYTHON"
HVD_AUTOTUNE_PYTHON = "HVD_AUTOTUNE_PYTHON"
# metrics plane (horovod_tpu/metrics/)
HVD_METRICS = "HVD_METRICS"                            # 0 disables the registry
HVD_METRICS_KV_ADDR = "HVD_METRICS_KV_ADDR"            # launcher rendezvous host
HVD_METRICS_KV_PORT = "HVD_METRICS_KV_PORT"            # launcher rendezvous port
HVD_METRICS_SECRET = "HVD_METRICS_SECRET"              # hex HMAC secret for pushes
HVD_METRICS_PUSH_SECONDS = "HVD_METRICS_PUSH_SECONDS"  # push interval (default 5)
# collective sanitizer + linter (horovod_tpu/analysis/)
HVD_SANITIZER = "HVD_SANITIZER"                        # 1 fingerprints every eager dispatch
HVD_SANITIZER_TIMEOUT_SECONDS = "HVD_SANITIZER_TIMEOUT_SECONDS"  # peer wait (default 60)
HVD_SANITIZER_EPOCH_STRICT = "HVD_SANITIZER_EPOCH_STRICT"  # 0 lets checks span membership epochs (default 1)
HVD_LINT_DISABLE = "HVD_LINT_DISABLE"                  # comma list of rule IDs hvd_lint skips
# schedule model checker (analysis/schedule/, scripts/hvd_verify.py)
HVD_VERIFY_MAX_PATHS = "HVD_VERIFY_MAX_PATHS"          # per-entry path budget (default 64)
HVD_VERIFY_LOOP_BOUND = "HVD_VERIFY_LOOP_BOUND"        # loop unroll bound (default 2)
HVD_PEAK_FLOPS = "HVD_PEAK_FLOPS"                      # per-chip peak FLOP/s for every MFU number (default: utils/flops.DEVICE_PEAKS by device kind; none for an unknown device)
# dPRO-style replay engine (horovod_tpu/timeline/replay/)
HVD_REPLAY_CLOCK_SYNC = "HVD_REPLAY_CLOCK_SYNC"        # 0 skips the init-time clock handshake
HVD_REPLAY_CLOCK_SAMPLES = "HVD_REPLAY_CLOCK_SAMPLES"  # handshake round trips (default 8)
HVD_REPLAY_ICI_GBPS = "HVD_REPLAY_ICI_GBPS"            # what-if link bandwidth, GB/s (default 186)
HVD_REPLAY_HOP_US = "HVD_REPLAY_HOP_US"                # what-if per-hop latency, µs (default 1)
HVD_REPLAY_DCN_GBPS = "HVD_REPLAY_DCN_GBPS"            # two-level what-if cross bandwidth, GB/s (default 25)
HVD_REPLAY_DCN_HOP_US = "HVD_REPLAY_DCN_HOP_US"        # two-level what-if cross hop latency, µs (default 10)
HVD_REPLAY_LOCAL_SIZE = "HVD_REPLAY_LOCAL_SIZE"        # two-level what-if ICI group size (default HVD_LOCAL_SIZE)
# fleet-scale digital twin (timeline/replay/projection.py,
# docs/projection.md): topology-projected replay + tracked accuracy
HVD_PROJECT_MODE = "HVD_PROJECT_MODE"                  # chain replication: distribution|slowest (default distribution)
HVD_PROJECT_SLO_GUARD = "HVD_PROJECT_SLO_GUARD"        # 0 disables the autoscaler's projected-p99 shrink guard (default 1)
HVD_BENCH_PROJECTION = "HVD_BENCH_PROJECTION"          # 0 skips bench.py's projection-accuracy leg
# failure-domain runtime (horovod_tpu/elastic/, docs/fault_tolerance.md)
HVD_HEARTBEAT_INTERVAL_SECONDS = "HVD_HEARTBEAT_INTERVAL_SECONDS"  # lease renewal (default 2)
HVD_HEARTBEAT_DISABLE = "HVD_HEARTBEAT_DISABLE"        # 1 turns the lease/abort plane off
HVD_TERM_GRACE_SECONDS = "HVD_TERM_GRACE_SECONDS"      # SIGTERM→SIGKILL escalation grace (default 5)
HVD_HTTP_RETRIES = "HVD_HTTP_RETRIES"                  # rendezvous HTTP retry budget (default 2)
HVD_HTTP_BACKOFF_MS = "HVD_HTTP_BACKOFF_MS"            # base retry backoff, ms (default 50)
HVD_FAULT_SPEC = "HVD_FAULT_SPEC"                      # fault-injection spec (elastic/faults.py)
HVD_FAULT_SEED = "HVD_FAULT_SEED"                      # seeds each injector's RNG (mixed with rank + restart) so prob= faults replay deterministically
HVD_RESTART_COUNT = "HVD_RESTART_COUNT"                # incarnation index set by the supervisor
HVD_RESTART_BACKOFF_SECONDS = "HVD_RESTART_BACKOFF_SECONDS"  # restart backoff base (default 1)
# elastic membership (elastic/membership.py + elastic/driver.py;
# docs/fault_tolerance.md): shrink/grow worlds without relaunch
HVD_ELASTIC = "HVD_ELASTIC"                            # 1 = elastic driver supervises the job
HVD_ELASTIC_WORKER_ID = "HVD_ELASTIC_WORKER_ID"        # stable worker identity across epochs
HVD_ELASTIC_MIN_NP = "HVD_ELASTIC_MIN_NP"              # floor world size before giving up (default 1)
HVD_ELASTIC_TIMEOUT_SECONDS = "HVD_ELASTIC_TIMEOUT_SECONDS"  # epoch wait/rebuild budget (default 60)
HVD_ELASTIC_MAX_FLAPS = "HVD_ELASTIC_MAX_FLAPS"        # removals before a worker is blocklisted (default 3)
HVD_ELASTIC_SILENT_GRACE_SECONDS = "HVD_ELASTIC_SILENT_GRACE_SECONDS"  # >0: a stable-epoch member with NO re-established lease this long past stability is removed as dead (default 0 = off)
# metrics-plane histogram shape (metrics/registry.py): the default
# latency bucket scheme is exponential from FLOOR seconds; serving-scale
# request latencies get their own floor below
HVD_METRICS_BUCKET_FLOOR = "HVD_METRICS_BUCKET_FLOOR"  # first latency bucket edge, seconds (default 1e-4)
HVD_METRICS_BUCKET_FACTOR = "HVD_METRICS_BUCKET_FACTOR"  # geometric growth per bucket (default 2)
HVD_METRICS_BUCKET_COUNT = "HVD_METRICS_BUCKET_COUNT"  # finite bucket count (default 18)
# serving plane (horovod_tpu/serving/, docs/inference.md): continuous-
# batching inference replicas + traffic-driven autoscaling on the
# elastic epoch machinery
HVD_SERVE = "HVD_SERVE"                                # 1 = serving plane on (tpurun --serve)
HVD_SERVE_MAX_BATCH = "HVD_SERVE_MAX_BATCH"            # batcher admits up to this many requests (default 8)
HVD_SERVE_MAX_WAIT_MS = "HVD_SERVE_MAX_WAIT_MS"        # flush deadline from first admitted request (default 5)
HVD_SERVE_BUCKET_SIZES = "HVD_SERVE_BUCKET_SIZES"      # comma list of padded batch sizes (default pow2 <= max batch)
HVD_SERVE_SLO_MS = "HVD_SERVE_SLO_MS"                  # p99 latency objective (default 100)
HVD_SERVE_TIMEOUT_SECONDS = "HVD_SERVE_TIMEOUT_SECONDS"  # per-request wait budget (default 30)
HVD_SERVE_QUEUE_LIMIT = "HVD_SERVE_QUEUE_LIMIT"        # admission cap; excess rejected (default 4096)
HVD_SERVE_LATENCY_BUCKET_FLOOR = "HVD_SERVE_LATENCY_BUCKET_FLOOR"  # serving histogram floor, seconds (default 2.5e-4)
HVD_SERVE_AUTOSCALE = "HVD_SERVE_AUTOSCALE"            # 1 = autoscaler drives the elastic driver
HVD_SERVE_QUEUE_HIGH = "HVD_SERVE_QUEUE_HIGH"          # per-replica queue depth read as overload (default 4)
HVD_SERVE_QUEUE_LOW = "HVD_SERVE_QUEUE_LOW"            # per-replica queue depth read as idle (default 0.5)
HVD_SERVE_HYSTERESIS_TICKS = "HVD_SERVE_HYSTERESIS_TICKS"  # sustained ticks before grow/shrink (default 3)
HVD_SERVE_COOLDOWN_SECONDS = "HVD_SERVE_COOLDOWN_SECONDS"  # min spacing between autoscale actions (default 10)
HVD_SERVE_MIN_REPLICAS = "HVD_SERVE_MIN_REPLICAS"      # shrink floor (default 1)
HVD_SERVE_MAX_REPLICAS = "HVD_SERVE_MAX_REPLICAS"      # grow ceiling (default 0 = bounded by spares)
HVD_SERVE_DRAIN_TIMEOUT_SECONDS = "HVD_SERVE_DRAIN_TIMEOUT_SECONDS"  # drain handshake budget (default elastic timeout)
HVD_SERVE_WEIGHT_COMPRESSION = "HVD_SERVE_WEIGHT_COMPRESSION"  # none|bf16|int8|fp8 at-rest weight format
HVD_BENCH_SERVE = "HVD_BENCH_SERVE"                    # 0 skips bench.py's serving leg
# async host pipeline (training.py TrailingLossFetcher, data/loader.py)
HVD_LOSS_FETCH_STEPS = "HVD_LOSS_FETCH_STEPS"          # trailing async loss fetch cadence (default 16; 0 never fetches)
HVD_PREFETCH_DEPTH = "HVD_PREFETCH_DEPTH"              # device prefetch queue depth in data/loader.py (default 2; 0 disables)
# hierarchical HA control plane (run/store.py, run/journal.py,
# run/relay.py; docs/control_plane.md): sharded KV + per-host relay
# aggregation + warm-standby failover
HVD_CP_SHARDS = "HVD_CP_SHARDS"                        # KV store shard count (default 8)
HVD_RENDEZVOUS_ADDRS = "HVD_RENDEZVOUS_ADDRS"          # ordered host:port,host:port failover list (primary first)
HVD_RENDEZVOUS_JOURNAL = "HVD_RENDEZVOUS_JOURNAL"      # mutation-journal path; enables warm-standby replay
HVD_RELAY = "HVD_RELAY"                                # 1 = local-rank-0 runs the per-host relay daemon
HVD_RELAY_PORT = "HVD_RELAY_PORT"                      # relay listen port (default 0 = ephemeral)
HVD_RELAY_FLUSH_MS = "HVD_RELAY_FLUSH_MS"              # relay upstream batch-flush cadence, ms (default 200)
HVD_HTTP_KEEPALIVE = "HVD_HTTP_KEEPALIVE"              # 0 disables pooled keep-alive connections (debug)
HVD_METRICS_DELTA = "HVD_METRICS_DELTA"                # 0 forces full metric snapshots every push (default delta)
HVD_BENCH_CONTROL = "HVD_BENCH_CONTROL"                # 0 skips bench.py's control-plane churn leg
# always-on telemetry time-series (metrics/timeseries.py, docs/observe.md):
# bounded ring-buffer history of cheap signals, flushed through the relay
# and served on the signed GET /timeseries
HVD_TIMESERIES = "HVD_TIMESERIES"                      # 0 disables the ring-buffer history
HVD_TIMESERIES_CAP = "HVD_TIMESERIES_CAP"              # raw-tier ring capacity, samples (default 512)
HVD_TIMESERIES_TIERS = "HVD_TIMESERIES_TIERS"          # downsampling tiers incl. raw (default 3)
HVD_TIMESERIES_FACTOR = "HVD_TIMESERIES_FACTOR"        # per-tier downsample factor (default 8)
HVD_TIMESERIES_FLUSH_SECONDS = "HVD_TIMESERIES_FLUSH_SECONDS"  # flush interval (default HVD_METRICS_PUSH_SECONDS)
HVD_TIMESERIES_SERVER_CAP = "HVD_TIMESERIES_SERVER_CAP"  # per-series sample cap in the server's per-rank doc (default 2048)
# online anomaly watchdog (horovod_tpu/observe/, docs/observe.md):
# detectors over the time-series history, alerts scope, auto-armed
# trace+profile windows
HVD_WATCH = "HVD_WATCH"                                # 0 disables the launcher-side watchdog
HVD_WATCH_WINDOW = "HVD_WATCH_WINDOW"                  # detector trailing window, samples (default 64)
HVD_WATCH_INTERVAL_SECONDS = "HVD_WATCH_INTERVAL_SECONDS"  # watchdog tick cadence (default 2)
HVD_WATCH_EWMA_ALPHA = "HVD_WATCH_EWMA_ALPHA"          # step-time EWMA smoothing (default 0.5)
HVD_WATCH_MAD_K = "HVD_WATCH_MAD_K"                    # regression threshold, robust sigmas above baseline (default 5)
HVD_WATCH_CONFIRM = "HVD_WATCH_CONFIRM"                # consecutive breaches before an alert (default 3)
HVD_WATCH_STRAGGLER_SKEW = "HVD_WATCH_STRAGGLER_SKEW"  # rank cadence / world median ratio read as straggling (default 1.3)
HVD_WATCH_BETA_DRIFT = "HVD_WATCH_BETA_DRIFT"          # measured/predicted µs-per-MiB ratio read as comm drift (default 2)
HVD_WATCH_SLO_BUDGET = "HVD_WATCH_SLO_BUDGET"          # tolerated SLO-breach sample fraction (default 0.01)
HVD_WATCH_BURN_RATE = "HVD_WATCH_BURN_RATE"            # breach-fraction / budget ratio that alerts (default 2)
HVD_WATCH_ARM = "HVD_WATCH_ARM"                        # 0 stops alerts from auto-arming trace windows (default 1)
HVD_WATCH_ARM_STEPS = "HVD_WATCH_ARM_STEPS"            # auto-armed trace+profile window length (default 8)
HVD_WATCH_ARM_MARGIN_STEPS = "HVD_WATCH_ARM_MARGIN_STEPS"  # arm start = newest observed step + margin (default 16)
HVD_WATCH_ARM_COOLDOWN_SECONDS = "HVD_WATCH_ARM_COOLDOWN_SECONDS"  # min spacing between auto-arms (default 120)
HVD_WATCH_EVICT = "HVD_WATCH_EVICT"                    # 1 feeds critical straggler alerts to the elastic removal path
HVD_BENCH_WATCH = "HVD_BENCH_WATCH"                    # 0 skips bench.py's watchdog detection leg
# control-plane flight recorder (horovod_tpu/observe/events.py,
# docs/observe.md): append-only correlation-ID-threaded event log of
# every lifecycle action, buffered in a per-process ring, flushed
# through the relay/batch path into the journaled `events` scope, and
# served on the signed GET /events (scripts/hvd_events.py console)
HVD_EVENTS = "HVD_EVENTS"                              # 0 disables the recorder (default on)
HVD_EVENTS_RING_CAP = "HVD_EVENTS_RING_CAP"            # per-process ring capacity, events (default 1024)
HVD_EVENTS_FLUSH_SECONDS = "HVD_EVENTS_FLUSH_SECONDS"  # worker-side flusher cadence (default HVD_METRICS_PUSH_SECONDS)
HVD_EVENTS_SERVER_CAP = "HVD_EVENTS_SERVER_CAP"        # server-side retained event cap per source (default 4096)
# peer-replicated state plane (elastic/peerstate.py,
# docs/fault_tolerance.md#the-peer-state-plane): async snapshots sharded
# to K peer hosts, restore-from-peers with storage-tier fallback
HVD_SNAPSHOT = "HVD_SNAPSHOT"                          # 1 enables the peer checkpoint tier (default off)
HVD_SNAPSHOT_SHARDS = "HVD_SNAPSHOT_SHARDS"            # shards one rank's snapshot splits into (default 4)
HVD_SNAPSHOT_KEEP = "HVD_SNAPSHOT_KEEP"                # own committed generations retained before GC (default 2)
HVD_SNAPSHOT_STORAGE_EVERY = "HVD_SNAPSHOT_STORAGE_EVERY"  # Nth save still hits the orbax storage tier (default 10)
HVD_SNAPSHOT_TIMEOUT_SECONDS = "HVD_SNAPSHOT_TIMEOUT_SECONDS"  # per shard push/pull HTTP budget (default 30)
HVD_SNAPSHOT_COPY = "HVD_SNAPSHOT_COPY"                # 1 also copies numpy leaves at enqueue — for loops that mutate arrays in place (default off)
HVD_PEER_REPLICAS = "HVD_PEER_REPLICAS"                # peer hosts holding each rank's shards, K (default 2)
HVD_BENCH_RESTORE = "HVD_BENCH_RESTORE"                # 0 skips bench.py's peer-restore leg
# chaos campaign engine (elastic/chaos.py, observe/invariants.py,
# scripts/hvd_chaos.py; docs/fault_tolerance.md#chaos-certification):
# scripted multi-fault scenarios run against an in-process elastic
# world and certified by invariant monitors over the flight recorder
HVD_CHAOS_WORLD = "HVD_CHAOS_WORLD"                    # workers per chaos scenario world (default 3)
HVD_CHAOS_STEP_SECONDS = "HVD_CHAOS_STEP_SECONDS"      # simulated train-step duration in the chaos world (default 0.01)
HVD_CHAOS_SNAPSHOT_EVERY = "HVD_CHAOS_SNAPSHOT_EVERY"  # steps between chaos-world snapshot commits (default 5)
HVD_CHAOS_TIMEOUT_SECONDS = "HVD_CHAOS_TIMEOUT_SECONDS"  # per-scenario wall budget before the runner declares a hang (default 30)
HVD_BENCH_CHAOS = "HVD_BENCH_CHAOS"                    # 0 skips bench.py's chaos campaign leg

DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024  # 64 MB, reference common.h:69
DEFAULT_CYCLE_TIME_MS = 5.0                        # reference common.h:67
FUSION_BUFFER_ATOMIC_UNIT = 64                     # reference common.h:94
DEFAULT_STALL_WARNING_SECONDS = 60.0               # reference stall_inspector.h:72
DEFAULT_HEARTBEAT_INTERVAL_SECONDS = 2.0           # elastic/heartbeat.py lease renewal
DEFAULT_TERM_GRACE_SECONDS = 5.0                   # run/run.py SIGTERM→SIGKILL grace
DEFAULT_HTTP_RETRIES = 2                           # run/http_client.py retry budget
DEFAULT_HTTP_BACKOFF_MS = 50.0                     # run/http_client.py backoff base
DEFAULT_RESTART_BACKOFF_SECONDS = 1.0              # run/run.py restart backoff base
DEFAULT_ELASTIC_TIMEOUT_SECONDS = 60.0             # elastic epoch wait/rebuild budget
DEFAULT_ELASTIC_MAX_FLAPS = 3                      # elastic/driver.py blocklist threshold
DEFAULT_AUTOTUNE_WINDOW_STEPS = 20                 # profile-guided measure/verify window
DEFAULT_AUTOTUNE_GUARD_BAND_PCT = 10.0             # rollback when realized lags predicted by more
DEFAULT_AUTOTUNE_CYCLE_FLUSH_STEPS = 0             # verified plans pinned forever unless set
DEFAULT_COMPRESSION_GUARD_STEPS = 25               # error-feedback residual-norm check cadence
DEFAULT_COMPRESSION_GUARD_FACTOR = 10.0            # residual divergence threshold (x baseline)
DEFAULT_DCN_GBPS = 25.0                            # modeled cross-host (DCN) bandwidth per host
DEFAULT_DCN_HOP_US = 10.0                          # modeled cross-host per-hop latency
DEFAULT_METRICS_BUCKET_FLOOR = 1e-4                # first latency bucket edge, seconds
DEFAULT_METRICS_BUCKET_FACTOR = 2.0                # geometric bucket growth
DEFAULT_METRICS_BUCKET_COUNT = 18                  # finite bucket count
DEFAULT_SERVE_MAX_BATCH = 8                        # serving/batching.py admission cap
DEFAULT_SERVE_MAX_WAIT_MS = 5.0                    # serving flush deadline from first admit
DEFAULT_SERVE_SLO_MS = 100.0                       # serving p99 latency objective
DEFAULT_SERVE_TIMEOUT_SECONDS = 30.0               # per-request wait budget
DEFAULT_SERVE_QUEUE_LIMIT = 4096                   # broker admission cap
DEFAULT_SERVE_LATENCY_BUCKET_FLOOR = 2.5e-4        # serving histogram floor, seconds
DEFAULT_SERVE_QUEUE_HIGH = 4.0                     # overload threshold, per replica
DEFAULT_SERVE_QUEUE_LOW = 0.5                      # idle threshold, per replica
DEFAULT_SERVE_HYSTERESIS_TICKS = 3                 # sustained ticks before an autoscale action
DEFAULT_SERVE_COOLDOWN_SECONDS = 10.0              # spacing between autoscale actions
DEFAULT_SERVE_MIN_REPLICAS = 1                     # autoscaler shrink floor
DEFAULT_LOSS_FETCH_STEPS = 16                      # trailing loss-fetch cadence (training.py)
DEFAULT_PREFETCH_DEPTH = 2                         # device prefetch queue depth (data/loader.py)
DEFAULT_CP_SHARDS = 8                              # run/store.py KV shard count
DEFAULT_RELAY_FLUSH_MS = 500.0                     # run/relay.py upstream batch cadence
DEFAULT_TIMESERIES_CAP = 512                       # metrics/timeseries.py raw-tier ring capacity
DEFAULT_TIMESERIES_TIERS = 3                       # downsampling tiers including the raw tier
DEFAULT_TIMESERIES_FACTOR = 8                      # per-tier downsample factor
DEFAULT_TIMESERIES_SERVER_CAP = 2048               # per-series cap in the server's per-rank doc
DEFAULT_WATCH_WINDOW = 64                          # observe/ detector trailing window, samples
DEFAULT_WATCH_INTERVAL_SECONDS = 2.0               # watchdog tick cadence
DEFAULT_WATCH_EWMA_ALPHA = 0.5                     # step-time regression EWMA smoothing
DEFAULT_WATCH_MAD_K = 5.0                          # regression threshold in robust sigmas
DEFAULT_WATCH_CONFIRM = 3                          # consecutive breaches before an alert
DEFAULT_WATCH_STRAGGLER_SKEW = 1.3                 # cadence / world-median straggler ratio
DEFAULT_WATCH_BETA_DRIFT = 2.0                     # measured/predicted comm-cost drift ratio
DEFAULT_WATCH_SLO_BUDGET = 0.01                    # tolerated SLO-breach sample fraction
DEFAULT_WATCH_BURN_RATE = 2.0                      # breach-fraction / budget alert ratio
DEFAULT_WATCH_ARM_STEPS = 8                        # auto-armed trace+profile window length
DEFAULT_WATCH_ARM_MARGIN_STEPS = 16                # arm start margin past the newest observed step
DEFAULT_WATCH_ARM_COOLDOWN_SECONDS = 120.0         # min spacing between auto-arms
DEFAULT_EVENTS_RING_CAP = 1024                     # observe/events.py per-process ring capacity
DEFAULT_EVENTS_FLUSH_SECONDS = 5.0                 # worker-side event flusher cadence
DEFAULT_EVENTS_SERVER_CAP = 4096                   # server-side retained events per source
DEFAULT_SNAPSHOT_SHARDS = 4                        # elastic/peerstate.py shards per rank snapshot
DEFAULT_SNAPSHOT_KEEP = 2                          # own committed generations kept before GC
DEFAULT_SNAPSHOT_STORAGE_EVERY = 10                # storage-tier save demotion cadence
DEFAULT_SNAPSHOT_TIMEOUT_SECONDS = 30.0            # per shard push/pull HTTP budget
DEFAULT_PEER_REPLICAS = 2                          # peer hosts holding each rank's shards
DEFAULT_ELASTIC_SILENT_GRACE_SECONDS = 0.0         # elastic/driver.py silent-member removal (0 = off)
DEFAULT_CHAOS_WORLD = 3                            # elastic/chaos.py workers per scenario
DEFAULT_CHAOS_STEP_SECONDS = 0.01                  # chaos-world simulated step duration
DEFAULT_CHAOS_SNAPSHOT_EVERY = 5                   # chaos-world snapshot commit cadence, steps
DEFAULT_CHAOS_TIMEOUT_SECONDS = 30.0               # per-scenario wall budget


def get_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def parse_bool(value: Optional[str], default: bool = False) -> bool:
    """The one truthiness rule for HVD_* flags — shared by the runtime
    (get_bool) and the launcher (which parses worker-bound env dicts),
    so both sides always agree on whether a knob is on."""
    if value is None or value == "":
        return default
    return value.strip().lower() in ("1", "true", "yes", "on")


def get_bool(name: str, default: bool = False) -> bool:
    return parse_bool(os.environ.get(name), default)


def get_str(name: str, default: Optional[str] = None) -> Optional[str]:
    v = os.environ.get(name)
    return v if v not in (None, "") else default


def fusion_threshold_bytes() -> int:
    n = get_int(HVD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES)
    # Round to the atomic unit so fused buffers stay divisible for
    # scatter-style ops (reference controller.cc:357-375).
    if n % FUSION_BUFFER_ATOMIC_UNIT:
        n = (n // FUSION_BUFFER_ATOMIC_UNIT + 1) * FUSION_BUFFER_ATOMIC_UNIT
    return n


def cycle_time_ms() -> float:
    return get_float(HVD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS)
