"""Topology discovery, initialization, and the SPMD rank model.

TPU-native re-design of Horovod's process/rank bootstrap
(reference: horovod/common/basics.py:22-212 and the extern-C API in
horovod/common/operations.cc:653-791).

Horovod's model: every *process* is a rank; ``hvd.init()`` ctypes-calls into a
C++ core that spawns a background thread and negotiates membership over
MPI/Gloo.  On TPU there is no MPI: the platform gives us the topology (the
ICI mesh), and XLA compiles collectives directly into the program.  So here:

* a **rank** is a *device* (TPU chip) in the global ``jax.sharding.Mesh``;
* the per-rank "script" is an SPMD function run under :func:`horovod_tpu.spmd`
  (``shard_map`` over the mesh) — inside it, :func:`rank` is the traced
  ``lax.axis_index``;
* the host Python process is a *controller* owning ``local_size()`` ranks;
  outside SPMD regions :func:`rank` reports the controller's process index
  (used for rank-0 gating: checkpoints, logging — same idiom as Horovod
  examples);
* multi-host bootstrap uses ``jax.distributed`` (the analog of Horovod's
  Gloo HTTP-rendezvous, reference horovod/common/gloo/gloo_context.cc:56-76),
  driven by ``HVD_*`` env vars set by the ``tpurun`` launcher.
"""

from __future__ import annotations

import hashlib
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from .utils import env as env_util
from .utils.logging import get_logger

log = get_logger(__name__)

# Reduction op constants, mirroring horovod.common.basics (reference
# horovod/common/basics.py:44-49 exposes horovod_reduce_op_average/_sum/
# _adasum read from the C++ enum in common/message.h).
Average = "Average"
Sum = "Sum"
Adasum = "Adasum"
Min = "Min"
Max = "Max"

#: Name of the global mesh axis spanning every rank (device).
AXIS = "hvd"
#: Hierarchical axes: "cross" spans hosts/slices (DCN), "local" spans the
#: devices within one host/slice (ICI) — the analog of Horovod's
#: LOCAL/CROSS communicators (reference horovod/common/common.h:110-114).
CROSS_AXIS = "cross"
LOCAL_AXIS = "local"


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call hvd.init() first."
        )


@dataclass
class _GlobalState:
    """Python analog of HorovodGlobalState (reference
    horovod/common/global_state.h:42) — minus the background thread, which
    XLA's async dispatch makes unnecessary on the hot path."""

    initialized: bool = False
    devices: tuple = ()
    mesh: Optional[Mesh] = None
    hmesh: Optional[Mesh] = None
    size: int = 0
    local_size: int = 0
    cross_size: int = 0
    process_index: int = 0
    process_count: int = 1
    platform: Optional[str] = None
    # Monotone id so cached jitted collectives can be invalidated on re-init.
    epoch: int = 0
    extra: dict = field(default_factory=dict)


_state = _GlobalState()
_lock = threading.Lock()
# The kwargs of the last successful init(), replayed by reinit() so an
# elastic membership change rebuilds against the same device selection.
_init_kwargs: dict = {}


class _SpmdContext(threading.local):
    """Tracks whether we are tracing inside an SPMD (shard_map) region and
    which mesh axes constitute the rank axis there."""

    def __init__(self) -> None:
        self.axes: Optional[tuple] = None  # e.g. ("hvd",) or ("cross","local")
        self.local_axis: Optional[str] = None


_ctx = _SpmdContext()


def _pick_devices(platform: Optional[str]) -> list:
    if platform is not None:
        return list(jax.devices(platform))
    return list(jax.devices())


#: in-checkout home of the persistent compile cache when
#: ``JAX_COMPILATION_CACHE_DIR`` does not place it.  The path is part of
#: the cache key, so it is fixed (derived the way runtime/native.py
#: derives ``build/``), never a temp dir.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _COMPILE_CACHE_DIR


def _place_compile_cache(platform: str) -> None:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir`
    before the first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    reads it itself and no directory is set here.  Every program is
    cached, not only those that compiled for over a second (JAX's
    default): a flax ``model.init`` is hundreds of small programs, and
    what gets cached must not depend on how long a compile happened to
    take.  The CPU mesh is the test plane: its programs are small, and a
    fresh checkout would pay the writes without ever reading them back,
    so it stays uncached."""
    if platform == "cpu":
        return
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
    _key_the_cache_by_scope_names()


def _key_the_cache_by_scope_names() -> None:
    """Fold the program's device-scope names (``models/scopes.py``) into
    the persistent cache's key.  JAX keys a program with its debug info
    stripped (``jax_compilation_cache_include_metadata_in_key`` is off, and
    on it would key by source paths and line numbers too), so a program
    that differs from a cached one only in its ``jax.named_scope``s loads
    the cached executable with the names of whoever compiled first, and a
    device trace reads scopes the running code does not emit
    (docs/profiling.md; ``tests/test_part_scopes.py`` shows both).  JAX
    0.9.0 hashes ``cache_key.custom_hook()`` into every key."""
    from jax._src import cache_key

    from .models import scopes

    digest = hashlib.sha256(
        "\n".join(scopes.documented()).encode()).hexdigest()[:16]
    cache_key.custom_hook = lambda: f"hvd_scopes:{digest}"


def init(
    *,
    platform: Optional[str] = None,
    devices: Optional[Sequence[Any]] = None,
    local_size: Optional[int] = None,
    comm: Optional[Sequence[int]] = None,
) -> None:
    """Initialize the framework: discover topology and build the global mesh.

    Mirrors ``hvd.init()`` (reference horovod/common/basics.py:33-65 →
    operations.cc:655 ``horovod_init``): idempotent, and accepts ``comm=``
    (a subset of ranks) the way Horovod accepts a sub-communicator.

    Args:
      platform: force a JAX platform ("tpu" / "cpu"); default = default
        backend.  Tests use ``platform="cpu"`` with
        ``--xla_force_host_platform_device_count=N`` — the analog of the
        reference's ``mpirun -np 2 -H localhost:2`` localhost simulation
        (reference docker-compose.test.yml:52).
      devices: explicit device list (overrides ``platform``).
      local_size: devices per "node" for the hierarchical (cross, local)
        mesh.  Defaults to this process's local device count; on a single
        process it can be overridden to simulate multiple nodes.
      comm: optional subset of global device indices to form the world from
        (reference operations.cc:655-663 ranks argument).
    """
    global _state, _init_kwargs
    with _lock:
        if _state.initialized:
            return
        _init_kwargs = {
            "platform": platform, "devices": devices,
            "local_size": local_size, "comm": comm,
        }
        # Elastic membership: adopt the committed epoch FIRST — a shrink
        # that raced this process's start-up rewrote the world, and the
        # identity/controller env must be read post-adoption (the ack
        # doubles as the driver's start barrier).
        try:
            from .elastic import membership

            membership.attach()
        except Exception as e:  # noqa: BLE001 — membership must never
            log.warning("membership attach failed: %s", e)  # block init
        if os.environ.get("HVD_COORDINATOR_ADDR"):
            # Multi-host bootstrap: the tpurun launcher sets these.  This is
            # the rendezvous step — the analog of GlooContext::Initialize's
            # HTTP KV-store handshake (reference gloo/gloo_context.cc:113-157).
            # Must run before anything touches the XLA backend; if the user
            # (or a passed `devices=` argument) already initialized it, fall
            # back to env-based process identity — the eager planes still
            # span the job through the native controller.
            try:
                jax.distributed.initialize(
                    coordinator_address=os.environ["HVD_COORDINATOR_ADDR"],
                    num_processes=int(os.environ.get("HVD_NUM_PROCESSES", "1")),
                    process_id=int(os.environ.get("HVD_PROCESS_ID", "0")),
                )
            except RuntimeError as e:
                # Only tolerate "backend already initialized" — a genuine
                # bootstrap failure (unreachable coordinator) must not
                # silently shrink the job to per-host training.
                msg = str(e)
                tolerable = ("must be called before" in msg
                             or "already initialized" in msg
                             or "only be called once" in msg)
                if not tolerable:
                    raise
                log.warning(
                    "jax.distributed bootstrap unavailable (%s); using "
                    "env-based process identity", e,
                )

        devs = list(devices) if devices is not None else _pick_devices(platform)
        # Process-major ordering so each controller's devices are contiguous
        # — this makes the (cross, local) reshape put intra-host links on
        # the fast axis, mirroring MPI_Comm_split_type(..., SHARED)
        # (reference mpi/mpi_context.cc).
        devs.sort(key=lambda d: (d.process_index, d.id))
        if comm is not None:
            devs = [devs[i] for i in comm]

        size = len(devs)
        if size == 0:
            raise RuntimeError("no devices available for horovod_tpu.init()")
        _place_compile_cache(devs[0].platform)

        # identity must come from the backend the mesh devices live on —
        # jax.process_count()/process_index() default to the default
        # backend, which can be a single-process accelerator plugin while
        # the (e.g. CPU) mesh backend spans a jax.distributed job
        mesh_platform = devs[0].platform
        try:
            jax_nproc = jax.process_count(mesh_platform)
            jax_pidx = jax.process_index(mesh_platform)
        except Exception:  # noqa: BLE001 — backend without process info
            jax_nproc, jax_pidx = jax.process_count(), jax.process_index()

        if local_size is None:
            mine = [d for d in devs if d.process_index == jax_pidx]
            local_size = len(mine) if mine else size
        if size % local_size != 0:
            raise ValueError(
                f"global size {size} not divisible by local_size {local_size}"
            )
        cross_size = size // local_size

        mesh = Mesh(np.asarray(devs, dtype=object), (AXIS,))
        hmesh = Mesh(
            np.asarray(devs, dtype=object).reshape(cross_size, local_size),
            (CROSS_AXIS, LOCAL_AXIS),
        )

        # Process identity: jax.distributed when it spans processes, else
        # the HVD_* env set by the launcher (tpurun / function-mode run()) —
        # the native-controller-only deployment, where the XLA plane stays
        # per-process but the eager control/data planes span the job
        # (reference gloo_context.cc:128-156 reads HOROVOD_RANK/SIZE the
        # same way).  Elastic jobs always use the env identity: membership
        # epochs rewrite HVD_NUM_PROCESSES/HVD_PROCESS_ID on every world
        # change, while jax.distributed cannot be resized in process and
        # would pin the stale pre-shrink world.
        if jax_nproc > 1 and not env_util.get_bool(env_util.HVD_ELASTIC):
            process_index, process_count = jax_pidx, jax_nproc
        else:
            process_count = env_util.get_int(env_util.HVD_NUM_PROCESSES, 1)
            process_index = env_util.get_int(env_util.HVD_PROCESS_ID, 0)

        _state = _GlobalState(
            initialized=True,
            devices=tuple(devs),
            mesh=mesh,
            hmesh=hmesh,
            size=size,
            local_size=local_size,
            cross_size=cross_size,
            process_index=process_index,
            process_count=process_count,
            platform=devs[0].platform,
            epoch=_state.epoch + 1,
        )
        log.info(
            "initialized: size=%d local_size=%d cross_size=%d platform=%s",
            size, local_size, cross_size, _state.platform,
        )
        try:
            from .runtime import eager_controller

            eager_controller.setup_from_env(
                _state.process_index, _state.process_count
            )
        except Exception as e:  # noqa: BLE001
            # A requested native controller that can't start (e.g. its port
            # is taken on this host) means a multi-process job with no
            # transport — fail loudly rather than deadlock later.
            if env_util.get_str(env_util.HVD_CONTROLLER) == "native" \
                    and _state.process_count > 1:
                raise
            log.warning("eager controller setup failed: %s", e)
        # Env-driven timeline startup, as the reference core does when
        # HOROVOD_TIMELINE is set (reference operations.cc:392-400):
        # initialize() is a no-op when HVD_TIMELINE/HVD_TRACE_DIR is unset.
        from .timeline.timeline import timeline

        timeline.initialize()
        # Per-host relay election (run/relay.py, HVD_RELAY=1): local
        # rank 0 stands up the aggregator BEFORE the pusher/heartbeat
        # resolve their control endpoint, so this host's batchable
        # traffic rides one upstream connection from the first beat.
        try:
            from .run import relay

            relay.start_from_env()
        except Exception as e:  # noqa: BLE001 — the relay is an
            log.warning("relay setup failed: %s", e)  # optimization
        # Live metrics export: when the launcher stood up a rendezvous
        # server and passed its address (HVD_METRICS_KV_*), start pushing
        # this rank's snapshots so the launcher's GET /metrics sees us.
        try:
            from .metrics.push import start_pusher_from_env

            start_pusher_from_env(_state.process_index)
        except Exception as e:  # noqa: BLE001 — metrics must never
            log.warning("metrics pusher setup failed: %s", e)  # block init
        # Telemetry history flusher (metrics/timeseries.py): ships the
        # ring-buffer series the watchdog's detectors read, and polls
        # the observe/arm broadcast so an alert can arm this rank's
        # trace+profile window off the step path.
        try:
            from .metrics.timeseries import start_flusher_from_env

            start_flusher_from_env(_state.process_index)
        except Exception as e:  # noqa: BLE001 — history must never
            log.warning("timeseries flusher setup failed: %s", e)  # block init
        # Heartbeat leases + coordinated-abort polling (elastic/
        # heartbeat.py): active when the launcher exported rendezvous
        # wiring and this is a multi-process job.
        try:
            from .elastic.heartbeat import start_from_env

            start_from_env()
        except Exception as e:  # noqa: BLE001 — liveness reporting must
            log.warning("heartbeat setup failed: %s", e)  # never block init


def shutdown() -> None:
    """Tear down state (reference horovod/common/basics.py:67-70 →
    operations.cc ``horovod_shutdown``)."""
    global _state
    try:
        from .runtime import eager_controller

        eager_controller.shutdown()
    except Exception:  # noqa: BLE001
        pass
    try:
        from .timeline.timeline import timeline

        timeline.shutdown()
    except Exception:  # noqa: BLE001
        pass
    try:
        from .metrics.push import stop_pusher

        stop_pusher()  # flushes one final snapshot to the launcher
    except Exception:  # noqa: BLE001
        pass
    try:
        from .metrics.timeseries import stop_flusher

        stop_flusher()  # final history flush
    except Exception:  # noqa: BLE001
        pass
    try:
        from .elastic import heartbeat

        heartbeat.stop()
    except Exception:  # noqa: BLE001
        pass
    try:
        from .run import relay

        relay.stop()  # drains one final upstream flush
    except Exception:  # noqa: BLE001
        pass
    with _lock:
        _state = _GlobalState(epoch=_state.epoch + 1)


def reinit() -> None:
    """Tear down and re-initialize in process against the *current*
    environment — the elastic-membership rebuild (docs/fault_tolerance.md):
    after the driver commits a new epoch, `elastic/membership.py` rewrites
    ``HVD_NUM_PROCESSES``/``HVD_PROCESS_ID``/``HVD_CONTROLLER_ADDR`` and
    calls this, which re-creates the mesh, reconnects the eager controller
    client to the epoch's fresh ControllerServer, and restarts the
    heartbeat/metrics daemons — no process relaunch, no JIT cache loss
    beyond the step functions that must re-trace over the new mesh
    (training.make_train_step rebuilds those lazily via the mesh epoch).

    The device selection of the last :func:`init` is replayed; callers
    that never initialized get a plain :func:`init`."""
    kwargs = dict(_init_kwargs)
    shutdown()
    init(**kwargs)


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def mesh() -> Mesh:
    """The global 1-D device mesh; axis name :data:`AXIS`."""
    return _require_init().mesh


def hierarchical_mesh() -> Mesh:
    """The 2-D (cross, local) mesh for hierarchical collectives."""
    return _require_init().hmesh


def size() -> int:
    """Total number of ranks (devices)."""
    return _require_init().size


def local_size() -> int:
    return _require_init().local_size


def cross_size() -> int:
    return _require_init().cross_size


def in_spmd() -> bool:
    """True while tracing inside an hvd SPMD region."""
    return _ctx.axes is not None


def _spmd_axes() -> Optional[tuple]:
    return _ctx.axes


def rank():
    """This rank's index.

    Inside an SPMD region: the traced per-device index along the rank axis
    (``lax.axis_index``).  Outside: the controller process index, which is
    what rank-0 gating in user scripts needs (reference idiom:
    examples/tensorflow2_mnist.py ``if hvd.rank() == 0``).
    """
    st = _require_init()
    if _ctx.axes is not None:
        from jax import lax

        if len(_ctx.axes) == 1:
            return lax.axis_index(_ctx.axes[0])
        # (cross, local) → flat rank = cross * local_size + local
        return (
            lax.axis_index(_ctx.axes[0]) * st.local_size
            + lax.axis_index(_ctx.axes[1])
        )
    return st.process_index


def local_rank():
    """Rank within the node (reference basics.py:152-160)."""
    st = _require_init()
    if _ctx.axes is not None:
        from jax import lax

        if len(_ctx.axes) == 2:
            return lax.axis_index(_ctx.axes[1])
        return lax.axis_index(_ctx.axes[0]) % st.local_size
    return 0


def cross_rank():
    """Node index of this rank (reference LOCAL/CROSS communicator split,
    horovod/common/common.h:110-114)."""
    st = _require_init()
    if _ctx.axes is not None:
        from jax import lax

        if len(_ctx.axes) == 2:
            return lax.axis_index(_ctx.axes[0])
        return lax.axis_index(_ctx.axes[0]) // st.local_size
    return st.process_index


def process_rank() -> int:
    return _require_init().process_index


def process_size() -> int:
    return _require_init().process_count


def is_homogeneous() -> bool:
    """All nodes have the same local_size — always true for a TPU slice
    (reference basics.py:171-179)."""
    _require_init()
    return True


# --- capability probes, mirroring horovod.common.util/basics feature checks
# (reference horovod/common/basics.py:83-150: mpi_enabled, mpi_built,
#  gloo_enabled, nccl_built, ddl_built, ccl_built, cuda_built, rocm_built).
def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """The one true data plane here."""
    return True


def mpi_threads_supported() -> bool:
    return False
