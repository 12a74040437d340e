"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod (the joapolarbear fork of 0.19 with per-rank
auto-profiling).

The data plane is XLA collectives over ICI/DCN (no MPI/NCCL/Gloo); the
rank model is SPMD over a ``jax.sharding.Mesh`` (see core.py); the eager
control path, launcher, timeline, and autotuner mirror the reference's
C++/Python runtime (see SURVEY.md at the repo root for the blueprint).

Typical use::

    import horovod_tpu as hvd

    hvd.init()

    @hvd.spmd
    def train_step(params, batch):
        grads = jax.grad(loss_fn)(params, batch)
        grads = hvd.allreduce_gradients(grads)
        return update(params, grads)
"""

__version__ = "0.1.0"

from .core import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    rank,
    local_rank,
    cross_rank,
    size,
    local_size,
    cross_size,
    process_rank,
    process_size,
    is_homogeneous,
    mesh,
    hierarchical_mesh,
    in_spmd,
    Average,
    Sum,
    Adasum,
    Min,
    Max,
    AXIS,
    CROSS_AXIS,
    LOCAL_AXIS,
    mpi_enabled,
    mpi_built,
    gloo_enabled,
    gloo_built,
    nccl_built,
    ddl_built,
    ccl_built,
    cuda_built,
    rocm_built,
    xla_built,
    mpi_threads_supported,
)
from .spmd import (  # noqa: F401
    spmd,
    rank_context,
    sharded,
    replicated,
    put_per_rank,
    get_per_rank,
)
from .ops import (  # noqa: F401
    allreduce,
    grouped_allreduce,
    allgather,
    allgatherv,
    broadcast,
    alltoall,
    reducescatter,
    allreduce_gradients,
    Compression,
)
from .ops.compression import ErrorFeedback  # noqa: F401
from .parallel.hierarchical import two_level_allreduce  # noqa: F401
from .ops.collectives import ProcessSet  # noqa: F401
from .ops.sparse import (  # noqa: F401
    IndexedSlices,
    allreduce_indexed_slices,
    embedding_grad_as_slices,
)
from .eager import (  # noqa: F401
    allreduce_ as eager_allreduce,
    allgather_ as eager_allgather,
    broadcast_ as eager_broadcast,
    broadcast_object,
    allgather_object,
)
from .optim import (  # noqa: F401
    DistributedOptimizer,
    DistributedGradientTape,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_variables,
)
from .elastic.join import join, join_allreduce  # noqa: F401
from .elastic import (  # noqa: F401
    ElasticState,
    HorovodAbortError,
    abort,
)
