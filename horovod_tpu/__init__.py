"""horovod_tpu — a TPU-native distributed training framework with the
capability set of Horovod 0.16.1 (reference: bigo-sg/horovod, mounted at
/root/reference), rebuilt for the JAX/XLA stack.

Architecture (vs the reference, see SURVEY.md):

* Control plane: TCP rendezvous + background controller (tensor fusion,
  response cache, timeline, stall detection) instead of MPI
  (``horovod/common/operations.cc``).
* Data plane: XLA collectives over ICI/DCN (``lax.psum`` & friends, sharded
  ``jit``) instead of NCCL; host tensors ride the native C++ ring backend.
* Two tiers: SPMD (jit over a device Mesh — the TPU hot path) and eager
  multi-process (Horovod parity for per-tensor host-driven collectives).

Top-level surface mirrors ``import horovod.torch as hvd`` /
``horovod.tensorflow``: init/rank/size, allreduce/allgather/broadcast
(+async), DistributedOptimizer, broadcast_parameters, Compression.
"""

__version__ = "0.1.0"

import time as _time

_IMPORT_START_NS = _time.perf_counter_ns()  # span "import": first line

from .common.basics import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    num_devices,
    local_num_devices,
    mpi_threads_supported,
)
from .ops.collective_ops import (  # noqa: F401
    Sum,
    Average,
    allreduce,
    allreduce_async,
    grouped_allreduce,
    grouped_allreduce_async,
    allgather,
    allgather_async,
    broadcast,
    broadcast_async,
    broadcast_object,
    allgather_object,
    barrier,
    reducescatter,
    alltoall,
    synchronize,
    poll,
    wait,
    set_default_spmd_axis,
)
from .compression import Compression  # noqa: F401
from .jax import (  # noqa: F401
    DistributedOptimizer,
    distributed_value_and_grad,
    broadcast_parameters,
    broadcast_optimizer_state,
)
from . import parallel  # noqa: F401
from . import metrics  # noqa: F401  (hvd.metrics.snapshot() et al.)
from . import trace  # noqa: F401  (hvd.trace.summary() / merge tooling)
from . import doctor  # noqa: F401  (hvd.doctor.report() / rule catalog)
from . import elastic  # noqa: F401  (hvd.elastic.run / State, docs/elastic.md)
from . import serving  # noqa: F401  (hvd.serving.serve / stats, docs/serving.md)
from .common import profiler  # noqa: F401
from .controller.bucket_scheduler import (  # noqa: F401
    BucketScheduler,
    partition_buckets,
    plan_from_compiled,
)

# Span "import": last line (see common/profiler.py; jax is most of it).
profiler.record_span("import", _IMPORT_START_NS, _time.perf_counter_ns())
