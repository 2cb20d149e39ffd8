"""Parallelism substrate: device meshes, shardings, and (TPU extensions)
sequence/context parallelism.

The reference implements data parallelism only (SURVEY.md §2.3); the mesh
utilities here are its substrate plus the axes future strategies hang off."""

from . import hierarchical, moe, pipeline, sequence  # noqa: F401
from .moe import (  # noqa: F401
    grouped_gated_mlp,
    moe_apply,
    moe_apply_dense,
    moe_apply_held,
    sigmoid_top_k,
    softmax_top_k,
    switch_aux_loss,
)
from .hierarchical import (  # noqa: F401
    hierarchical_allgather,
    hierarchical_allreduce,
)
from .pipeline import (  # noqa: F401
    pipeline_1f1b,
    collect_from_last_stage,
    pipeline_apply,
    pipeline_loss,
    stack_stage_params,
)
from .mesh import (  # noqa: F401
    DATA_AXIS,
    common_mesh,
    make_mesh,
    make_multislice_mesh,
    mesh,
    set_mesh,
    reset_mesh,
    data_sharding,
    replicated_sharding,
    shard_batch,
    sharding_axes,
    replicate,
)
