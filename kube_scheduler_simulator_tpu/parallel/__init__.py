from .mesh import (  # noqa: F401
    batched_step,
    initialize_distributed,
    make_mesh,
    shard_workload,
    sharded_step,
)
