from tpu_resnet.parallel.mesh import (
    batch_sharding,
    check_divisible,
    create_mesh,
    fit_mesh,
    local_batch_size,
    replicated,
    staged_batch_sharding,
)
from tpu_resnet.parallel.multihost import initialize, is_primary
from tpu_resnet.parallel.partition import (
    PARTITION_MODES,
    StatePartitioner,
    check_partition_mode,
    make_partitioner,
)

__all__ = [
    "batch_sharding",
    "check_divisible",
    "create_mesh",
    "fit_mesh",
    "local_batch_size",
    "replicated",
    "staged_batch_sharding",
    "initialize",
    "is_primary",
    "PARTITION_MODES",
    "StatePartitioner",
    "check_partition_mode",
    "make_partitioner",
]
