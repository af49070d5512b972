from . import partition, shardctx, spec_verify
from .partition import Partitioner, data_axes
from .shardctx import abstract_mesh, ambient_mesh, axis_size, constrain, host_mesh
from .spec_verify import (
    MODEL_AXIS,
    ShardPlan,
    plan_shards,
    spec_verify_sharded,
)

__all__ = [
    "MODEL_AXIS",
    "Partitioner",
    "ShardPlan",
    "abstract_mesh",
    "ambient_mesh",
    "axis_size",
    "constrain",
    "data_axes",
    "host_mesh",
    "partition",
    "plan_shards",
    "shardctx",
    "spec_verify",
    "spec_verify_sharded",
]
