from koordinator_tpu.parallel.mesh import (  # noqa: F401
    CLUSTER_AXIS,
    cluster_mesh,
    make_mesh,
    node_sharding,
    pow2_device_count,
    replicated_sharding,
    score_sharding,
    shard_cluster_snapshot,
    shard_snapshot_for_scoring,
    shard_snapshot_for_assign,
    snapshot_partition_specs,
    snapshot_shardings,
)
from koordinator_tpu.parallel.shard_assign import (  # noqa: F401
    greedy_assign_sharded,
    greedy_assign_waves,
)
