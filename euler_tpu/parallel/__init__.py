from euler_tpu.parallel.mesh import (  # noqa: F401
    data_sharding,
    make_mesh,
    mesh_shape_for,
    replicated,
    shard_batch,
)
from euler_tpu.parallel.sharded_embedding import (  # noqa: F401
    ShardedEmbedding,
    apply_param_shardings,
    param_shardings,
)
from euler_tpu.parallel.device_sampler import (  # noqa: F401
    DeviceNeighborTable,
    build_alias_tables,
    fuse_tables,
    logical_rows,
    make_table_gather,
    sample_fanout_rows,
    sample_fanout_rows_fused,
    sample_hop,
    sample_hop_fused,
    store_rows,
    stored_info,
    take_rows,
)
from euler_tpu.parallel.placement import (  # noqa: F401
    put_replicated,
    put_row_sharded,
)
from euler_tpu.parallel.device_walk import (  # noqa: F401
    DeviceNodeSampler,
    gen_pair_rows,
    sample_global_rows,
    walk_rows,
)
from euler_tpu.parallel.feature_store import DeviceFeatureStore  # noqa: F401
from euler_tpu.parallel.partitioned_store import (  # noqa: F401
    PartitionedFeatureStore,
    hub_routed_take,
)
from euler_tpu.parallel.ring_exchange import (  # noqa: F401
    allgather_lookup,
    pick_lookup_strategy,
    ring_lookup,
)
from euler_tpu.parallel.train import make_spmd_train_step, spmd_init  # noqa: F401
