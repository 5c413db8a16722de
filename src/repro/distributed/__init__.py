"""Distribution: the flow-table tier's ('shard', 'data') mesh and shardings."""

from repro.distributed.sharding import (
    as_flow_mesh,
    named_sharding_tree,
    flow_shard_mesh,
    flow_table_sharding,
)
