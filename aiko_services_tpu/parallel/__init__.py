from .mesh import (                                           # noqa: F401
    MESH_AXIS_ORDER, create_mesh, get_mesh, named_sharding, partition_spec,
    shard_pytree, filter_specs)
from .attention import (                                      # noqa: F401
    attention_reference, flash_attention, paged_attention,
    paged_attention_reference, ring_attention, ring_attention_sharded, sp_decode_attention,
    sp_decode_attention_sharded, ulysses_attention,
    ulysses_attention_sharded)
from .distributed import (                                    # noqa: F401
    global_mesh, initialize_distributed, is_distributed, process_count,
    process_index, shutdown_distributed)
