from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.parallel.dist import (
    is_main_process,
    main_process_only,
    maybe_initialize_distributed,
    process_count,
    process_index,
)
from perceiver_io_tpu.parallel.mesh import (
    batch_sharding,
    fsdp_param_shardings,
    param_shardings,
    make_mesh,
    mesh_from_spec,
    parse_mesh_spec,
    replicated,
    required_devices,
    shard_batch,
)
from perceiver_io_tpu.parallel.ring_attention import (
    make_ring_cross_attention,
    make_ring_self_attention,
    ring_self_attention,
    seq_sharded_cross_attention,
)

__all__ = [
    "is_main_process",
    "main_process_only",
    "maybe_initialize_distributed",
    "process_count",
    "process_index",
    "batch_sharding",
    "fsdp_param_shardings",
    "param_shardings",
    "make_mesh",
    "replicated",
    "shard_batch",
    "make_ring_cross_attention",
    "make_ring_self_attention",
    "ring_self_attention",
    "seq_sharded_cross_attention",
    "mesh_from_spec",
    "parse_mesh_spec",
    "required_devices",
]

_STARTUP.close(_IMPORTING)
