from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.data.loader import Batches, shard_indices_for_process

__all__ = [
    "Batches",
    "shard_indices_for_process",
]

_STARTUP.close(_IMPORTING)
