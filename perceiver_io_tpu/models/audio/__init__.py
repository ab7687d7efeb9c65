from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.models.audio.symbolic import SymbolicAudioModel, SymbolicAudioModelConfig

__all__ = [
    "SymbolicAudioModel",
    "SymbolicAudioModelConfig",
]

_STARTUP.close(_IMPORTING)
