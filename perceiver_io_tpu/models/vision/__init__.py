from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.models.vision.image_classifier import (
    ImageClassifier,
    ImageClassifierConfig,
    ImageEncoderConfig,
    ImageInputAdapter,
)
from perceiver_io_tpu.models.vision.optical_flow import (
    OpticalFlow,
    OpticalFlowConfig,
    OpticalFlowDecoderConfig,
    OpticalFlowEncoderConfig,
)

__all__ = [
    "ImageClassifier",
    "ImageClassifierConfig",
    "ImageEncoderConfig",
    "ImageInputAdapter",
    "OpticalFlow",
    "OpticalFlowConfig",
    "OpticalFlowDecoderConfig",
    "OpticalFlowEncoderConfig",
]

_STARTUP.close(_IMPORTING)
