from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.models.text.classifier import TextClassifier, TextClassifierConfig
from perceiver_io_tpu.models.text.clm import CausalLanguageModel, CausalLanguageModelConfig
from perceiver_io_tpu.models.text.common import TextEncoderConfig
from perceiver_io_tpu.models.text.mlm import MaskedLanguageModel, MaskedLanguageModelConfig, TextDecoderConfig

__all__ = [
    "TextClassifier",
    "TextClassifierConfig",
    "CausalLanguageModel",
    "CausalLanguageModelConfig",
    "TextEncoderConfig",
    "MaskedLanguageModel",
    "MaskedLanguageModelConfig",
    "TextDecoderConfig",
]

_STARTUP.close(_IMPORTING)
