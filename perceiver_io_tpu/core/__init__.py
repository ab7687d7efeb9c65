from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.core.adapter import (
    ClassificationOutputAdapter,
    TiedTokenOutputAdapter,
    TokenInputAdapter,
    TokenInputAdapterWithRotarySupport,
    TokenOutputAdapter,
    TrainableQueryProvider,
)
from perceiver_io_tpu.core.attention import KVCache, MultiHeadAttention, init_kv_cache
from perceiver_io_tpu.core.config import (
    CausalSequenceModelConfig,
    ClassificationDecoderConfig,
    DecoderConfig,
    EncoderConfig,
    PerceiverARConfig,
    PerceiverIOConfig,
)
from perceiver_io_tpu.core.modules import (
    MLP,
    CausalSequenceModel,
    CrossAttention,
    CrossAttentionLayer,
    PerceiverAR,
    PerceiverDecoder,
    PerceiverEncoder,
    PerceiverIO,
    SelfAttention,
    SelfAttentionBlock,
    SelfAttentionLayer,
)
from perceiver_io_tpu.core.position import (
    FourierPositionEncoding,
    RotaryPositionEmbedding,
    frequency_position_encoding,
    positions,
)

__all__ = [
    "ClassificationOutputAdapter",
    "TiedTokenOutputAdapter",
    "TokenInputAdapter",
    "TokenInputAdapterWithRotarySupport",
    "TokenOutputAdapter",
    "TrainableQueryProvider",
    "KVCache",
    "MultiHeadAttention",
    "init_kv_cache",
    "CausalSequenceModelConfig",
    "ClassificationDecoderConfig",
    "DecoderConfig",
    "EncoderConfig",
    "PerceiverARConfig",
    "PerceiverIOConfig",
    "MLP",
    "CausalSequenceModel",
    "CrossAttention",
    "CrossAttentionLayer",
    "PerceiverAR",
    "PerceiverDecoder",
    "PerceiverEncoder",
    "PerceiverIO",
    "SelfAttention",
    "SelfAttentionBlock",
    "SelfAttentionLayer",
    "FourierPositionEncoding",
    "RotaryPositionEmbedding",
    "frequency_position_encoding",
    "positions",
]

_STARTUP.close(_IMPORTING)
