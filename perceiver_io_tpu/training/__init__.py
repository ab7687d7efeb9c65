from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.training.optim import (
    constant_with_warmup,
    cosine_with_warmup,
    make_optimizer,
)
from perceiver_io_tpu.training.state import TrainState
from perceiver_io_tpu.training.losses import (
    classification_loss_fn,
    clm_loss_fn,
    masked_lm_loss_fn,
    mse_loss_fn,
)
from perceiver_io_tpu.training.optim import freeze_mask
_MODULE = _STARTUP.open("startup/import", package=__name__, module="checkpoint")
from perceiver_io_tpu.training.checkpoint import (
    CheckpointManager,
    ResumePreflightError,
    config_from_dict,
    config_to_dict,
    load_config,
    load_params_into,
    load_pretrained,
    save_config,
    save_pretrained,
    sharding_fingerprint,
)
_STARTUP.close(_MODULE)
_MODULE = _STARTUP.open("startup/import", package=__name__, module="faults")
from perceiver_io_tpu.training.faults import (
    DivergenceHalt,
    DivergenceSentinel,
    FetchRetriesExhausted,
    PreemptionGuard,
    QuarantineIterator,
    RetryPolicy,
    SentinelConfig,
    call_with_retry,
    fetch_retry_emitter,
)
_STARTUP.close(_MODULE)
from perceiver_io_tpu.training.metrics import MetricsLogger
from perceiver_io_tpu.training.prefix_dropout import (
    prefix_keep_count,
    sample_prefix_keep_idx,
    with_prefix_keep_idx,
)
_MODULE = _STARTUP.open("startup/import", package=__name__, module="trainer")
from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig
_STARTUP.close(_MODULE)

__all__ = [
    "constant_with_warmup",
    "cosine_with_warmup",
    "make_optimizer",
    "TrainState",
    "classification_loss_fn",
    "clm_loss_fn",
    "masked_lm_loss_fn",
    "mse_loss_fn",
    "freeze_mask",
    "CheckpointManager",
    "ResumePreflightError",
    "sharding_fingerprint",
    "config_from_dict",
    "config_to_dict",
    "load_config",
    "load_params_into",
    "load_pretrained",
    "save_config",
    "save_pretrained",
    "MetricsLogger",
    "DivergenceHalt",
    "DivergenceSentinel",
    "FetchRetriesExhausted",
    "PreemptionGuard",
    "QuarantineIterator",
    "RetryPolicy",
    "SentinelConfig",
    "call_with_retry",
    "fetch_retry_emitter",
    "prefix_keep_count",
    "sample_prefix_keep_idx",
    "with_prefix_keep_idx",
    "Trainer",
    "TrainerConfig",
]

_STARTUP.close(_IMPORTING)
