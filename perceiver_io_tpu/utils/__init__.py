from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.utils.flops import (  # noqa: F401
    ComputeEstimator,
    ModelInfo,
    num_model_params,
    num_training_steps,
    num_training_tokens,
    training_flops,
)
from perceiver_io_tpu.utils.laws import (  # noqa: F401
    ScalingLaw,
    fit_power_law,
    fit_scaling_exponents,
    fit_scaling_law,
)
from perceiver_io_tpu.utils.profiling import StepTimer, trace  # noqa: F401

__all__ = [
    "ComputeEstimator",
    "ModelInfo",
    "num_model_params",
    "num_training_steps",
    "num_training_tokens",
    "training_flops",
    "ScalingLaw",
    "fit_power_law",
    "fit_scaling_exponents",
    "fit_scaling_law",
    "StepTimer",
    "trace",
]

_STARTUP.close(_IMPORTING)
