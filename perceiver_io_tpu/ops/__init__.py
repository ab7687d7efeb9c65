"""TPU kernels (Pallas) and fused ops."""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

from perceiver_io_tpu.ops.flash_attention import flash_attention, flash_supported
from perceiver_io_tpu.ops.quant import dequantize_weights, quantize_weights

__all__ = ["flash_attention", "flash_supported", "quantize_weights", "dequantize_weights"]

_STARTUP.close(_IMPORTING)
