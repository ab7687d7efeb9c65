"""The accelerator check of the measuring entry points."""

from __future__ import annotations

import jax


def require_tpu(what: str):
    """The first device, which must be a TPU. A measuring path that finds no
    chip exits non-zero: on any other backend the Pallas kernels run in
    interpret mode (``ops.flash_attention._interpret_default``) and every
    time would be the interpreter's, under a device metric's name."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"{what} needs a TPU; JAX found platform {device.platform!r} "
            f"({device.device_kind}). Tests and count-only gates run on the CPU; "
            "a time or a rate comes only from the chip."
        )
    return device
