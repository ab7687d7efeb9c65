"""Device ms a step of what XLA makes of the attention blocks around the
flash kernels: everything under ``cross_attend`` / ``self_attend`` that is
neither a ``flash_*`` kernel nor the block's MLP. Prints the parts:
``qkv_proj``, ``o_proj``, ``rotary``, ``norm`` and what is left in the block."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "attention_xla_device_ms.train",
                       lambda name, row: scopes.in_attention_block(row) and scopes.KERNEL_NAME_HOLDS not in name.lower(),
                       parts=lambda name, row: row["layer"])
