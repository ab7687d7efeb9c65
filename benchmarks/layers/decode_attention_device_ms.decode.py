"""Device ms a decode step spent in attention: ``cross_attend`` /
``self_attend`` without their MLPs (Perceiver AR), ``mla/absorb``,
``attn/window`` and ``attn/full`` with their cache writes (decoder-only)."""

from benchmarks.lib import scopes


def read(run):
    return scopes.read(run, "decode_attention_device_ms.decode",
                       lambda name, row: row["phase"] == "decode"
                       and (row["layer"] in scopes.DECODER_ATTENTION or scopes.in_attention_block(row)),
                       over="decode", parts=lambda name, row: row["layer"])
