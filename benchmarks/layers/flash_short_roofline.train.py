"""The short-KV flash kernels' share of their roofline over the traced steps,
in percent: every attention call below the largest KV length of the family's
own list (the latent self-attention layers), selected by the
``flash_<pass>_q<n_q>_kv<n_kv>`` names the program gives its kernels. ``None``
where flash kernels worth over 1% of the flash time carry no such name
(``lib/flash_groups.py``)."""

from benchmarks.lib import flash_groups


def read(run):
    return flash_groups.read(run, "short")
