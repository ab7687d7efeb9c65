"""The long-KV flash kernels' share of their roofline over the traced steps,
in percent: the attention calls at the largest KV length of the family's own
list (the cross-attention over the input), selected by the
``flash_<pass>_q<n_q>_kv<n_kv>`` names the program gives its kernels. ``None``
where flash kernels worth over 1% of the flash time carry no such name
(``lib/flash_groups.py``)."""

from benchmarks.lib import flash_groups


def read(run):
    return flash_groups.read(run, "long")
