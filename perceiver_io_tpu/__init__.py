"""perceiver_io_tpu — a TPU-native (JAX/Flax/XLA/Pallas) Perceiver framework.

Implements the full capability surface of Perceiver (arXiv:2103.03206),
Perceiver IO (arXiv:2107.14795) and Perceiver AR (arXiv:2202.07765) —
feature parity target is krasserm/perceiver-io v0.11.1 — redesigned
TPU-first: static shapes throughout, fixed-capacity KV caches, SPMD
parallelism over `jax.sharding.Mesh`, and Pallas attention kernels for
the hot ops.

Layer map (mirrors the reference's four stacked layers, re-drawn for JAX):

  L5  CLI       perceiver_io_tpu.scripts      auto-CLI over config dataclasses
  L4  Training  perceiver_io_tpu.training     jitted train_step, optax, orbax
  L3  Tasks     perceiver_io_tpu.models       text / vision / audio task models
  L2  Core      perceiver_io_tpu.core         attention, encoder/decoder, AR
  L1  Data      perceiver_io_tpu.data         host-side iterators feeding JAX
  ops           perceiver_io_tpu.ops          Pallas kernels
  parallel      perceiver_io_tpu.parallel     mesh / sharding / ring attention
  hf            perceiver_io_tpu.hf           conversion, auto-models, pipelines
  utils         perceiver_io_tpu.utils        FLOPs estimator, scaling laws, profiling
  generation    perceiver_io_tpu.generation   compiled decode: sampling + beam search
  serving       perceiver_io_tpu.serving      hardened front end: admission, deadlines,
                                              shedding, circuit breaking, clean books
  obs           perceiver_io_tpu.obs          events, spans, metrics, SLO, flight recorder
  analysis      perceiver_io_tpu.analysis     graph lint/contracts over jaxprs + HLO
"""

from perceiver_io_tpu._startup import RECORD as _STARTUP

_IMPORTING = _STARTUP.open("startup/import", package=__name__)

__version__ = "0.1.0"

from perceiver_io_tpu.core import config as config  # noqa: F401

__all__ = [
    "config",
]

_STARTUP.close(_IMPORTING)
