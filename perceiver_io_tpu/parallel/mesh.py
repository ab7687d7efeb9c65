"""Device mesh and sharding rules — the TPU-native replacement for the
reference's DDP/FSDP/NCCL strategies (SURVEY §2.7).

One SPMD program over a named `jax.sharding.Mesh`; XLA GSPMD inserts the
collectives over ICI:

- **Data parallel** (reference: Lightning DDPStrategy,
  perceiver/scripts/cli.py:32-33, trainer.yaml:14): batch sharded over the
  ``data`` (and ``fsdp``) axes; gradient all-reduce is implicit.
- **FSDP / ZeRO-3** (reference: FSDPStrategy + transformer_auto_wrap_policy,
  perceiver/scripts/text/clm_fsdp.py:24-36): parameters and optimizer state
  sharded along ``fsdp`` via NamedSharding; XLA all-gathers weights per layer
  and reduce-scatters gradients.
- ``tensor``/``seq`` axes are reserved for tensor and sequence/context
  parallelism (beyond reference parity; the reference has neither — SURVEY
  §2.7 P8).

Multi-host: initialize with ``jax.distributed.initialize()``; every host runs
the same program and feeds its per-process batch shard
(`jax.make_array_from_process_local_data`), replacing the reference's
``split_dataset_by_node`` (perceiver/data/text/c4.py:76-79).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"

MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_TENSOR, AXIS_SEQ)


def make_mesh(
    data: Optional[int] = None,
    fsdp: int = 1,
    tensor: int = 1,
    seq: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 4-axis mesh (data, fsdp, tensor, seq). ``data=None`` absorbs
    all remaining devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = fsdp * tensor * seq
    if data is None:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fsdp*tensor*seq={fixed}")
        data = n // fixed
    if data * fixed != n:
        raise ValueError(f"mesh {data}x{fsdp}x{tensor}x{seq} != {n} devices")
    if max(data, fsdp, tensor, seq) == n:
        devices = _ring_order(devices)
    dev_array = np.asarray(devices).reshape(data, fsdp, tensor, seq)
    return Mesh(dev_array, MESH_AXES)


def _ring_order(devices):
    """Order for a mesh whose one axis spans every device: rows of the
    physical grid walked boustrophedon, so that consecutive devices are ICI
    neighbours. TPU devices enumerate row-major — on a v5e 2x2 as (0,0),
    (1,0), (0,1), (1,1) — where a 4-long axis would step (1,0) -> (0,1)
    across the diagonal; walked this way it is the closed ring 0, 1, 3, 2.
    A mesh of several axes keeps the enumeration order, whose axes already
    follow the grid's. Devices without coordinates (CPU) stay as given."""
    if any(getattr(d, "coords", None) is None for d in devices):
        return devices

    def key(d):
        x, y, *rest = d.coords
        return (*reversed(rest), y, x if y % 2 == 0 else -x)

    return sorted(devices, key=key)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2, seq_dim: Optional[int] = None) -> NamedSharding:
    """Shard the leading (batch) dim over data and fsdp axes — the standard
    JAX zero-style layout where fsdp also contributes data parallelism.
    ``seq_dim`` additionally shards that dim over the ``seq`` axis (sequence/
    context parallelism; the dim size must divide the seq axis size)."""
    spec = [None] * ndim
    spec[0] = (AXIS_DATA, AXIS_FSDP)
    if seq_dim is not None and 0 < seq_dim < ndim:
        spec[seq_dim] = AXIS_SEQ
    return NamedSharding(mesh, P(*spec))


def shard_batch(batch, mesh: Mesh, seq_dim: Optional[int] = None):
    """Device-put a host batch pytree with leading-dim (and optionally
    sequence-dim) sharding.

    The leading (batch) dim of every array leaf must divide the
    ``data x fsdp`` submesh — checked here with the offending leaf path,
    because the same mistake surfaced deep inside pjit as an opaque
    "sharding ... is not divisible" error otherwise."""
    n_batch_shards = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]

    def put(path, x):
        shape = np.shape(x)
        if len(shape) >= 1 and shape[0] % n_batch_shards != 0:
            raise ValueError(
                f"batch leaf {jax.tree_util.keystr(path) or '<root>'}: leading dim "
                f"{shape[0]} is not divisible by the data x fsdp submesh "
                f"({mesh.shape[AXIS_DATA]} x {mesh.shape[AXIS_FSDP]} = "
                f"{n_batch_shards} shards) — pad or resize the batch"
            )
        return jax.device_put(x, batch_sharding(mesh, ndim=len(shape), seq_dim=seq_dim))

    return jax.tree_util.tree_map_with_path(put, batch)


def _fsdp_dim(shape, fsdp_size: int, min_weight_size: int, exclude=()) -> Optional[int]:
    """Largest axis divisible by the fsdp size (None for small/replicated
    parameters) — the per-layer wrap-policy analog of the reference's
    transformer_auto_wrap_policy over attention layers (clm_fsdp.py:29-36)."""
    if fsdp_size <= 1 or math.prod(shape) < min_weight_size:
        return None
    # prefer the last axis, then earlier ones, by size
    order = sorted(range(len(shape)), key=lambda i: (shape[i], i), reverse=True)
    for i in order:
        if i not in exclude and shape[i] % fsdp_size == 0:
            return i
    return None


def _spec(axes) -> P:
    """``P(*axes)`` without trailing ``None``s — the form jit hands a sharding
    back in. A state placed under ``P('fsdp', None)`` and returned under
    ``P('fsdp')`` is the same layout but an unequal sharding, and the next
    call misses the jit cache on it."""
    axes = list(axes)
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


def _fsdp_spec(shape, fsdp_size: int, min_weight_size: int) -> P:
    dim = _fsdp_dim(shape, fsdp_size, min_weight_size)
    if dim is None:
        return P()
    spec = [None] * len(shape)
    spec[dim] = AXIS_FSDP
    return _spec(spec)


def fsdp_param_shardings(params, mesh: Mesh, min_weight_size: int = 2**14):
    """NamedSharding pytree for parameters (and, by shape, optimizer state):
    each large-enough tensor is sharded along its largest fsdp-divisible axis."""
    fsdp_size = mesh.shape[AXIS_FSDP]

    def spec_for(x):
        return NamedSharding(mesh, _fsdp_spec(np.shape(x), fsdp_size, min_weight_size))

    return jax.tree.map(spec_for, params)


# Megatron-style tensor parallelism over the attention-head / MLP-hidden dims
# (beyond reference parity — SURVEY §2.7 P8): column-parallel projections
# shard their output dim, row-parallel projections their input dim; GSPMD
# propagates the activation shardings and inserts the all-reduces.
_TENSOR_COL_PARALLEL = ("q_proj", "k_proj", "v_proj", "dense_1")
_TENSOR_ROW_PARALLEL = ("o_proj", "dense_2")


def _tensor_spec(path_names, shape, tensor_size: int) -> P:
    if tensor_size <= 1 or not shape:
        return P()
    leaf = path_names[-1]
    col = any(n in _TENSOR_COL_PARALLEL for n in path_names)
    row = any(n in _TENSOR_ROW_PARALLEL for n in path_names)
    if leaf == "kernel" and len(shape) == 2:
        if col and shape[1] % tensor_size == 0:
            return P(None, AXIS_TENSOR)
        if row and shape[0] % tensor_size == 0:
            return P(AXIS_TENSOR, None)
    if leaf == "bias" and len(shape) == 1 and col and shape[0] % tensor_size == 0:
        return P(AXIS_TENSOR)
    return P()


def param_shardings(params, mesh: Mesh, min_weight_size: int = 2**14):
    """Combined tensor-parallel + FSDP parameter shardings: the TP rule picks
    the head/hidden dim, FSDP shards a remaining dim of large tensors."""
    tensor_size = mesh.shape[AXIS_TENSOR]
    fsdp_size = mesh.shape[AXIS_FSDP]

    def spec_for(path, x):
        shape = np.shape(x)
        names = [getattr(k, "key", str(k)) for k in path]
        tp = _tensor_spec(names, shape, tensor_size)
        taken = {i for i, a in enumerate(tp) if a is not None}
        spec = list(tp) + [None] * (len(shape) - len(tp))
        dim = _fsdp_dim(shape, fsdp_size, min_weight_size, exclude=taken)
        if dim is not None:
            spec[dim] = AXIS_FSDP
        return NamedSharding(mesh, _spec(spec))

    return jax.tree_util.tree_map_with_path(spec_for, params)


def required_devices(spec: Dict[str, int]) -> int:
    """Device count a parsed mesh spec needs (product of axis sizes)."""
    need = 1
    for v in spec.values():
        need *= int(v)
    return need


def mesh_from_spec(spec_str: str, devices=None) -> Mesh:
    """Build the data/fsdp mesh a ``--mesh`` spec describes — the ONE
    implementation behind tools/graphlint.py, tools/graphcheck.py and
    ``analysis.flagship``. Raises ``ValueError`` (with the XLA_FLAGS hint)
    when too few devices are visible; callers own their shortage policy
    (exit, skip-note, or virtual-device respawn)."""
    spec = parse_mesh_spec(spec_str)
    devices = list(jax.devices() if devices is None else devices)
    need = required_devices(spec)
    if len(devices) < need:
        raise ValueError(
            f"mesh {spec_str!r} needs {need} devices, have {len(devices)} (for a "
            f"CPU dryrun: XLA_FLAGS=--xla_force_host_platform_device_count={need})"
        )
    return make_mesh(devices=devices[:need], **spec)


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"data=2,fsdp=4"`` -> ``{"data": 2, "fsdp": 4}`` (the ``--mesh``
    argument of tools/graphlint.py and tools/graphcheck.py)."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh spec {spec!r}: expected axis=N[,axis=N...]")
        axis, _, n = part.partition("=")
        axis = axis.strip()
        if axis not in (AXIS_DATA, AXIS_FSDP):
            raise ValueError(f"bad mesh spec {spec!r}: axis {axis!r} (allowed: data, fsdp)")
        out[axis] = int(n)
    if not out:
        raise ValueError(f"bad mesh spec {spec!r}: empty")
    return out
