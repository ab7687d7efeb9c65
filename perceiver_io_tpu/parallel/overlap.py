"""Overlap-scheduled distributed train step: explicit ``shard_map`` data/FSDP
parallelism with chunk-interleaved gradient reduce-scatter and bucket-chained
FSDP all-gather prefetch.

The GSPMD path (``training/loop.py::make_train_step`` + ``NamedSharding``)
leaves every collective to XLA: gradient sync lands wherever the compiler
schedules it, usually as one bulk sync after the last microbatch chunk, and
the FSDP parameter gathers are invisible and unaudited. This module makes the
communication schedule explicit — the standard lever of the pjit-era TPU
scaling playbook (arXiv:2204.06514) — while keeping the optimizer math
bit-for-bit the GSPMD step's:

- **Chunk-interleaved gradient sync**: with ``microbatch=k`` the step unrolls
  k fwd+bwd chunks; each chunk's gradients start their ``reduce_scatter``
  (fsdp axis) + ``all_reduce`` (data axis) immediately, so chunk *i*'s
  collectives are dataflow-independent of chunk *i+1*'s compute and the
  latency-hiding scheduler can run them concurrently — instead of one exposed
  bulk sync after the last chunk. Leaves are coalesced into size-bounded
  **buckets** (one collective per bucket, not per leaf) so small leaves do
  not pay per-collective latency.
- **FSDP all-gather prefetch**: parameters sharded along the ``fsdp`` axis
  (same per-leaf rule as ``mesh.fsdp_param_shardings``) are all-gathered per
  bucket at step start; with ``prefetch=True`` bucket *b+1*'s gather is
  chained one bucket behind bucket *b*'s completion via
  ``optimization_barrier`` (depth-1 prefetch — bounds concurrent gather
  buffers while each gather stays free to ride under any compute that does
  not consume it).
- **ZeRO-style sharded update**: the step returns reduce-scattered gradient
  shards from the ``shard_map`` region; the optimizer update runs outside it
  on the (logically full, physically fsdp-sharded) gradient/param/moment
  arrays, so no device ever materializes a full gradient tree for the
  optimizer and ``optax.global_norm`` clipping stays a *global* norm (GSPMD
  partitions the reduction).

Scheduling is *asserted*, not assumed: the ``collective-overlap`` graphlint
rule (analysis/rules.py) walks the compiled HLO and checks every
reduce-scatter/all-gather has compute it can overlap with —
``tools/graphlint.py --mesh data=N,fsdp=M`` lints the sharded flagship step
from the CLI, and :func:`expected_collectives` declares the per-kind counts
the ``collective-budget`` rule pins.

Correctness bar (tests/test_overlap.py + ``__graft_entry__.dryrun_multichip``):
loss and post-update params equal to the GSPMD step on the forced-8-device
CPU dryrun across ``{data:8}``, ``{data:2,fsdp:4}``, ``{data:4,fsdp:2}``
meshes. Equivalence is certified for *uniform-weighting* losses (the same
precondition the microbatched GSPMD step enforces): a device-sharded mean of
per-shard means only equals the global mean when every sample weighs the
same, so padded batches are rejected exactly like ``make_train_step`` does.

Per the repo's measure-before-shipping policy the overlap step is
feature-gated default-off (``TrainerConfig.overlap`` / ``bench.py --overlap``)
until a TPU session lands the A/B number — ``tools/overlap_ab.py`` stages it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_io_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, _fsdp_dim

# one collective per ~4 MB of gradient/parameter payload: big enough to
# amortize per-collective latency, small enough that the first chunk's
# reduce-scatter can start while most of the chunk's backward is still
# running (bucket-size guidance: docs/parallelism.md)
DEFAULT_BUCKET_BYTES = 4 << 20


@dataclasses.dataclass(frozen=True)
class OverlapConfig:
    """Configuration of the overlap-scheduled step.

    ``min_weight_size`` must match the value the train state was sharded
    with (``shard_train_state`` / ``fsdp_param_shardings``) so the step's
    ``in_specs`` agree with the incoming parameter placement."""

    mesh: Mesh
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    prefetch: bool = True  # chain all-gathers one bucket ahead of use
    min_weight_size: int = 2**14


@dataclasses.dataclass(frozen=True)
class _Leaf:
    index: int  # position in the flattened param tree
    shape: Tuple[int, ...]
    dtype: str
    dim: Optional[int]  # fsdp-sharded dim; None = replicated

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * np.dtype(self.dtype).itemsize


def _leaf_plan(shapes_dtypes, fsdp_size: int, min_weight_size: int) -> List[_Leaf]:
    return [
        _Leaf(
            i,
            tuple(map(int, shape)),
            str(np.dtype(dtype)),
            _fsdp_dim(shape, fsdp_size, min_weight_size),
        )
        for i, (shape, dtype) in enumerate(shapes_dtypes)
    ]


def _plan_buckets(
    leaves: Sequence[_Leaf], bucket_bytes: int
) -> Tuple[List[List[_Leaf]], List[List[_Leaf]]]:
    """Greedy tree-order coalescing into (sharded, replicated) bucket lists.

    Same-dtype leaves accumulate into a bucket until it reaches
    ``bucket_bytes``; a leaf that alone meets the threshold closes its own
    bucket (the single-leaf fast path gathers/scatters it without the
    flatten round-trip). A dtype change also closes the open bucket —
    coalescing concatenates flattened leaves, which requires one dtype."""

    def pack(group: Sequence[_Leaf]) -> List[List[_Leaf]]:
        buckets: List[List[_Leaf]] = []
        cur: List[_Leaf] = []
        cur_bytes = 0
        for lf in group:
            if cur and (lf.dtype != cur[0].dtype or cur_bytes + lf.nbytes > bucket_bytes):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(lf)
            cur_bytes += lf.nbytes
            if cur_bytes >= bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            buckets.append(cur)
        return buckets

    sharded = pack([lf for lf in leaves if lf.dim is not None])
    replicated = pack([lf for lf in leaves if lf.dim is None])
    return sharded, replicated


def _shard_shape(lf: _Leaf, fsdp_size: int) -> Tuple[int, ...]:
    if lf.dim is None:
        return lf.shape
    s = list(lf.shape)
    s[lf.dim] //= fsdp_size
    return tuple(s)


# ---------------------------------------------------------------- collectives


def _gather_bucket(shards: List[jax.Array], bucket: List[_Leaf], fsdp_size: int) -> List[jax.Array]:
    """All-gather one bucket of fsdp-sharded leaves into full leaves — ONE
    collective for the whole bucket."""
    if len(bucket) == 1:
        return [lax.all_gather(shards[0], AXIS_FSDP, axis=bucket[0].dim, tiled=True)]
    flat = jnp.concatenate([s.reshape(-1) for s in shards])
    g = lax.all_gather(flat, AXIS_FSDP, axis=0, tiled=False)  # (fsdp, sum(shard sizes))
    out, off = [], 0
    for lf, s in zip(bucket, shards):
        n = int(np.prod(s.shape, dtype=np.int64))
        seg = g[:, off : off + n].reshape((fsdp_size,) + s.shape)
        # tiled-concat layout: device block g sits at rows [g*shard_d, (g+1)*shard_d)
        # of the sharded dim — moveaxis + reshape merges (fsdp, shard_d) back
        out.append(jnp.moveaxis(seg, 0, lf.dim).reshape(lf.shape))
        off += n
    return out


def _device_major(g: jax.Array, lf: _Leaf, fsdp_size: int) -> jax.Array:
    """(fsdp, shard_numel) view of a full gradient: row j is device j's shard
    of the fsdp dim, flattened — the layout ``psum_scatter`` hands back."""
    d = lf.dim
    shape = g.shape
    shard_d = shape[d] // fsdp_size
    g2 = g.reshape(shape[:d] + (fsdp_size, shard_d) + shape[d + 1 :])
    return jnp.moveaxis(g2, d, 0).reshape(fsdp_size, -1)


def _reduce_scatter_bucket(
    grads: List[jax.Array], bucket: List[_Leaf], fsdp_size: int, data_size: int
) -> List[jax.Array]:
    """Reduce-scatter one bucket of full per-device gradients into summed
    shards: ONE ``psum_scatter`` over fsdp (+ one ``psum`` over data when the
    data axis is non-trivial) for the whole bucket. Returns shard-shaped
    leaves summed over ALL batch-sharding devices."""
    if len(bucket) == 1:
        lf = bucket[0]
        shard = lax.psum_scatter(grads[0], AXIS_FSDP, scatter_dimension=lf.dim, tiled=True)
        if data_size > 1:
            shard = lax.psum(shard, AXIS_DATA)
        return [shard]
    flat = jnp.concatenate([_device_major(g, lf, fsdp_size) for g, lf in zip(grads, bucket)], axis=1)
    shard_flat = lax.psum_scatter(flat, AXIS_FSDP, scatter_dimension=0, tiled=False)
    if data_size > 1:
        shard_flat = lax.psum(shard_flat, AXIS_DATA)
    out, off = [], 0
    for lf in bucket:
        shape = _shard_shape(lf, fsdp_size)
        n = int(np.prod(shape, dtype=np.int64))
        out.append(shard_flat[off : off + n].reshape(shape))
        off += n
    return out


def _allreduce_bucket(grads: List[jax.Array], bucket: List[_Leaf]) -> List[jax.Array]:
    """Sum one bucket of replicated-leaf gradients over every batch-sharding
    device: ONE ``psum`` over (data, fsdp) for the whole bucket."""
    if len(bucket) == 1:
        return [lax.psum(grads[0], (AXIS_DATA, AXIS_FSDP))]
    flat = jnp.concatenate([g.reshape(-1) for g in grads])
    flat = lax.psum(flat, (AXIS_DATA, AXIS_FSDP))
    out, off = [], 0
    for lf in bucket:
        n = int(np.prod(lf.shape, dtype=np.int64))
        out.append(flat[off : off + n].reshape(lf.shape))
        off += n
    return out


def _chunk(x, i: int, k: int):
    if x is None:
        return None
    n = x.shape[0]
    if n % k != 0:
        raise ValueError(f"microbatch={k} does not divide per-device batch size {n}")
    per = n // k
    return x[i * per : (i + 1) * per]


# ------------------------------------------------------------------ the step


def _validate_mesh(mesh: Mesh) -> Tuple[int, int]:
    shape = dict(mesh.shape)
    for axis in (AXIS_DATA, AXIS_FSDP):
        if axis not in shape:
            raise ValueError(f"overlap step needs a mesh with a '{axis}' axis; got {shape}")
    for axis, size in shape.items():
        if axis not in (AXIS_DATA, AXIS_FSDP) and size > 1:
            raise ValueError(
                f"overlap step supports data/fsdp meshes only; axis '{axis}' has size "
                f"{size} — use the GSPMD path (make_train_step(overlap=None)) for "
                "tensor/sequence parallelism"
            )
    return shape[AXIS_DATA], shape[AXIS_FSDP]


def make_overlap_train_step(
    loss_fn: Callable,
    config: OverlapConfig,
    *,
    microbatch: int = 1,
    donate: bool = True,
    jit: bool = True,
) -> Callable:
    """``train_step(state, batch) -> (state, metrics)`` — the explicit
    shard_map twin of ``training.loop.make_train_step``.

    The state must be placed by ``shard_train_state`` (params/optimizer
    moments fsdp-sharded with the SAME ``min_weight_size``), the batch by
    ``shard_batch``. Same ``loss_fn`` contract and the same uniform-chunk-
    weighting precondition as the GSPMD step — here it applies even at
    ``microbatch=1`` because the loss is averaged per batch *shard*.
    """
    data_size, fsdp_size = _validate_mesh(config.mesh)
    mesh = config.mesh
    n_dev = data_size * fsdp_size
    k = microbatch

    if getattr(loss_fn, "uniform_weighting", None) is False:
        raise ValueError(
            "this loss declares uniform_weighting=False (per-call count "
            "normalization); the overlap step averages per-shard means and "
            "would reweight tokens — use the GSPMD step with microbatch=1"
        )
    uniform_declared = getattr(loss_fn, "uniform_weighting", None) is True
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        if not uniform_declared and isinstance(batch, dict) and batch.get("pad_mask") is not None:
            raise ValueError(
                "the overlap step requires equal per-shard/per-chunk weighting; "
                "padded batches normalize per call and would reweight tokens — "
                "pass pad_mask=None (packed windows) or a uniform_weighting loss"
            )
        rng, step_rng = jax.random.split(state.rng)

        params_flat, treedef = jax.tree_util.tree_flatten(state.params)
        leaves = _leaf_plan(
            [(p.shape, p.dtype) for p in params_flat], fsdp_size, config.min_weight_size
        )
        sharded_buckets, replicated_buckets = _plan_buckets(leaves, config.bucket_bytes)
        param_specs = [
            P() if lf.dim is None else P(*[AXIS_FSDP if i == lf.dim else None for i in range(len(lf.shape))])
            for lf in leaves
        ]

        def body(params_tree, local_batch, step_rng):
            params_shards = jax.tree_util.tree_leaves(params_tree)
            # ---- FSDP all-gather, bucket-chained one ahead of use --------
            full: List[Optional[jax.Array]] = list(params_shards)
            anchor = None
            for bi, bucket in enumerate(sharded_buckets):
                shards = [params_shards[lf.index] for lf in bucket]
                if config.prefetch and anchor is not None:
                    # depth-1 prefetch: this bucket's gather may not issue
                    # before the previous bucket's gather has completed, but
                    # stays independent of all compute — the scheduler slides
                    # it under whatever runs meanwhile
                    chained = lax.optimization_barrier(tuple(shards) + (anchor,))
                    shards, anchor = list(chained[:-1]), chained[-1]
                with jax.named_scope(f"fsdp_gather/b{bi}"):
                    gathered = _gather_bucket(shards, bucket, fsdp_size)
                for lf, g in zip(bucket, gathered):
                    full[lf.index] = g
                anchor = gathered[0]
            params_full = jax.tree_util.tree_unflatten(treedef, full)

            # ---- chunked fwd+bwd, reduce-scatter interleaved per chunk ---
            # per-shard RNG: fold the device's linear mesh index into the
            # step key — a replicated key would draw IDENTICAL dropout masks
            # on every batch shard, cutting mask diversity n_dev-fold vs the
            # GSPMD step (draws differ from GSPMD's global-batch masks but
            # keep the same distribution; equivalence is certified on
            # deterministic losses)
            dev_index = lax.axis_index(AXIS_DATA) * fsdp_size + lax.axis_index(AXIS_FSDP)
            chunk_rngs = jax.random.split(jax.random.fold_in(step_rng, dev_index), k)
            acc: Optional[List[jax.Array]] = None
            metrics_acc = None
            for ci in range(k):  # unrolled: k is small and static
                chunk = jax.tree.map(
                    lambda x: _chunk(x, ci, k), local_batch, is_leaf=lambda x: x is None
                )
                (_, m), grads = grad_fn(params_full, chunk, chunk_rngs[ci])
                gflat = jax.tree_util.tree_leaves(grads)
                synced: List[Optional[jax.Array]] = [None] * len(leaves)
                for bi, bucket in enumerate(sharded_buckets):
                    with jax.named_scope(f"grad_sync/c{ci}b{bi}"):
                        shards = _reduce_scatter_bucket(
                            [gflat[lf.index] for lf in bucket], bucket, fsdp_size, data_size
                        )
                    for lf, s in zip(bucket, shards):
                        synced[lf.index] = s
                for bi, bucket in enumerate(replicated_buckets):
                    with jax.named_scope(f"grad_sync/c{ci}r{bi}"):
                        full_g = _allreduce_bucket([gflat[lf.index] for lf in bucket], bucket)
                    for lf, g in zip(bucket, full_g):
                        synced[lf.index] = g
                # chunk ci's scattered shards are consumed only HERE (an
                # elementwise add) and at the final scale — nothing in chunk
                # ci+1's fwd+bwd depends on them, which is exactly the
                # dataflow freedom the latency-hiding scheduler needs
                acc = synced if acc is None else [a + s for a, s in zip(acc, synced)]
                metrics_acc = (
                    m if metrics_acc is None else jax.tree.map(jnp.add, metrics_acc, m)
                )
            inv = 1.0 / (k * n_dev)
            grads_out = jax.tree_util.tree_unflatten(treedef, [g * inv for g in acc])
            metrics = jax.tree.map(
                lambda x: lax.psum(x, (AXIS_DATA, AXIS_FSDP)) / (k * n_dev), metrics_acc
            )
            return grads_out, metrics

        # custom-VJP gather/embed rewrites defeat shard_map's static
        # varying-mesh-axes inference (same trade as parallel/long_context.py:
        # keep the static check, trace with the plain ops)
        from perceiver_io_tpu.ops.gathers import plain_gathers

        def body_plain(*args):
            with plain_gathers():
                return body(*args)

        grad_specs = jax.tree_util.tree_unflatten(treedef, param_specs)
        sharded = jax.shard_map(
            body_plain,
            mesh=mesh,
            in_specs=(grad_specs, P((AXIS_DATA, AXIS_FSDP)), P()),
            out_specs=(grad_specs, P()),
        )
        grads, metrics = sharded(state.params, batch, step_rng)
        # ZeRO-style update OUTSIDE the shard_map region: grads/params/moments
        # are logically full but physically fsdp-sharded arrays, so the optax
        # update runs on shards (elementwise stays sharded under GSPMD) and
        # global-norm clipping reduces globally
        state = state.apply_gradients(grads).replace(rng=rng)
        return state, metrics

    if not jit:
        return train_step
    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


# ------------------------------------------------------------------ auditing


def expected_collectives(
    params,
    mesh: Mesh,
    *,
    microbatch: int = 1,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    min_weight_size: int = 2**14,
) -> Dict[str, int]:
    """Per-kind collective counts the overlap step's shard_map region emits —
    the declaration the ``collective-budget`` graphlint rule pins.

    Exact upper bounds for the explicit collectives (XLA's combiner passes may
    merge, never add): one all-gather per sharded bucket, one reduce-scatter
    per sharded bucket per chunk, one data-axis all-reduce per sharded bucket
    per chunk (when ``data>1``) plus one (data, fsdp) all-reduce per
    replicated bucket per chunk and one for the metrics tree. The optimizer
    update outside the region adds a handful of GSPMD all-reduces (global-norm
    clipping) — callers budgeting a whole compiled step should add slack to
    ``all-reduce`` only."""
    data_size, fsdp_size = _validate_mesh(mesh)
    shapes = [(np.shape(p), np.asarray(p).dtype if not hasattr(p, "dtype") else p.dtype)
              for p in jax.tree_util.tree_leaves(params)]
    leaves = _leaf_plan(shapes, fsdp_size, min_weight_size)
    sharded, replicated = _plan_buckets(leaves, bucket_bytes)
    k = microbatch
    n_sh = len(sharded)
    return {
        "all-gather": n_sh,
        "reduce-scatter": k * n_sh,
        "all-reduce": k * ((n_sh if data_size > 1 else 0) + len(replicated)) + 1,
    }


def required_devices(spec: Dict[str, int]) -> int:
    """Device count a parsed mesh spec needs (product of axis sizes)."""
    need = 1
    for v in spec.values():
        need *= int(v)
    return need


def mesh_from_spec(spec_str: str, devices=None) -> Mesh:
    """Build the data/fsdp mesh a ``--mesh`` spec describes — the ONE
    implementation behind bench.py, tools/graphlint.py, tools/overlap_ab.py
    and ``analysis.flagship.graphlint_telemetry``. Raises ``ValueError``
    (with the XLA_FLAGS hint) when too few devices are visible; callers own
    their shortage policy (exit, skip-note, or virtual-device respawn)."""
    from perceiver_io_tpu.parallel.mesh import make_mesh

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
    argument shared by bench.py and tools/graphlint.py)."""
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
