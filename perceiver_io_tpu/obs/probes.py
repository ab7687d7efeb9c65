"""Probeline — in-graph numerics telemetry (docs/observability.md#probes).

Spanline (PR 8) says what a step *took* and graphcheck says what the
compiled graph *is*; nothing says what the numbers *did* inside the
compiled program — when the DivergenceSentinel fires we know the loss went
non-finite and nothing about which layer's activations or gradients went
bad first. This module adds trace-time **probes**: cheap on-device
statistics (rms, absmax, non-finite fraction, zero fraction) computed per
selected ``jax.named_scope`` site and returned as **auxiliary pytree
outputs of the same compiled program** — no host callbacks (the
``callback-in-jit`` graphlint rule stays clean), no per-step host sync
(the trainer parks snapshots as device arrays and fetches them only at log
boundaries and on sentinel trips).

Discipline (same as ``ops.flash_attention.fast_kernels``): probing is a
**trace-time feature**. :func:`probe` reads a contextvar — with no
collector active it is a pure host-side no-op that traces **zero ops**, so
probes-off reproduces today's graphs bitwise (the committed graphcheck
contracts for the unprobed programs pin this; ``contracts/
train_probed.json`` pins that probes-on adds zero collectives, no
callbacks and bounded const/temp bytes).

Pieces:

- :class:`ProbeConfig` — static selection (scope globs, grad-bucket depth,
  which stat families run). Passed to ``make_train_step(probes=...)`` /
  ``TrainerConfig.probes``.
- :func:`probe` — the tap model code calls at its named-scope sites
  (``core/modules.py``, ``core/attention.py``); identity on the tensor.
- :func:`collecting` — the trace-time collector context
  ``make_train_step`` opens around the loss forward; collected stats land
  under ``metrics["probes"]`` keyed ``"NNN:scope"`` (the zero-padded index
  preserves forward/topological order across the jit boundary, where dict
  pytrees re-sort by key).
- :func:`grad_bucket_stats` / :func:`update_ratio_stats` — per-layer-bucket
  gradient norms and update/param-ratio stats from the grad pytree,
  appended by the train step after the backward pass.
- :func:`blast_report` — host-side blast-radius attribution over the
  trainer's ring of snapshots: the first scope (in topological order) of
  the earliest snapshot whose stats went non-finite; the trainer emits it
  as a ``probe.blast`` event inside the step span.
- :func:`decode_health` — the decode-body gauges (KV-cache occupancy,
  logit entropy, non-finite logit fraction) ``generation.make_decode_fns``
  computes in-graph and the instrumented wrapper publishes into the
  ``MetricsRegistry`` and onto each ``request`` event.
- :func:`probes_live_report` — the dataflow check that probe outputs are
  live in the traced program (not silently DCE'd).
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from dataclasses import dataclass
from fnmatch import fnmatch
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ProbeConfig:
    """Static (trace-time) probe selection.

    ``scopes`` are fnmatch globs against the probe-site names the model
    declares (``perceiver_ar.cross_attend``, ``self_attention.layer_0``,
    ``attention.out`` ... — docs/observability.md#probes has the site
    table). ``bucket_depth`` controls how many path components form one
    gradient/update bucket (4 reaches ``params.perceiver_ar.
    self_attention.layer_0`` — per-layer buckets on the flagship tree).
    ``ring`` is the host-side knob riding along: how many recent snapshots
    the trainer keeps for blast-radius attribution.
    """

    scopes: Tuple[str, ...] = ("*",)
    activations: bool = True
    grad_norms: bool = True
    update_ratio: bool = True
    bucket_depth: int = 4
    ring: int = 8

    def wants(self, scope: str) -> bool:
        return any(fnmatch(scope, p) for p in self.scopes)


class _Collector:
    """Ordered scope -> stats accumulator for one trace. Keys carry a
    zero-padded forward-call index (``"004:self_attention.layer_1"``) so
    sorted order == topological order even after a jit boundary re-sorts
    the dict pytree."""

    def __init__(self, config: ProbeConfig):
        self.config = config
        self.stats: Dict[str, Dict] = {}
        self._seen: Dict[str, int] = {}

    def add(self, scope: str, stats: Dict) -> None:
        n = self._seen.get(scope, 0)
        self._seen[scope] = n + 1
        if n:
            scope = f"{scope}#{n}"  # repeated site (shared blocks in a loop)
        self.stats[ordered_key(len(self.stats), scope)] = stats


_ACTIVE: "contextvars.ContextVar[Optional[_Collector]]" = contextvars.ContextVar(
    "obs_probe_collector", default=None
)


def ordered_key(index: int, scope: str) -> str:
    return f"{index:03d}:{scope}"


def scope_of(key: str) -> str:
    """The bare scope name of an ordered snapshot key."""
    head, sep, tail = key.partition(":")
    return tail if sep and head.isdigit() else key


@contextlib.contextmanager
def collecting(config: ProbeConfig):
    """Open a probe collector for the duration of a trace; :func:`probe`
    calls inside deposit their stats here. Trace-time scoping, exactly like
    ``fast_kernels`` — a function traced outside the context keeps zero
    probe ops forever."""
    col = _Collector(config)
    token = _ACTIVE.set(col)
    try:
        yield col
    finally:
        _ACTIVE.reset(token)


def active() -> bool:
    """True when a collector is open (model code can branch cheaply)."""
    return _ACTIVE.get() is not None


def activation_stats(x) -> Dict:
    """The per-scope stat quartet, reduced on device in f32: rms, absmax,
    non-finite fraction, zero fraction. rms/absmax deliberately propagate
    NaN/Inf (a poisoned tensor shows up in every column); the non-finite
    fraction is the robust detector blast attribution keys on."""
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    return {
        "rms": jnp.sqrt(jnp.mean(jnp.square(x32))),
        "absmax": jnp.max(jnp.abs(x32)),
        "nonfinite_frac": jnp.mean((~jnp.isfinite(x32)).astype(jnp.float32)),
        "zero_frac": jnp.mean((x32 == 0).astype(jnp.float32)),
    }


def probe(scope: str, x):
    """Tap one tensor at a named site; returns ``x`` unchanged.

    No-op (zero traced ops) unless a :func:`collecting` context is open AND
    ``scope`` matches the config's globs. The stats ops are wrapped in a
    ``jax.named_scope("probes.<scope>")`` so graphlint/dataflow attribute
    them and :func:`probes_live_report` can find them."""
    col = _ACTIVE.get()
    if col is None or not col.config.activations or not col.config.wants(scope):
        return x
    import jax

    with jax.named_scope(f"probes.{scope}"):
        col.add(scope, activation_stats(x))
    return x


def tap(scope: str, stats: Dict) -> None:
    """Deposit ready-made scalar stats (counts, maxima) at a named site: the
    tap for what is not an activation, such as an expert layer's routed-pair
    counts (``moe.load``). No-op unless a :func:`collecting` context is open
    and ``scope`` matches; the caller guards any ops that compute ``stats``
    with :func:`active`."""
    col = _ACTIVE.get()
    if col is not None and col.config.wants(scope):
        col.add(scope, stats)


def current_config() -> Optional[ProbeConfig]:
    """The open collector's config (``None`` when none is open): lets code
    that wraps a loop body open a collector of its own inside the body."""
    col = _ACTIVE.get()
    return None if col is None else col.config


# ---------------------------------------------------------------------------
# gradient / update-ratio buckets (the train-step half)
# ---------------------------------------------------------------------------


def _bucket_leaves(tree, depth: int) -> Dict[str, List]:
    """Group a pytree's array leaves into path buckets: the first ``depth``
    path components joined with '.' (optimizer/grad trees mirror the param
    tree, so buckets line up across all three)."""
    import jax

    out: Dict[str, List] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not hasattr(leaf, "shape"):
            continue
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        bucket = ".".join(names[:depth]) if names else "<root>"
        out.setdefault(bucket, []).append(leaf)
    return out


def grad_bucket_stats(grads, depth: int = 4) -> Dict[str, Dict]:
    """Per-bucket gradient stats: l2 norm, absmax, non-finite fraction —
    the backward-pass half of blast attribution (an activation blow-up in
    layer k shows up in that layer's grad bucket first)."""
    import jax.numpy as jnp

    out: Dict[str, Dict] = {}
    for bucket, leaves in sorted(_bucket_leaves(grads, depth).items()):
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
        amax = jnp.max(
            jnp.stack([jnp.max(jnp.abs(g.astype(jnp.float32))) for g in leaves])
        )
        n = sum(g.size for g in leaves)
        nonfinite = sum(
            jnp.sum((~jnp.isfinite(g.astype(jnp.float32))).astype(jnp.float32))
            for g in leaves
        )
        out[f"grad.{bucket}"] = {
            "l2": jnp.sqrt(sq),
            "absmax": amax,
            "nonfinite_frac": nonfinite / n,
        }
    return out


def update_ratio_stats(old_params, new_params, depth: int = 4) -> Dict[str, Dict]:
    """Per-bucket ``||p_new - p_old|| / ||p_old||`` — the effective-step-size
    telemetry (a healthy run sits ~1e-3; a bucket at 1e-1 is about to
    diverge, one at 0 is dead/frozen)."""
    import jax.numpy as jnp

    old_b = _bucket_leaves(old_params, depth)
    new_b = _bucket_leaves(new_params, depth)
    out: Dict[str, Dict] = {}
    for bucket in sorted(old_b):
        if bucket not in new_b:
            continue
        d_sq = sum(
            jnp.sum(jnp.square(n.astype(jnp.float32) - o.astype(jnp.float32)))
            for o, n in zip(old_b[bucket], new_b[bucket])
        )
        p_sq = sum(jnp.sum(jnp.square(o.astype(jnp.float32))) for o in old_b[bucket])
        out[f"update.{bucket}"] = {
            "ratio": jnp.sqrt(d_sq) / (jnp.sqrt(p_sq) + 1e-12),
        }
    return out


def attach_train_stats(pstats: Dict, config: ProbeConfig, grads, old_params, new_params) -> Dict:
    """Extend a (possibly empty) activation-stat dict with the grad-bucket
    and update-ratio families, continuing the ordered-key numbering so the
    whole snapshot stays topologically sorted (forward activations, then
    gradients, then updates)."""
    i = len(pstats)
    out = dict(pstats)
    if config.grad_norms:
        for scope, st in grad_bucket_stats(grads, config.bucket_depth).items():
            out[ordered_key(i, scope)] = st
            i += 1
    if config.update_ratio:
        for scope, st in update_ratio_stats(
            old_params, new_params, config.bucket_depth
        ).items():
            out[ordered_key(i, scope)] = st
            i += 1
    return out


# ---------------------------------------------------------------------------
# decode health (the generation half)
# ---------------------------------------------------------------------------


def decode_health(logits, kv_cache, kv_start) -> Dict:
    """The per-token decode gauges, computed in-graph from the step body's
    last-position logits and the post-append cross-attention cache:
    KV-window occupancy fraction (the batch's mean where the cache keeps a
    length a row; 0 where ``kv_cache`` is ``None``: a stack whose states have one
    size and no cache that fills), mean logit entropy (nats — collapsing
    entropy is the classic degenerate-sampling signal), and the non-finite
    logit fraction (the serving-side numerics probe)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("probes.decode_health"):
        l32 = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(l32, axis=-1)
        ent = -jnp.sum(jnp.where(jnp.isfinite(logp), jnp.exp(logp) * logp, 0.0), axis=-1)
        if kv_cache is None:
            used, capacity = jnp.zeros((), jnp.float32), 1.0
        else:
            used, capacity = (kv_cache.length - kv_start).astype(jnp.float32), float(kv_cache.capacity)
            if used.ndim:  # a cache that keeps a length a row
                used = jnp.mean(used)
        return {
            "logit_entropy": jnp.mean(ent),
            "kv_cache_frac": used / capacity,
            "nonfinite_logit_frac": jnp.mean((~jnp.isfinite(l32)).astype(jnp.float32)),
        }


# ---------------------------------------------------------------------------
# host side: snapshots, ring, blast-radius attribution
# ---------------------------------------------------------------------------


def snapshot_to_host(snapshot: Dict) -> Dict[str, Dict[str, float]]:
    """One fetch for the whole snapshot; values become plain floats (the
    ``probe`` event body). Key order is sorted == topological (ordered
    keys)."""
    import jax

    host = jax.device_get(snapshot)
    return {
        k: {s: float(v) for s, v in host[k].items()} for k in sorted(host)
    }


def _stats_nonfinite(stats: Dict[str, float]) -> bool:
    nf = stats.get("nonfinite_frac")
    if nf is not None and nf > 0:
        return True
    return any(not math.isfinite(float(v)) for v in stats.values())


def first_nonfinite_scope(host_snapshot: Dict[str, Dict[str, float]]) -> Optional[str]:
    """The first scope in topological order whose stats went non-finite —
    the blast origin. ``host_snapshot`` must already be host-fetched."""
    for key in sorted(host_snapshot):
        if _stats_nonfinite(host_snapshot[key]):
            return key
    return None


def blast_report(ring) -> Optional[Dict]:
    """Blast-radius attribution over a ring of ``(step, snapshot)`` entries
    (oldest first, snapshots still on device): find the EARLIEST snapshot
    containing any non-finite scope and name its first affected scope in
    topological order — where the divergence entered the program — plus the
    full affected set (the blast radius). None when every snapshot is
    clean (e.g. a loss spike without numeric blow-up)."""
    for step_dev, snap in ring:
        host = snapshot_to_host(snap)
        affected = [k for k in sorted(host) if _stats_nonfinite(host[k])]
        if affected:
            origin = affected[0]
            return {
                "step": int(step_dev),
                "scope": scope_of(origin),
                "stats": host[origin],
                "affected": [scope_of(k) for k in affected],
                "n_affected": len(affected),
                "n_scopes": len(host),
            }
    return None


# ---------------------------------------------------------------------------
# analysis tie-in: probe outputs must be live, never DCE'd
# ---------------------------------------------------------------------------


def probes_live_report(fn, args: tuple) -> Dict:
    """Dataflow liveness audit of a probed program: every ``probes.*``
    named scope must have at least one LIVE op (reaching a jaxpr output).
    A fully-dead probe scope would silently report nothing — this is the
    check that the aux-output plumbing actually carries the stats out.

    Granularity is per SCOPE, not per op: the backward trace leaves dead
    tangent remnants of the probe reductions under the same scope (aux
    outputs are not differentiated, so their tangents are pruned by XLA) —
    those are expected and cheap; what must never happen is a scope whose
    ops are ALL dead.

    Returns ``{"probe_scopes": N, "probe_ops": M, "dead_scopes": [...]}``;
    healthy means ``probe_scopes > 0 and not dead_scopes``."""
    from perceiver_io_tpu.analysis import dataflow
    from perceiver_io_tpu.analysis import graph as G

    closed = G.trace(fn, *args)
    df = dataflow.build(closed)
    dead_ids = {n.nid for n in df.dead_nodes()}
    by_scope: Dict[str, List] = {}
    for n in df.nodes:
        scope = n.scope or ""
        i = scope.find("probes.")
        if i < 0:
            continue
        tail = scope[i:]
        by_scope.setdefault(tail.split("/")[0], []).append(n)
    dead_scopes = [
        s for s, nodes in sorted(by_scope.items())
        if all(n.nid in dead_ids for n in nodes)
    ]
    return {
        "probe_scopes": len(by_scope),
        "probe_ops": sum(len(v) for v in by_scope.values()),
        "dead_scopes": dead_scopes,
    }
