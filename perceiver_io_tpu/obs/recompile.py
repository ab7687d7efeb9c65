"""Recompilation tracking — surface silent shape-driven jit cache misses.

A jitted step that quietly retraces (a new batch shape, a donated buffer
whose layout changed, a Python-object hash miss) costs seconds to minutes
on TPU and is invisible in ``metrics.csv``: throughput just dips. The
:class:`RecompileTracker` wraps compiled callables and watches the jit
executable cache (``fn._cache_size()``) across calls — a size increase
means THIS call compiled, its wall time is (trace + compile + dispatch)
time, and the argument shape signature says what drove it. Each miss is
emitted as a ``compile`` event and accounted against goodput. The row splits
``wall_s`` by what the start-up record (``obs/startup.py``, JAX's own compile
events) saw close inside the call: ``trace_s``, ``lower_s``, ``backend_s``
(self times) and ``cache`` (``"hit"``, ``"miss"`` or ``"off"``: whether the
persistent cache served the programs); the rest of ``wall_s`` is the first
dispatch.

The first call's compile is expected; any later ``compile`` event on the
same function is the smoking gun for a shape leak.

A ``compile`` row also carries what the traces so far fixed about the packed
flash kernels (``flash_tiles``: one row per distinct attention geometry with
its blocks and the score tiles run, masked and skipped; the plan is chosen at
trace time from the lengths alone, ``ops.flash_attention.tile_plan``), and
about the position-table gradient of the compact prefix-dropout embedding
(``embed_tiles``: one row per distinct call with its tiles, grid steps and
one-hot FLOPs, and whether it took the kernel or XLA's scatter-add;
``ops.gathers.embed_tile_plan``, from the shapes alone), about the experts'
grouped products (``moe_tiles``: one row per distinct product with its blocks,
the VMEM they take and whether an expert's weight block stays resident across
its visits; ``ops.grouped_matmul.block_plan``, from the shapes alone), and about the MLPs'
exact GELU under differentiation (``mlp_gelu``: one row per distinct hidden
shape with its sites, what each keeps for the backward and its ``erfc``
evaluations; ``core.modules.mlp_gelu_plans``, counted over this call's trace
alone: the trainer traces its step a second time for graphlint).
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Dict, Optional

from perceiver_io_tpu.obs import startup


def _cache_size(fn) -> Optional[int]:
    """The jit executable-cache size, or None when ``fn`` does not expose
    one (not a jit wrapper, or a future jax moved the attribute)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def shape_signature(args, kwargs=None, top: int = 8) -> Dict:
    """Compact signature of a call's array arguments: leaf count and the
    most common ``dtype[shape]`` strings — enough to diff two ``compile``
    events and see which input changed shape."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    counter = collections.Counter()
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            counter[type(leaf).__name__] += 1
        else:
            dtype = getattr(leaf, "dtype", None)
            counter[f"{getattr(dtype, 'name', dtype)}{list(shape)}"] += 1
    return {"leaves": len(leaves), "shapes": dict(counter.most_common(top))}


class RecompileTracker:
    """Wrap jitted callables; count and log their cache misses.

    ``events`` (an ``obs.events.EventLog``) and ``goodput`` (an
    ``obs.mfu.GoodputTracker``) are plain attributes so a long-lived
    tracker — the Trainer wraps its steps once at construction — can be
    pointed at each ``fit()``'s sinks.
    """

    def __init__(self, events=None, goodput=None):
        self.events = events
        self.goodput = goodput
        self._state: Dict[str, Dict] = {}

    def wrap(self, fn: Callable, name: str, extra: Optional[Callable] = None) -> Callable:
        """``extra(args, kwargs) -> dict``, where given, adds fields to each
        ``compile`` row (the generator's cache geometry rides there)."""
        st = self._state.setdefault(
            name, {"calls": 0, "compiles": 0, "compile_s": 0.0}
        )

        def wrapped(*args, **kwargs):
            if self.events is not None:
                from perceiver_io_tpu.core.modules import mlp_gelu_plans, mlp_gelu_sites

                gelu_sites = mlp_gelu_sites()  # the row below counts this call's trace alone
            before = _cache_size(fn)
            closed = startup.mark()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            st["calls"] += 1
            after = _cache_size(fn)
            if after is not None and before is not None:
                compiled = after > before
            else:
                # no cache introspection: assume only the first call compiles
                compiled = st["calls"] == 1
            if compiled:
                st["compiles"] += 1
                st["compile_s"] += dt
                if self.goodput is not None:
                    self.goodput.add("compile", dt)
                if self.events is not None:
                    from perceiver_io_tpu.ops.flash_attention import tile_plans
                    from perceiver_io_tpu.ops.gathers import embed_tile_plans
                    from perceiver_io_tpu.ops.grouped_matmul import moe_tile_plans

                    flash_tiles, embed_tiles, mlp_gelu = tile_plans(), embed_tile_plans(), mlp_gelu_plans(since=gelu_sites)
                    moe_tiles = moe_tile_plans()
                    self.events.emit(
                        "compile",
                        fn=name,
                        wall_s=round(dt, 6),
                        **startup.compile_split(closed),
                        n_compiles=st["compiles"],
                        cache_size=after,
                        arg_shapes=shape_signature(args, kwargs),
                        **({"flash_tiles": flash_tiles} if flash_tiles else {}),
                        **({"embed_tiles": embed_tiles} if embed_tiles else {}),
                        **({"moe_tiles": moe_tiles} if moe_tiles else {}),
                        **({"mlp_gelu": mlp_gelu} if mlp_gelu else {}),
                        **(extra(args, kwargs) if extra is not None else {}),
                    )
            return out

        wrapped.__name__ = f"tracked_{name}"
        wrapped.__wrapped__ = fn
        return wrapped

    def counts(self) -> Dict[str, int]:
        return {name: st["compiles"] for name, st in self._state.items()}

    @property
    def total_compiles(self) -> int:
        return sum(st["compiles"] for st in self._state.values())

    @property
    def total_compile_s(self) -> float:
        return sum(st["compile_s"] for st in self._state.values())
