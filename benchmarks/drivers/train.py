"""Train cells: one compiled optimizer step, called back to back.

Set-up builds ONE object (the compiled step with its state), drives it
through its first ``checked_steps`` steps by the window's own call and feed,
and hands that same object to the window. While a step runs on the device
the host builds and places the next batch, as an input pipeline would; each
step ends with the host reading its loss. After the window the program's
state is freed and the plain reference follows the same first steps from the
same seed."""

from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import compare
from benchmarks.lib.weights import family_weights, flat_dict, seed_key, weight_builder
from benchmarks.reference import common as reference_common


def _leaf_norms(tree) -> dict:
    norms = jax.jit(lambda t: jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    return {k: float(v) for k, v in flat_dict(norms).items()}


def _first_moment(opt_state):
    """The Adam first-moment tree inside an optax state."""
    found = [s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


class TrainRun:
    """The compiled step, its state and its feed."""

    def __init__(self, ctx):
        from perceiver_io_tpu.training import TrainState, make_optimizer
        from perceiver_io_tpu.training.loop import make_train_step

        self.ctx, self.family, self.p = ctx, ctx.family, ctx.cell["params"]
        p, family = self.p, self.family
        self.batch_size = p["batch_size"]
        ctx.mark("program imported")
        model = family.model()
        self.build_weights = weight_builder(family.param_shapes(model), family.cfg["init_scale"])
        self.key = seed_key(ctx.seed)
        tx = make_optimizer(p["learning_rate"], gradient_clip=p["gradient_clip"],
                            weight_decay=p["weight_decay"], moment_dtype=p["adam_moment_dtype"])
        self.state = TrainState.create(model.apply, self.build_weights(self.key), tx, jax.random.PRNGKey(1))
        ctx.mark("state made from the seed")
        step = make_train_step(family.train_loss_fn(model), microbatch=p["microbatch"])
        self.n_fed = 0
        self.next_batch = self._place_batch()
        lowered = step.lower(self.state, self.next_batch)
        ctx.mark("step traced and lowered")
        self.step = lowered.compile()
        ctx.mark("step compiled or read from the cache")
        self.losses = []

    def _place_batch(self):
        with jax.profiler.TraceAnnotation("bench/batch_build"):
            batch = self.family.train_batch(self.ctx.seed, self.n_fed, self.batch_size)
            self.n_fed += 1
            return jax.device_put(batch)

    def advance(self) -> float:
        """One step: dispatch it, build the next batch meanwhile, read the loss."""
        with jax.profiler.TraceAnnotation("bench/step_dispatch"):
            self.state, metrics = self.step(self.state, self.next_batch)
        self.next_batch = self._place_batch()
        with jax.profiler.TraceAnnotation("bench/loss_fetch"):
            loss = float(metrics["loss"])
        self.losses.append(loss)
        return loss

    def checked_steps(self) -> dict:
        """The first steps, through ``advance``: their losses, the norm per
        leaf of the first gradient as the optimizer got it, and the norm per
        leaf of the parameters' change after them."""
        start = self.build_weights(self.key)["params"]
        first_grad = None
        for _ in range(self.p["checked_steps"]):
            self.advance()
            if first_grad is None:
                # kept on the host, in the moments' own dtype, until the reference has run
                first_grad = jax.device_get({"params": _first_moment(self.state.opt_state)["params"]})
        change = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(self.state.params["params"], start)
        first_grad = {k: np.asarray(v, np.float32) / (1.0 - 0.9) for k, v in flat_dict(first_grad).items()}
        return {"losses": list(self.losses), "first_grad": first_grad,
                "grad_norms": {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64)))) for k, v in first_grad.items()},
                "update_norms": _leaf_norms({"params": change})}

    def free(self):
        self.state = self.next_batch = self.step = None


def reference_steps(ctx, precision: str = "float32") -> dict:
    """The plain reference through the same first steps from the same seed."""
    family, p = ctx.family, ctx.cell["params"]
    weights = flat_dict(family_weights(family, ctx.seed, family.cfg["init_scale"]))
    batches = [
        {k: jnp.asarray(v) for k, v in family.reference_batch(family.train_batch(ctx.seed, i, p["batch_size"])).items()}
        for i in range(p["checked_steps"])
    ]
    return reference_common.follow_train_steps(
        family.reference_loss(precision), weights, batches, rows=p["reference_rows"],
        moment_dtype=jnp.dtype(p["adam_moment_dtype"] or "float32"), lr=p["learning_rate"],
        clip=p["gradient_clip"], weight_decay=p["weight_decay"],
    )


# a leaf whose reference gradient is under this share of the median leaf's has
# no gradient but rounding (a key bias: softmax ignores a shift of every
# score); Adam scales that noise up to full-size updates, so the parameters'
# change of such a leaf says nothing and is left out of its comparison
NO_GRADIENT = 1e-3


def compare_steps(got: dict, want: dict, limits: dict) -> list:
    """The numbers a train cell is judged by, each beside its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(got["losses"], want["losses"]))
    grad_gap, grad_leaf = compare.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    floor = NO_GRADIENT * statistics.median(want["grad_norms"].values())
    moved = [k for k, v in want["grad_norms"].items() if v >= floor]
    update_gap, update_leaf = compare.worst_leaf_gap({k: got["update_norms"][k] for k in moved},
                                                     {k: want["update_norms"][k] for k in moved})
    update_leaf += f"; {len(want['grad_norms']) - len(moved)} leaves without a gradient left out"
    differences = {
        k: float(np.sqrt(np.sum(np.square(np.asarray(got["first_grad"][k], np.float64) - np.asarray(g, np.float64)))))
        for k, g in want["first_grad"].items()
    }
    diff_gap, diff_leaf = compare.worst_leaf_difference(differences, want["grad_norms"])
    return [
        compare.check("grad_diff_gap", diff_gap, limits["grad_diff_gap"], f"worst leaf {diff_leaf}"),
        compare.check("loss_gap", loss_gap, limits["loss_gap"],
                      f"losses {got['losses']} against the reference's {want['losses']}"),
        compare.check("grad_norm_gap", grad_gap, limits["grad_norm_gap"], f"worst leaf {grad_leaf}"),
        compare.check("update_norm_gap", update_gap, limits["update_norm_gap"], f"worst leaf {update_leaf}"),
    ]


def step_times_line(t0: float, ends: list) -> str:
    """The window's steps one by one, for a reader who has to tell a run that
    stalled (a few steps far over the median, the others on it) from a step
    that got slower (the median moved). A single step's time is off by the
    host clock's half millisecond; the rate is taken over the whole window."""
    ms = [(b - a) * 1e3 for a, b in zip([t0] + ends, ends)]
    median = statistics.median(ms)
    slow = [(i, round(t, 1)) for i, t in enumerate(ms) if t > 1.5 * median]
    lost = sum(t - median for _, t in slow)
    return (f"step times: median {median:.2f} ms, min {min(ms):.2f}, max {max(ms):.2f}; "
            f"{len(slow)} of {len(ms)} steps over 1.5 x median, {lost:.0f} ms over it in all: {slow[:20]}")


def run(ctx) -> dict:
    run_ = TrainRun(ctx)
    got = run_.checked_steps()
    ctx.mark("checked steps done")
    ends = []  # the host clock at the end of each step of the window
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.window_seconds:
            run_.advance()
            ends.append(time.perf_counter())
        elapsed = ends[-1] - t0
    steps = len(ends)
    window_losses = run_.losses[-steps:]
    run_.free()
    del run_
    ctx.after_window()

    ctx.mark("window closed, program state freed")
    checks = compare_steps(got, reference_steps(ctx), ctx.cell["limits"])
    ctx.mark("reference followed the checked steps")
    n_bad = sum(not math.isfinite(x) for x in window_losses)
    checks.append(compare.check("nonfinite_window_losses", float(n_bad), 0.0, f"{steps} steps in the window"))
    samples = steps * ctx.cell["params"]["batch_size"]
    rate = samples / elapsed
    print(f"train: {steps} steps, {samples} samples in {elapsed:.4f} s; "
          f"{rate * ctx.family.units_per_sample:.1f} {ctx.family.sample_unit}/s; "
          f"last loss {window_losses[-1]:.4f}", flush=True)
    print(step_times_line(t0, ends), flush=True)
    return {
        "attempted": steps,
        "failed": n_bad,
        "checks": checks,
        "end_to_end": {"train_samples_per_s": rate},
        "counters": {"steps": steps, "samples": samples, "elapsed_s": elapsed, "samples_per_s": rate,
                     "batch_size": ctx.cell["params"]["batch_size"]},
    }


def control(ctx, precision: str) -> list:
    """The cell's compared numbers with the plain reference at ``precision``
    in the program's place (see ``benchmarks/control.py``)."""
    return compare_steps(reference_steps(ctx, precision), reference_steps(ctx), ctx.cell["limits"])
