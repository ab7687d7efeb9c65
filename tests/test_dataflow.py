"""analysis/dataflow.py (the def-use / provenance engine): value threading
through pjit/scan/cond/custom_vjp bodies, reachability and liveness, the
provenance-chain renderer (golden), FLOPs weighting, PRNG key identity, and
the sharding propagator's transfer rules — engine-level coverage; the rules
built on top are covered in tests/test_analysis.py."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perceiver_io_tpu.analysis import dataflow as D


# ------------------------------------------------------------ def-use basics


def test_def_use_and_io_wiring():
    def f(x, y):
        a = x * 2.0
        return a + y

    df = D.analyze(f, jnp.ones((4,)), jnp.ones((4,)))
    assert len(df.input_vids) == 2
    mul = next(n for n in df.nodes if n.primitive == "mul")
    add = next(n for n in df.nodes if n.primitive == "add")
    # x is consumed by the mul, the mul's output by the add
    assert mul.nid in df.values[df.input_vids[0]].uses
    assert add.nid in df.values[mul.outvals[0]].uses
    assert df.def_node(add.outvals[0]).nid == add.nid
    assert df.output_vids == [add.outvals[0]]


def test_threading_through_pjit_boundary():
    """A value flowing into a jitted sub-call is the SAME dataflow value
    inside the body — the chain crosses the pjit boundary."""

    inner = jax.jit(lambda v: jnp.tanh(v))

    def f(x):
        return inner(x * 2.0).sum()

    df = D.analyze(f, jnp.ones((4,)))
    mul = next(n for n in df.nodes if n.primitive == "mul")
    tanh = next(n for n in df.nodes if n.primitive == "tanh")
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert tanh.parent is not None and df.nodes[tanh.parent].primitive == "jit"
    chain = df.find_chain(mul.nid, red.nid)
    assert chain is not None
    assert [n.primitive for n in chain if n.primitive != "jit"] == [
        "mul", "tanh", "reduce_sum"
    ]


def test_scan_threading_carry_loopback_and_dead_body_op():
    def f(xs, init):
        def body(c, x):
            dead = c * 3.0  # noqa: F841 — feeds nothing
            c2 = c + x
            return c2, c2 * 2.0
        c, ys = lax.scan(body, init, xs)
        return ys

    df = D.analyze(f, jnp.ones((3, 2)), jnp.zeros((2,)))
    assert df.loop_vids, "scan carry binders must be marked loop-carried"
    dead = df.dead_nodes()
    assert [(n.primitive, n.region) for n in dead] == [("mul", ("scan",))]
    # the final-carry output is unused; ys reach the output through the loop
    add = next(n for n in df.nodes if n.primitive == "add")
    assert add.nid in df.live_node_ids()


def test_cond_threading_merges_branches():
    def f(p, x):
        return lax.cond(p, lambda v: v * 2.0, lambda v: v + 1.0, x).sum()

    df = D.analyze(f, jnp.asarray(True), jnp.ones((3,)))
    mul = next(n for n in df.nodes if n.primitive == "mul")
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert "cond" in mul.region
    assert df.find_chain(mul.nid, red.nid) is not None


def test_custom_vjp_body_is_threaded():
    @jax.custom_vjp
    def g(x):
        return jnp.sin(x)

    g.defvjp(lambda x: (jnp.sin(x), x), lambda x, ct: (ct * jnp.cos(x),))

    def f(x):
        return g(x * 2.0).sum()

    df = D.analyze(f, jnp.ones((4,)))
    sin = next((n for n in df.nodes if n.primitive == "sin"), None)
    assert sin is not None, "custom_vjp body not inlined"
    mul = next(n for n in df.nodes if n.primitive == "mul")
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert df.find_chain(mul.nid, red.nid) is not None


# --------------------------------------------------------- provenance golden


def test_provenance_chain_rendering_golden():
    """The renderer is part of the rule-message contract: one op per line,
    ``primitive dtype[shape] @ scope``."""

    def f(x, y):
        with jax.named_scope("enc"):
            h = x @ y
        with jax.named_scope("head"):
            return jnp.tanh(h).sum()

    df = D.analyze(f, jnp.ones((4, 4)), jnp.ones((4, 4)))
    src = next(n for n in df.nodes if n.primitive == "dot_general")
    dst = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert df.provenance(src.nid, dst.nid) == (
        "dot_general float32[4x4] @ enc\n"
        "-> tanh float32[4x4] @ head\n"
        "-> reduce_sum float32[] @ head"
    )


def test_provenance_chain_elides_long_middles():
    def f(x):
        for _ in range(12):
            x = x + 1.0
        return x.sum()

    df = D.analyze(f, jnp.ones((4,)))
    first = next(n for n in df.nodes if n.primitive == "add")
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    text = df.provenance(first.nid, red.nid, max_ops=4)
    assert "... (" in text and text.count("\n") == 4  # 4 ops + 1 elision line


# ------------------------------------------------------------ liveness/FLOPs


def test_effectful_op_keeps_feeders_live():
    def f(x):
        s = x.sum()  # feeds only the debug print
        jax.debug.print("s={}", s)
        return x * 2.0

    df = D.analyze(f, jnp.ones((4,)))
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert red.nid in df.live_node_ids(), "effect sinks must keep feeders live"
    assert all(n.primitive != "reduce_sum" for n in df.dead_nodes())


def test_node_flops_dot_general_exact():
    def f(a, b):
        return a @ b

    df = D.analyze(f, jnp.ones((8, 32)), jnp.ones((32, 16)))
    dot = next(n for n in df.nodes if n.primitive == "dot_general")
    assert D.node_flops(dot, df.values) == 2 * 8 * 16 * 32


# ------------------------------------------------------------- key identity


def test_key_identity_tells_split_rows_apart():
    def f(key):
        k1, k2 = jax.random.split(key)
        return jax.random.uniform(k1, (4,)) + jax.random.normal(k2, (4,))

    assert D.rng_reuse_findings(D.analyze(f, jax.random.PRNGKey(0))) == []

    def g(key):
        k1, _ = jax.random.split(key)
        return jax.random.uniform(k1, (4,)) + jax.random.uniform(k1, (4,))

    findings = D.rng_reuse_findings(D.analyze(g, jax.random.PRNGKey(0)))
    assert [f.kind for f in findings] == ["draw-draw"]
    assert len(findings[0].sink_nids) == 2


def test_draw_then_split_is_a_finding():
    def f(key):
        u = jax.random.uniform(key, (4,))
        k1, _ = jax.random.split(key)  # children correlate with the draw
        return u + jax.random.uniform(k1, (4,))

    kinds = [x.kind for x in D.rng_reuse_findings(D.analyze(f, jax.random.PRNGKey(0)))]
    assert "draw-derive" in kinds


# -------------------------------------------------------- sharding propagator


def test_propagate_shardings_transfer_rules():
    from jax.sharding import PartitionSpec as P

    def f(x, w):
        h = x @ w            # (data, None) @ (None, fsdp) -> (data, fsdp)
        h = jnp.tanh(h)      # elementwise keeps the layout
        return h.sum(axis=1)  # reduce drops the fsdp dim

    df = D.analyze(f, jnp.ones((8, 16)), jnp.ones((16, 4)))
    conflicts, state = D.propagate_shardings(df, [P("data"), P(None, "fsdp")])
    assert conflicts == []
    red = next(n for n in df.nodes if n.primitive == "reduce_sum")
    assert state[red.outvals[0]] == (("data",),)


def test_propagate_shardings_predicts_reshard_points():
    from jax.sharding import PartitionSpec as P

    def f(x, y):
        a = x[0:2]  # slice along the data-sharded dim: permute predicted
        return a, x + y  # dim 0: data vs fsdp — mismatched operands

    df = D.analyze(f, jnp.ones((4, 4)), jnp.ones((4, 4)))
    conflicts, _ = D.propagate_shardings(df, [P("data"), P("fsdp")])
    kinds = sorted(c.kind for c in conflicts)
    assert kinds == ["mismatched-operands", "sliced-sharded-dim"]


def test_propagate_shardings_drops_layouts_across_scan_rank_changes():
    """A scan's stacked xs (rank r+1) alias to per-iteration slices (rank
    r): carrying the stacked layout across would shift mesh axes onto the
    wrong dims and invent phantom conflicts. The layout must become
    unknown at the rank change, not misindexed."""
    from jax.sharding import PartitionSpec as P

    def f(xs, h):
        def body(c, x):
            return c + x, c.sum()  # carry(fsdp@1) joins x — NOT a conflict

        c, ys = lax.scan(body, h, xs)
        return c, ys

    df = D.analyze(f, jnp.ones((3, 4, 8)), jnp.zeros((4, 8)))
    # stacked xs sharded 'data' on dim 1 == the slice's dim 0, carry 'fsdp'
    # on dim 1: same-rank transfer would see a dim-1 data-vs-fsdp clash
    conflicts, _ = D.propagate_shardings(df, [P(None, "data"), P(None, "fsdp")])
    assert conflicts == [], conflicts


def test_propagate_shardings_skips_shard_map_interiors():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(-1), ("data",))

    def f(x):
        def body(x):
            return x[0:1] * 2.0  # a slice of the LOCAL shard: not a reshard

        return jax.shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
        )(x)

    df = D.analyze(f, jnp.ones((8, 4)))
    conflicts, state = D.propagate_shardings(df, [P("data")])
    assert conflicts == []
    sm = next(n for n in df.nodes if n.primitive == "shard_map")
    # region outputs take their layout from out_specs
    assert state[sm.outvals[0]] == (("data",), None)
