"""analysis/ (graphlint): each rule against a synthetic graph with a known
planted violation (positive) and a clean twin (negative), allowlist
behavior, the report/JSON surface, the trainer's ``graphlint`` event, and
a smoke lint of the real flagship step functions on CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_io_tpu import analysis
from perceiver_io_tpu.analysis import LintPolicy


# ---------------------------------------------------------------- dtype-drift


def test_dtype_drift_fires_on_f32_matmul_in_bf16_scope():
    def planted(x):
        with jax.named_scope("block"):
            return x.astype(jnp.float32) @ jnp.ones((8, 8), jnp.float32)

    report = analysis.check(
        planted,
        (jnp.ones((4, 8), jnp.bfloat16),),
        rules=("dtype-drift",),
        policy=LintPolicy(bf16_scopes=("*block*",)),
    )
    assert [v.rule for v in report.violations] == ["dtype-drift"]
    assert report.violations[0].scope == "block"
    assert not report.ok()


def test_dtype_drift_clean_on_bf16_matmul_and_undeclared_scope():
    def clean(x):
        with jax.named_scope("block"):
            return x @ jnp.ones((8, 8), jnp.bfloat16)

    policy = LintPolicy(bf16_scopes=("*block*",))
    x = jnp.ones((4, 8), jnp.bfloat16)
    assert analysis.check(clean, (x,), rules=("dtype-drift",), policy=policy).clean

    def f32_elsewhere(x):  # f32 matmul OUTSIDE the declared scope: fine
        return x.astype(jnp.float32) @ jnp.ones((8, 8), jnp.float32)

    assert analysis.check(f32_elsewhere, (x,), rules=("dtype-drift",), policy=policy).clean


# -------------------------------------------------------------- const-capture


def test_const_capture_fires_on_closed_over_weight():
    big = np.ones((256, 256), np.float32)  # 256 KB >= the 64 KB default

    def planted(x):
        return x @ big

    report = analysis.check(planted, (jnp.ones((4, 256)),), rules=("const-capture",))
    assert [v.rule for v in report.violations] == ["const-capture"]
    assert "256x256" in report.violations[0].message


def test_const_capture_clean_below_threshold_and_for_arguments():
    small = np.ones((16, 16), np.float32)  # 1 KB

    def clean(x):
        return x @ small

    assert analysis.check(clean, (jnp.ones((4, 16)),), rules=("const-capture",)).clean

    def weights_as_args(x, w):  # the fix the rule demands
        return x @ w

    big = jnp.ones((256, 256))
    assert analysis.check(
        weights_as_args, (jnp.ones((4, 256)), big), rules=("const-capture",)
    ).clean


# ----------------------------------------------------------------- hot-concat


def _seq_concat_in(scope_name):
    def fn(a, b):
        with jax.named_scope(scope_name):
            kv = jnp.concatenate([a, b], axis=1)  # (B, Np+Nq, C) seq-axis build
            return kv.sum()

    return fn


_A, _B = jnp.ones((2, 200, 32)), jnp.ones((2, 128, 32))


def test_hot_concat_fires_in_attention_scope():
    report = analysis.check(
        _seq_concat_in("cross_attend"), (_A, _B), rules=("hot-concat",)
    )
    assert [v.rule for v in report.violations] == ["hot-concat"]
    assert report.violations[0].op == "concatenate"
    assert "cross_attend" in report.violations[0].scope


def test_hot_concat_clean_outside_hot_scope_and_for_channel_glue():
    # same concat, cold scope: no violation
    assert analysis.check(
        _seq_concat_in("embed"), (_A, _B), rules=("hot-concat",)
    ).clean

    # RoPE-style channel-axis glue inside a hot scope: the concatenated
    # axis is short, the structural filter keeps it out
    def rotate_half(x):
        with jax.named_scope("cross_attend"):
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([-x2, x1], axis=-1).sum()

    assert analysis.check(
        rotate_half, (jnp.ones((2, 512, 32)),), rules=("hot-concat",)
    ).clean


def test_hot_concat_forbidden_dim_fires_anywhere():
    """The "never build this tensor" guarantee: a concat producing a tensor with the
    forbidden kv-length dimension ON THE CONCATENATED AXIS fires regardless
    of scope."""
    n_kv = _A.shape[1] + _B.shape[1]
    report = analysis.check(
        _seq_concat_in("embed"),  # cold scope — only the dim trigger applies
        (_A, _B),
        rules=("hot-concat",),
        policy=LintPolicy(concat_dim_sizes=(n_kv,)),
    )
    assert len(report.violations) == 1
    assert "forbidden dimension" in report.violations[0].message


def test_hot_concat_forbidden_dim_ignores_untouched_axes():
    """An axis that merely COINCIDES with the forbidden size must not fire:
    a channel-axis rotate-half concat on a (B, n_kv, C) tensor joins the
    last axis — the untouched seq axis equaling n_kv is not a kv build."""
    def rotate_half(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1).sum()

    report = analysis.check(
        rotate_half,
        (jnp.ones((2, 48, 8)),),
        rules=("hot-concat",),
        policy=LintPolicy(concat_dim_sizes=(48,)),
    )
    assert report.clean, report.format()


def test_hot_gather_fires_on_unsorted_gather_in_attention_scope():
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 512, size=(2048,)))

    def planted(table):
        with jax.named_scope("self_attend"):
            return jnp.take(table, idx, axis=0).sum()

    report = analysis.check(planted, (jnp.ones((512, 64)),), rules=("hot-concat",))
    assert [v.op for v in report.violations] == ["gather"]

    def cold(table):  # same gather outside the attention scopes: clean
        return jnp.take(table, idx, axis=0).sum()

    assert analysis.check(cold, (jnp.ones((512, 64)),), rules=("hot-concat",)).clean


# ------------------------------------------------------------ callback-in-jit


def test_callback_in_jit_fires_on_debug_print():
    def planted(x):
        with jax.named_scope("decode"):
            jax.debug.print("x={}", x.sum())
        return x * 2

    report = analysis.check(planted, (jnp.ones((4,)),), rules=("callback-in-jit",))
    assert [v.rule for v in report.violations] == ["callback-in-jit"]
    assert "decode" in report.violations[0].scope

    def clean(x):
        return x * 2

    assert analysis.check(clean, (jnp.ones((4,)),), rules=("callback-in-jit",)).clean


# ----------------------------------------------------------- donation-dropped


def test_donation_dropped_fires_when_donation_unusable():
    # the donated f32 buffer cannot back the bf16 output — jax drops the
    # donation at lowering and the compiled module carries no alias
    fn = jax.jit(lambda s: (s * 2).astype(jnp.bfloat16), donate_argnums=(0,))
    report = analysis.check(
        fn,
        (jnp.ones((64, 64), jnp.float32),),
        rules=("donation-dropped",),
        policy=LintPolicy(expect_donation=True),
    )
    assert [v.rule for v in report.violations] == ["donation-dropped"]
    # on CPU a dropped donation costs no HBM traffic: downgraded to warn
    assert report.violations[0].severity == ("warn" if jax.default_backend() == "cpu" else "error")
    assert not report.clean


def test_donation_rule_skipped_without_declared_donation():
    report = analysis.check(
        lambda x: x * 2, (jnp.ones((4,)),), rules=("donation-dropped",)
    )
    assert report.rules_skipped == ("donation-dropped",)
    assert report.clean


def test_donation_detected_from_lowered_module_with_compiled_true():
    """pjit hides donate_argnums attributes (jax 0.4.37), but with
    compiled=True the rule reads the lowered args_info — a donating jitted
    fn whose donation is dropped fires with NO policy hints."""
    fn = jax.jit(lambda s: (s * 2).astype(jnp.bfloat16), donate_argnums=(0,))
    report = analysis.check(
        fn, (jnp.ones((64, 64), jnp.float32),),
        rules=("donation-dropped",), compiled=True,
    )
    assert [v.rule for v in report.violations] == ["donation-dropped"]


def test_donation_committed_is_clean():
    # same-shape same-dtype donation: XLA commits the alias even on CPU
    fn = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
    report = analysis.check(
        fn,
        (jnp.ones((64, 64)), jnp.ones((64, 64))),
        rules=("donation-dropped",),
        policy=LintPolicy(expect_donation=True),
    )
    assert report.clean


# ---------------------------------------------------------- collective-budget


def _psum_fn():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("x",))
    fn = jax.shard_map(
        lambda x: jax.lax.psum(x, "x"), mesh=mesh, in_specs=P("x"), out_specs=P()
    )
    return jax.jit(fn), (jnp.ones((len(jax.devices()), 4)),)


def test_collective_budget_fires_over_budget():
    fn, args = _psum_fn()
    report = analysis.check(
        fn,
        args,
        rules=("collective-budget",),
        policy=LintPolicy(collective_budget={"all-reduce": 0}),
    )
    assert [v.op for v in report.violations] == ["all-reduce"]
    assert not report.ok()


def test_collective_budget_clean_within_budget_and_total_form():
    fn, args = _psum_fn()
    assert analysis.check(
        fn, args, rules=("collective-budget",),
        policy=LintPolicy(collective_budget={"all-reduce": 4}),
    ).clean
    report = analysis.check(
        fn, args, rules=("collective-budget",),
        policy=LintPolicy(collective_budget={"total": 0}),
    )
    assert len(report.violations) == 1 and "total budget" in report.violations[0].message


# --------------------------------------------------------- peak-memory-budget


def test_peak_memory_budget_fires_over_budget():
    def planted(x):
        return (x @ x.T).sum()  # 512x512 f32 temp = 1 MB

    x = jnp.ones((512, 128))
    report = analysis.check(
        planted, (x,), rules=("peak-memory-budget",),
        policy=LintPolicy(peak_memory_budget_bytes=64 << 10),
    )
    assert [v.rule for v in report.violations] == ["peak-memory-budget"]
    assert "MB" in report.violations[0].message and not report.ok()


def test_peak_memory_budget_clean_within_budget_and_skipped_undeclared():
    def fn(x):
        return (x @ x.T).sum()

    x = jnp.ones((512, 128))
    assert analysis.check(
        fn, (x,), rules=("peak-memory-budget",),
        policy=LintPolicy(peak_memory_budget_bytes=64 << 20),
    ).clean
    report = analysis.check(fn, (x,), rules=("peak-memory-budget",))
    assert report.rules_skipped == ("peak-memory-budget",)


# ----------------------------------------------------- replicated-large-tensor


def _mesh_2x4():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "fsdp"))


def _partitioned_matmul(w_spec):
    """x @ a with ``a`` placed by ``w_spec`` over a data x fsdp mesh — the
    compiled module is partitioned (num_partitions=8), so replication of
    ``a`` is a real per-device HBM choice."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh_2x4()
    a = jax.device_put(jnp.ones((512, 512)), NamedSharding(mesh, w_spec))  # 1 MB f32
    x = jax.device_put(jnp.ones((8, 512)), NamedSharding(mesh, P("data")))
    return jax.jit(lambda x, a: (x @ a).sum()), (x, a)


def test_replicated_large_tensor_fires_on_replicated_weight():
    from jax.sharding import PartitionSpec as P

    fn, args = _partitioned_matmul(P())  # fully replicated
    report = analysis.check(
        fn, args, rules=("replicated-large-tensor",),
        policy=LintPolicy(replicated_bytes_limit=1 << 20),
    )
    assert [v.rule for v in report.violations] == ["replicated-large-tensor"]
    assert "replicated" in report.violations[0].message


def test_replicated_large_tensor_clean_when_sharded_or_small_or_unpartitioned():
    from jax.sharding import PartitionSpec as P

    fn, args = _partitioned_matmul(P("fsdp"))  # sharded over fsdp: fine
    policy = LintPolicy(replicated_bytes_limit=1 << 20)
    assert analysis.check(fn, args, rules=("replicated-large-tensor",), policy=policy).clean

    fn, args = _partitioned_matmul(P())  # replicated but UNDER the limit
    assert analysis.check(
        fn, args, rules=("replicated-large-tensor",),
        policy=LintPolicy(replicated_bytes_limit=16 << 20),
    ).clean

    # single-device module: replication is not a choice — never fires
    plain = jax.jit(lambda x: (x @ jnp.ones((512, 512))).sum())
    assert analysis.check(
        plain, (jnp.ones((8, 512)),), rules=("replicated-large-tensor",), policy=policy
    ).clean


# ------------------------------------------------------------ implicit-reshard


def _ppermute_fn():
    from jax.sharding import Mesh, PartitionSpec as P

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("x",))
    fn = jax.shard_map(
        lambda x: jax.lax.ppermute(x, "x", [(i, (i + 1) % n) for i in range(n)]),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"),
    )
    return jax.jit(fn), (jnp.ones((n, 4)),)


def test_implicit_reshard_fires_on_unbudgeted_permute():
    fn, args = _ppermute_fn()
    report = analysis.check(
        fn, args, rules=("implicit-reshard",), policy=LintPolicy(reshard_budget={})
    )
    assert [v.op for v in report.violations] == ["collective-permute"]
    assert "reshard" in report.violations[0].message


def test_implicit_reshard_clean_within_budget_and_skipped_undeclared():
    fn, args = _ppermute_fn()
    assert analysis.check(
        fn, args, rules=("implicit-reshard",),
        policy=LintPolicy(reshard_budget={"collective-permute": 8}),
    ).clean
    report = analysis.check(fn, args, rules=("implicit-reshard",))
    assert report.rules_skipped == ("implicit-reshard",)


# -------------------------------------------------------------- rng-key-reuse


def test_rng_key_reuse_fires_on_double_draw():
    def planted(key):
        k1, _ = jax.random.split(key)
        return jax.random.uniform(k1, (4,)) + jax.random.uniform(k1, (4,))

    report = analysis.check(
        planted, (jax.random.PRNGKey(0),), rules=("rng-key-reuse",),
        policy=LintPolicy(check_rng=True),
    )
    assert [v.rule for v in report.violations] == ["rng-key-reuse"]
    assert "split" in report.violations[0].message and not report.ok()


def test_rng_key_reuse_clean_when_split_and_skipped_undeclared():
    def clean(key):
        k1, k2 = jax.random.split(key)
        return jax.random.uniform(k1, (4,)) + jax.random.uniform(k2, (4,))

    assert analysis.check(
        clean, (jax.random.PRNGKey(0),), rules=("rng-key-reuse",),
        policy=LintPolicy(check_rng=True),
    ).clean

    report = analysis.check(clean, (jax.random.PRNGKey(0),), rules=("rng-key-reuse",))
    assert report.rules_skipped == ("rng-key-reuse",)


def _shard_map_draw(fold_device_index: bool):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(-1), ("data",))

    def body(x, key):
        if fold_device_index:
            key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        return x * jax.random.uniform(key, x.shape)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"),
        check_vma=False,
    )
    return fn, (jnp.ones((8, 4)), jax.random.PRNGKey(0))


def test_rng_key_reuse_fires_on_replicated_key_in_shard_map():
    fn, args = _shard_map_draw(fold_device_index=False)
    report = analysis.check(
        fn, args, rules=("rng-key-reuse",), policy=LintPolicy(check_rng=True)
    )
    assert [v.rule for v in report.violations] == ["rng-key-reuse"]
    assert "REPLICATED" in report.violations[0].message


def test_rng_key_reuse_clean_with_device_index_fold():
    fn, args = _shard_map_draw(fold_device_index=True)
    assert analysis.check(
        fn, args, rules=("rng-key-reuse",), policy=LintPolicy(check_rng=True)
    ).clean


# --------------------------------------------------------------- dead-compute


def test_dead_compute_weights_matmul_error_reshape_info():
    def planted(x):
        dead_mm = x @ x.T  # noqa: F841 — 33 MFLOP of dead compute
        dead_rs = jnp.reshape(x, (-1,))  # noqa: F841 — dead data movement
        return jnp.tanh(x).sum()

    report = analysis.check(
        planted, (jnp.ones((256, 256)),), rules=("dead-compute",),
        policy=LintPolicy(dead_compute_min_flops=1 << 20),
    )
    errors = [v for v in report.violations if v.severity == "error"]
    assert [v.op for v in errors] == ["dot_general"]
    assert "MFLOP" in errors[0].message and not report.ok()
    infos = [v for v in report.violations if v.severity == "info"]
    assert infos and "data-movement" in infos[0].message


def test_dead_compute_clean_and_skipped_undeclared():
    def clean(x):
        return (x @ x.T).sum()

    policy = LintPolicy(dead_compute_min_flops=1 << 20)
    assert analysis.check(
        clean, (jnp.ones((128, 128)),), rules=("dead-compute",), policy=policy
    ).clean
    report = analysis.check(clean, (jnp.ones((128, 128)),), rules=("dead-compute",))
    assert report.rules_skipped == ("dead-compute",)


# -------------------------------------------------------------- sharding-flow


def test_sharding_flow_predicts_reshard_points():
    from jax.sharding import PartitionSpec as P

    def planted(x, y):
        a = x[0:2]  # slice along the data-sharded batch dim
        return a.sum() + (x + y).sum()  # and a data-vs-fsdp elementwise join

    report = analysis.check(
        planted,
        (jnp.ones((4, 4)), jnp.ones((4, 4))),
        rules=("sharding-flow",),
        policy=LintPolicy(sharding_flow=(P("data"), P("fsdp"))),
    )
    kinds = sorted(v.message.split(" ")[1] for v in report.violations)
    assert kinds == ["mismatched-operands", "sliced-sharded-dim"]
    assert all("chain:" in v.message for v in report.violations)


def test_sharding_flow_clean_when_aligned_and_skipped_undeclared():
    from jax.sharding import PartitionSpec as P

    def clean(x, w):
        return jnp.tanh(x @ w).sum()

    args = (jnp.ones((8, 16)), jnp.ones((16, 4)))
    assert analysis.check(
        clean, args, rules=("sharding-flow",),
        policy=LintPolicy(sharding_flow=(P("data"), P(None, "fsdp"))),
    ).clean
    report = analysis.check(clean, args, rules=("sharding-flow",))
    assert report.rules_skipped == ("sharding-flow",)


def test_sharding_flow_agrees_with_compiled_reshard_contracts():
    """The acceptance pin: sharding-flow's pre-compile predictions must
    agree with the compiled-HLO reshard findings recorded in the committed
    contract — train_sharded (GSPMD microbatch chunk slices along the
    data-sharded batch axis) compiles with collective-permutes and must be
    predicted."""
    from perceiver_io_tpu.analysis.flagship import build_programs

    contracts_dir = os.path.join(os.path.dirname(os.path.dirname(__file__)), "contracts")
    name = "train_sharded"
    target = build_programs((name,))[name]
    report = analysis.check(
        target.fn, target.args, rules=("sharding-flow",),
        policy=target.policy, compiled=False, name=name,
    )
    with open(os.path.join(contracts_dir, f"{name}.json")) as f:
        coll = json.load(f)["fingerprint"].get("collectives", {})
    compiled_reshards = sum(
        coll.get(k, {}).get("count", 0) for k in ("all-to-all", "collective-permute")
    )
    predicted = len(report.violations)
    assert (predicted > 0) == (compiled_reshards > 0), (
        f"{name}: predicted {predicted} reshard point(s) vs "
        f"{compiled_reshards} compiled reshard collective(s)\n{report.format()}"
    )


# -------------------------------------------------- cross-program-consistency


def _cache_pair(loop_steps=0, bad_index=False, loop_dtype=None):
    """A toy prefill/decode pair with labeled cache appends: the prompt
    phase writes the prompt at offset 0, the decode loop appends one slot
    at the carried length (or, planted, at a CONSTANT slot / wrong dtype)."""
    from jax import lax

    def prog(x):
        dtype = jnp.dtype(loop_dtype) if loop_dtype else x.dtype
        cache = jnp.zeros((2, 16, 4), dtype)
        with jax.named_scope("prefill"), jax.named_scope("kv_cache_append"):
            cache = lax.dynamic_update_slice(cache, x.astype(dtype), (0, 0, 0))
        if loop_steps == 0:
            return cache.sum()
        length = jnp.asarray(x.shape[1], jnp.int32)

        def step(carry, _):
            cache, length = carry
            upd = jnp.ones((2, 1, 4), dtype)
            idx = jnp.zeros((), jnp.int32) if bad_index else length
            with jax.named_scope("decode"), jax.named_scope("kv_cache_append"):
                cache = lax.dynamic_update_slice(cache, upd, (0, idx, 0))
            return (cache, length + 1), cache.sum()

        (_, _), ys = lax.scan(step, (cache, length), None, length=loop_steps)
        return ys.sum()

    return prog


def test_cross_program_consistency_clean_on_agreeing_pair():
    from perceiver_io_tpu.analysis import CompanionProgram

    x = jnp.ones((2, 4, 4))
    report = analysis.check(
        _cache_pair(loop_steps=3), (x,),
        rules=("cross-program-consistency",),
        policy=LintPolicy(
            companion=CompanionProgram("prefill", _cache_pair(loop_steps=0), (x,))
        ),
    )
    assert report.clean, report.format()


def test_cross_program_consistency_fires_on_static_append_index():
    from perceiver_io_tpu.analysis import CompanionProgram

    x = jnp.ones((2, 4, 4))
    report = analysis.check(
        _cache_pair(loop_steps=3, bad_index=True), (x,),
        rules=("cross-program-consistency",),
        policy=LintPolicy(
            companion=CompanionProgram("prefill", _cache_pair(loop_steps=0), (x,))
        ),
    )
    assert not report.ok()
    assert any("provenance" in v.message for v in report.violations)


def test_cross_program_consistency_fires_on_dtype_mismatch():
    from perceiver_io_tpu.analysis import CompanionProgram

    x = jnp.ones((2, 4, 4))
    report = analysis.check(
        _cache_pair(loop_steps=3, loop_dtype=jnp.bfloat16), (x,),
        rules=("cross-program-consistency",),
        policy=LintPolicy(
            companion=CompanionProgram("prefill", _cache_pair(loop_steps=0), (x,))
        ),
    )
    assert not report.ok()
    assert any("layout/dtype" in v.message for v in report.violations)


def test_cross_program_consistency_skipped_without_companion():
    report = analysis.check(
        _cache_pair(), (jnp.ones((2, 4, 4)),), rules=("cross-program-consistency",)
    )
    assert report.rules_skipped == ("cross-program-consistency",)


# ----------------------------------------------------- allowlist + report API


def test_allowlist_by_rule_and_by_scope_key():
    fn, args = _seq_concat_in("cross_attend"), (_A, _B)
    by_rule = analysis.check(fn, args, rules=("hot-concat",), allow=("hot-concat",))
    assert by_rule.ok() and by_rule.clean and len(by_rule.allowed) == 1

    by_key = analysis.check(
        fn, args, rules=("hot-concat",), allow=("hot-concat:*cross_attend*",)
    )
    assert by_key.clean and len(by_key.allowed) == 1

    miss = analysis.check(
        fn, args, rules=("hot-concat",), allow=("hot-concat:*decode*",)
    )
    assert not miss.clean and not miss.allowed


def test_allowlist_scope_separator_patterns():
    """fnmatch '*' crosses '/' — a pattern anchored at a scope-path TAIL
    (``*/kv_concat``-style) matches the site at any nesting depth, while a
    tail mismatch stays a violation (the DEFAULT_ALLOW entries in
    analysis/flagship.py rely on exactly this)."""

    def nested(a, b):
        with jax.named_scope("cross_attend"):
            with jax.named_scope("kv_concat"):
                return jnp.concatenate([a, b], axis=1).sum()

    args = (_A, _B)
    report = analysis.check(nested, args, rules=("hot-concat",))
    assert [v.scope for v in report.violations] == ["cross_attend/kv_concat"]

    # tail-anchored: any nesting above the labeled site
    tail = analysis.check(nested, args, rules=("hot-concat",), allow=("*/kv_concat",))
    assert tail.clean and len(tail.allowed) == 1

    # rule-qualified with a separator inside the scope part
    qualified = analysis.check(
        nested, args, rules=("hot-concat",), allow=("hot-concat:*/kv_concat",)
    )
    assert qualified.clean and len(qualified.allowed) == 1

    # a DIFFERENT tail does not match — the separator is load-bearing
    miss = analysis.check(nested, args, rules=("hot-concat",), allow=("*/q_concat",))
    assert not miss.clean and not miss.allowed

    # the site WITHOUT an enclosing scope: '*/kv_concat' requires a parent
    def flat(a, b):
        with jax.named_scope("kv_concat"):
            return jnp.concatenate([a, b], axis=1).sum()

    top = analysis.check(flat, args, rules=("hot-concat",), allow=("*/kv_concat",))
    assert not top.clean, "tail pattern must not match a parentless scope"
    assert analysis.check(
        flat, args, rules=("hot-concat",), allow=("*kv_concat",)
    ).clean


def test_unknown_rule_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        analysis.check(lambda x: x, (jnp.ones(1),), rules=("no-such-rule",))


def test_report_surface():
    report = analysis.check(
        _seq_concat_in("cross_attend"), (_A, _B), rules=("hot-concat",)
    )
    d = json.loads(report.to_json())
    assert d["counts"]["error"] == 1 and d["violations"][0]["rule"] == "hot-concat"
    assert "hot-concat" in report.format()
    with pytest.raises(analysis.GraphLintError):
        report.raise_if("error")
    report.raise_if("none")  # no-op


def test_invalid_severity_override_rejected_at_config_time():
    with pytest.raises(ValueError, match="invalid severity"):
        analysis.check(
            lambda x: x, (jnp.ones(1),),
            policy=LintPolicy(severity_overrides={"hot-concat": "warning"}),
        )


def test_severity_override_respected():
    report = analysis.check(
        _seq_concat_in("cross_attend"),
        (_A, _B),
        rules=("hot-concat",),
        policy=LintPolicy(severity_overrides={"hot-concat": "info"}),
    )
    assert report.ok() and report.count("info") == 1


# -------------------------------------------------- trainer graphlint event


def test_trainer_emits_graphlint_event_with_planted_const(tmp_path):
    from perceiver_io_tpu.training.metrics import MetricsLogger
    from perceiver_io_tpu.training.optim import make_optimizer
    from perceiver_io_tpu.training.state import TrainState
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    baked = np.ones((200, 200), np.float32)  # 160 KB closed-over "weight"

    def apply_fn(p, x):
        return (x @ p["w"]) @ baked

    def loss_fn(p, batch, rng):
        out = apply_fn(p, batch["x"])
        return jnp.mean(out**2), {"loss": jnp.mean(out**2)}

    state = TrainState.create(
        apply_fn, {"w": jnp.ones((8, 200))}, make_optimizer(1e-3), jax.random.PRNGKey(0)
    )
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(loss_fn, config=TrainerConfig(max_steps=2, log_interval=1), logger=logger)

    def batches():
        while True:
            yield {"x": jnp.ones((2, 8))}

    state = trainer.fit(state, batches())
    assert int(state.step) == 2
    events = [json.loads(l) for l in open(os.path.join(str(tmp_path), "events.jsonl"))]
    gl = [e for e in events if e["event"] == "graphlint"]
    assert len(gl) == 1, "exactly one graphlint event per fit"
    assert gl[0]["ok"] is False and gl[0]["counts"]["error"] >= 1
    assert any(v["rule"] == "const-capture" for v in gl[0]["violations"])
    # the trace-level fingerprint rides alongside as a graphcheck event —
    # the planted 160 KB const shows up in its captured-const bytes
    gc = [e for e in events if e["event"] == "graphcheck"]
    assert len(gc) == 1, "exactly one graphcheck event per fit"
    assert gc[0]["captured_const_bytes"] >= 160_000
    assert gc[0]["n_ops"] >= 1 and "dtype_histogram" in gc[0]


def test_trainer_graphlint_off_emits_nothing(tmp_path):
    from perceiver_io_tpu.training.metrics import MetricsLogger
    from perceiver_io_tpu.training.optim import make_optimizer
    from perceiver_io_tpu.training.state import TrainState
    from perceiver_io_tpu.training.trainer import Trainer, TrainerConfig

    def loss_fn(p, batch, rng):
        out = batch["x"] @ p["w"]
        return jnp.mean(out**2), {"loss": jnp.mean(out**2)}

    state = TrainState.create(
        None, {"w": jnp.ones((8, 8))}, make_optimizer(1e-3), jax.random.PRNGKey(0)
    )
    logger = MetricsLogger(str(tmp_path), use_tensorboard=False)
    trainer = Trainer(
        loss_fn,
        config=TrainerConfig(max_steps=1, log_interval=1, graphlint=False, graphcheck=False),
        logger=logger,
    )
    trainer.fit(state, iter([{"x": jnp.ones((2, 8))}] * 2))
    events = [json.loads(l) for l in open(os.path.join(str(tmp_path), "events.jsonl"))]
    assert not [e for e in events if e["event"] in ("graphlint", "graphcheck")]


# ------------------------------------------------------- flagship smoke (CPU)


def test_flagship_micro_lint_is_clean():
    """The real flagship train/prefill/decode graphs lint clean at micro
    geometry with the documented default allowlist — the gate
    `tasks.py graphlint` runs."""
    from perceiver_io_tpu.analysis.flagship import lint_flagship

    reports = lint_flagship(geometry="micro")
    assert set(reports) == {"train", "prefill", "decode"}
    for name, report in reports.items():
        assert report.ok(), f"{name}:\n{report.format()}"
        # the default-route kv concat is allowlisted, not silently absent
    assert any("kv_concat" in v.key for v in reports["train"].allowed)
