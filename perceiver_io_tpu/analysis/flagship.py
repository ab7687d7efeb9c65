"""Graphlint targets for the flagship workload: the 16k Perceiver AR CLM
train step, prefill, and decode functions.

``tools/graphlint.py`` (CLI) and ``tests/test_analysis.py``'s real-graph
smoke build the SAME functions through :func:`build_targets`, so the lint
gate and the linted program can't drift apart; :func:`build_programs`
extends that to the graphcheck programs (adding the GSPMD sharded train
step), shared by ``analysis/fingerprint.py``'s contracts and the dataflow
rule gate (``tools/graphlint.py --programs all``, ``tasks.py perf``). The
per-target policies arm the dataflow rules — rng-key-reuse and
dead-compute everywhere, sharding-flow on the sharded steps, the decode ↔
prefill cross-program companion. Geometries:

- ``micro`` — the flagship architecture at toy sizes (same op structure,
  same scopes, seconds to compile on CPU). Graph-shape rules are geometry-
  invariant, so this is the default gate everywhere.
- ``flagship`` — the real 16384/1024 single-chip geometry
  (``chip_smoke.flagship_config``'s numbers); trace is fine anywhere,
  compiling it is a TPU-sized job.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

from perceiver_io_tpu.analysis.check import Report, check
from perceiver_io_tpu.analysis.rules import CompanionProgram, LintPolicy

# the known-good allowlist of the flagship graphs:
# - kv_concat: the prefix cross-attention builds its [prefix; latents] kv
#   tensor (core/modules.py CrossAttention, "kv_concat" scope): what the
#   program does, reviewed and accepted;
# - perceiver_ar._attend: the RoPE frequency-table [prefix; latents]
#   concat — a true sequence-axis concat, but of a (B, N, head_dim/2)
#   table (~1 MB f32 at 16k vs the kv build's 64 MB), reviewed and accepted
DEFAULT_ALLOW: Tuple[str, ...] = (
    "hot-concat:*kv_concat*",
    "hot-concat:*perceiver_ar._attend",
)

# dead-compute threshold for the flagship policies: a dead matmul-class op
# at/over 1 MFLOP is real lost work; smaller strays aggregate as warn/info
DEAD_COMPUTE_MIN_FLOPS = 1 << 20


def features_context(features: Optional[Sequence[str]]):
    """The trace-time kernel feature context shared by every flagship
    entry point (lint, the five-program gate, graphcheck fingerprints):
    an explicit feature set also forces the flash routes on — feature sets
    only exist there, and flash auto-enables on TPU only, so the traced
    graph matches the TPU program the set actually changes. ``None`` keeps
    the ambient/default kernels."""
    import contextlib

    from perceiver_io_tpu.ops.flash_attention import default_flash, fast_kernels

    if features is None:
        return contextlib.nullcontext()
    ctx = contextlib.ExitStack()
    ctx.enter_context(default_flash(True))
    ctx.enter_context(fast_kernels(set(features)))
    return ctx


GEOMETRIES = {
    # same architecture/op structure as the flagship, toy sizes; latents
    # stay >= 128 so the flash kernel routes (flash_supported) remain
    # eligible when a feature-set lint forces flash on
    "micro": dict(seq_len=512, latents=128, channels=64, heads=4, layers=2,
                  batch=2, decode_tokens=8),
    # chip_smoke.flagship_config's numbers (single v5e chip, 37M params)
    "flagship": dict(seq_len=16384, latents=1024, channels=512, heads=8,
                     layers=8, batch=4, decode_tokens=8),
}


@dataclasses.dataclass
class LintTarget:
    name: str
    fn: object
    args: tuple
    policy: LintPolicy
    allow: Tuple[str, ...]


def _clm_config(g: dict, remat: bool = False):
    from perceiver_io_tpu.models.text import CausalLanguageModelConfig

    return CausalLanguageModelConfig(
        vocab_size=262,
        max_seq_len=g["seq_len"],
        max_latents=g["latents"],
        num_channels=g["channels"],
        num_heads=g["heads"],
        num_self_attention_layers=g["layers"],
        cross_attention_dropout=0.5,
        activation_checkpointing=remat,
    )


def build_targets(
    geometry: str = "micro",
    targets: Sequence[str] = ("train", "prefill", "decode"),
    dtype=None,
    collective_budget: Optional[Dict[str, int]] = None,
    mesh=None,
    microbatch: Optional[int] = None,
    probes=None,
) -> Dict[str, LintTarget]:
    """Build the flagship functions and their lint policies.

    ``mesh``: a data/fsdp ``jax.sharding.Mesh`` shards the TRAIN target
    (state via ``shard_train_state``, batch via ``shard_batch``; the batch
    is padded up to the submesh): the GSPMD step, XLA owns the schedule.
    ``microbatch`` defaults to 2 on the sharded step.

    ``probes``: an ``obs.probes.ProbeConfig`` compiles the Probeline
    numerics telemetry into the (unsharded) TRAIN target — the
    ``train_probed`` contract program; its committed fingerprint proves
    probes add zero collectives, no callbacks and bounded const/temp bytes.

    Trace-time kernel features (``fast_kernels``) must be active around BOTH
    this call and the subsequent ``check`` — callers own the feature
    context."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.models.text import CausalLanguageModel
    from perceiver_io_tpu.training import TrainState, clm_loss_fn, make_optimizer
    from perceiver_io_tpu.training.loop import make_train_step

    g = GEOMETRIES[geometry]
    dtype = jnp.bfloat16 if dtype is None else dtype
    config = _clm_config(g)
    model = CausalLanguageModel(config, dtype=dtype)
    b, n = g["batch"], g["seq_len"]
    if mesh is not None:
        # batch must divide the data x fsdp submesh, with >= 2 samples per
        # device so the sharded step can microbatch-chunk
        dpf = mesh.shape["data"] * mesh.shape["fsdp"]
        b = dpf * max(2, -(-b // dpf))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, size=(b, n + 1))
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens[:, : g["latents"] + 1]), prefix_len=1
    )

    backend = jax.default_backend()
    # bf16 models must keep their projection matmuls bf16; the attention
    # kernels' f32 score/accumulator islands are deliberate numerics and
    # live outside these scopes
    bf16_scopes = ("*qkv_proj*",) if dtype == jnp.bfloat16 else ()
    # the dataflow rules run on every flagship target: RNG hygiene and dead
    # compute are program-shape properties, not geometry or mesh properties
    dataflow_policy = dict(check_rng=True, dead_compute_min_flops=DEAD_COMPUTE_MIN_FLOPS)
    out: Dict[str, LintTarget] = {}
    if "train" in targets:
        from perceiver_io_tpu.training.prefix_dropout import sample_prefix_keep_idx

        prefix_len = n - g["latents"]
        batch = {
            "labels": jnp.asarray(tokens[:, 1:]),
            "input_ids": jnp.asarray(tokens[:, :-1]),
            "pad_mask": None,
            "prefix_keep_idx": jnp.asarray(
                sample_prefix_keep_idx(rng, b, prefix_len, config.cross_attention_dropout)
            ),
        }
        tx = make_optimizer(1e-3, gradient_clip=1.0, moment_dtype="bfloat16")
        state = TrainState.create(model.apply, params, tx, jax.random.PRNGKey(1))
        loss_fn = clm_loss_fn(model.apply, max_latents=g["latents"])
        if probes is not None and mesh is not None:
            # loud, not dropped: a caller asking to fingerprint/lint a probed
            # SHARDED step would otherwise get a verdict about the unprobed
            # graph (the sharded contract program isn't built probed)
            raise ValueError(
                "probes= is only supported for the unsharded train target "
                "(the train_probed contract program); drop mesh= or probes="
            )
        if mesh is None:
            step = make_train_step(loss_fn, probes=probes)
            policy = LintPolicy(
                bf16_scopes=bf16_scopes,
                # the train step donates its state; required only where a
                # dropped donation costs HBM traffic (see donation-dropped)
                expect_donation=backend != "cpu",
                collective_budget=collective_budget,
                **dataflow_policy,
            )
        else:
            from perceiver_io_tpu.parallel.mesh import shard_batch
            from perceiver_io_tpu.training.loop import shard_train_state

            # min_weight_size=0 so the micro model actually fsdp-shards
            k = 2 if microbatch is None else microbatch
            state = shard_train_state(state, mesh, min_weight_size=0)
            batch = shard_batch(batch, mesh)
            step = make_train_step(loss_fn, microbatch=k)
            policy = LintPolicy(
                bf16_scopes=bf16_scopes,
                expect_donation=backend != "cpu",
                collective_budget=collective_budget,
                # the sharded step's args carry committed NamedShardings —
                # propagate them and predict GSPMD reshard points pre-compile
                # (the GSPMD microbatch chunk slices along the data-sharded
                # batch axis are REAL permutes — see train_sharded's
                # contract — reported at warn severity, not gated)
                sharding_flow=True,
                **dataflow_policy,
            )
        out["train"] = LintTarget(
            name="train_step",
            fn=step,
            args=(state, batch),
            policy=policy,
            allow=DEFAULT_ALLOW,
        )

    if "prefill" in targets or "decode" in targets or "decode_paged" in targets:
        from perceiver_io_tpu.generation import GenerationConfig, make_generate_fn

        prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(b, n)))
        fns = {
            tgt: make_generate_fn(
                model,
                g["latents"],
                GenerationConfig(max_new_tokens=new_tokens, do_sample=True, top_k=10),
                cache_dtype=dtype,
            )
            # the prefill fn is always built: it is the decode targets'
            # cross-program companion even when only decode is linted
            for tgt, new_tokens in (("prefill", 1), ("decode", g["decode_tokens"]))
        }
        for tgt, fn in fns.items():
            if tgt not in targets:
                continue
            out[tgt] = LintTarget(
                name=tgt,
                fn=fn,
                args=(params, prompt),
                policy=LintPolicy(
                    bf16_scopes=bf16_scopes,
                    collective_budget=collective_budget,
                    # the static guard ROADMAP item 4's cache interface is
                    # held to: decode must agree with prefill on KV-cache
                    # layout, dtype and append-index provenance
                    companion=(
                        CompanionProgram("prefill", fns["prefill"], (params, prompt))
                        if tgt == "decode"
                        else None
                    ),
                    **dataflow_policy,
                ),
                allow=DEFAULT_ALLOW,
            )
        if "decode_paged" in targets:
            # the ENGINE's batched paged decode step (serving.engine drives
            # the same fn): per-slot lengths/windows/rng chains over paged
            # caches. Companion = prefill (the disaggregated prompt pass);
            # the paged appends are DECLARED page-table-indexed, so the
            # cross-program rule holds them to the paged discipline instead
            # of ignoring scatter-based writes.
            fn, args = _build_decode_paged_args(model, config, params, g, dtype)
            out["decode_paged"] = LintTarget(
                name="decode_paged",
                fn=fn,
                args=args,
                policy=LintPolicy(
                    bf16_scopes=bf16_scopes,
                    collective_budget=collective_budget,
                    companion=CompanionProgram("prefill", fns["prefill"], (params, prompt)),
                    paged_cache_scopes=("*paged_kv_append*",),
                    **dataflow_policy,
                ),
                allow=DEFAULT_ALLOW,
            )
    if "decode_spec" in targets:
        # the SPECULATIVE draft/verify span (Specline): drafter scan + ONE
        # flagship verify forward + rejection-sampling accept + length-
        # counter rollback — the contract pins that no kv-axis concatenate
        # appears and the verify stays a single span-append per cache
        fn, args = _build_decode_spec_args(model, config, params, g, dtype)
        out["decode_spec"] = LintTarget(
            name="decode_spec",
            fn=fn,
            args=args,
            policy=LintPolicy(
                bf16_scopes=bf16_scopes,
                collective_budget=collective_budget,
                **dataflow_policy,
            ),
            allow=DEFAULT_ALLOW,
        )
    return out


# paged-step geometry per flagship geometry: tokens per KV page
PAGED_PAGE_SIZE = {"micro": 16, "flagship": 64}

# decode_spec program geometry: draft-span width and drafter depth — tiny
# on purpose (graph shape, not perf, is what the contract pins)
SPEC_K = 2
SPEC_DEPTH = 1


def _build_decode_spec_args(model, config, params, g: dict, dtype):
    """The ``decode_spec`` program: one speculative draft/verify span
    (``generation.make_speculative_decode_fns``' step fn) plus its
    post-prefill state (produced by actually running the jitted spec
    prefill at build time — the program under contract is the STEP).
    Half-window prompt and half the latent budget keep the no-slide
    validation satisfied at every geometry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perceiver_io_tpu.generation import GenerationConfig, make_speculative_decode_fns

    rng = np.random.default_rng(7)
    prompt_len = g["seq_len"] // 2
    num_latents = g["latents"] // 2
    prompt = jnp.asarray(rng.integers(0, config.vocab_size, size=(1, prompt_len)))
    prefill, step = make_speculative_decode_fns(
        model,
        num_latents,
        GenerationConfig(max_new_tokens=g["decode_tokens"], do_sample=True, top_k=10),
        k=SPEC_K,
        draft_depth=SPEC_DEPTH,
        cache_dtype=dtype,
    )
    _, state = prefill(params, prompt, None, jax.random.PRNGKey(0))
    return step, (state,)


def _build_decode_paged_args(model, config, params, g: dict, dtype):
    """The ``decode_paged`` program: ``make_paged_step_fn`` plus a
    representative mid-serve state — every slot occupied at prompt fill
    (the graph is shape-only; values just need to be plausible)."""
    import jax
    import jax.numpy as jnp

    from perceiver_io_tpu.core.modules import CausalSequenceModel
    from perceiver_io_tpu.generation import GenerationConfig, make_paged_step_fn

    slots = g["batch"]
    page = PAGED_PAGE_SIZE.get("flagship" if g["seq_len"] > 4096 else "micro", 16)
    ca_tokens = g["seq_len"] + g["decode_tokens"]
    sa_tokens = g["latents"] + g["decode_tokens"]
    ca_pps = -(-ca_tokens // page)
    sa_pps = -(-sa_tokens // page)
    caches = CausalSequenceModel.init_paged_cache(
        config, slots, page,
        ca_num_pages=1 + slots * ca_pps, ca_pages_per_slot=ca_pps,
        sa_num_pages=1 + slots * sa_pps, sa_pages_per_slot=sa_pps,
        dtype=dtype,
    )

    def occupied(c, pps, tokens):
        table = jnp.arange(1, 1 + slots * pps, dtype=jnp.int32).reshape(slots, pps)
        return dataclasses.replace(
            c,
            page_table=table,
            length=jnp.full((slots,), tokens, jnp.int32),
        )

    caches = (occupied(caches[0], ca_pps, g["seq_len"]),) + tuple(
        occupied(c, sa_pps, g["latents"]) for c in caches[1:]
    )
    state = {
        "cache": caches,
        "ca_start": jnp.zeros((slots,), jnp.int32),
        "sa_start": jnp.zeros((slots,), jnp.int32),
        "token": jnp.zeros((slots,), jnp.int32),
        "rng": jnp.stack([jax.random.PRNGKey(i) for i in range(slots)]),
        "done": jnp.zeros((slots,), bool),
        "pad_slots": jnp.zeros((slots, caches[0].capacity), bool),
        "pos_shift": jnp.zeros((slots, 1), jnp.int32),
    }
    fn = make_paged_step_fn(
        model, GenerationConfig(max_new_tokens=g["decode_tokens"], do_sample=True, top_k=10)
    )
    return fn, (params, state)


def lint_flagship(
    geometry: str = "micro",
    targets: Sequence[str] = ("train", "prefill", "decode"),
    rules: Optional[Sequence[str]] = None,
    allow: Sequence[str] = (),
    compiled: Optional[bool] = None,
    collective_budget: Optional[Dict[str, int]] = None,
    features: Optional[Sequence[str]] = None,
    mesh=None,
) -> Dict[str, Report]:
    """Lint the flagship functions; returns ``{target: Report}``.

    ``mesh``: shard the train target over a data/fsdp mesh and lint the
    GSPMD distributed step — see :func:`build_targets`.

    ``features``: trace-time kernel feature set to lint under (e.g.
    ``("paged",)``); ``None`` keeps the ambient/default set. Feature sets
    only exist on the flash kernel routes, which auto-enable on TPU only —
    so an explicit ``features`` also forces flash on (interpret-capable
    trace off-TPU), making the linted graph match the TPU program the
    feature set actually changes."""
    with features_context(features):
        built = build_targets(geometry, targets, collective_budget=collective_budget, mesh=mesh)
        return {
            key: check(
                t.fn,
                t.args,
                rules=rules,
                allow=tuple(t.allow) + tuple(allow),
                policy=t.policy,
                compiled=compiled,
                name=t.name,
            )
            for key, t in built.items()
        }


# the flagship programs graphcheck snapshots and the dataflow rules gate
# (tasks.py perf): flat train, the Probeline-instrumented flat train (the
# contract that probes add zero collectives/callbacks and bounded bytes),
# the GSPMD sharded train step on the DEFAULT_MESH_SPEC submesh, prefill,
# decode, the engine's batched paged decode step (decode_paged — PR 13
# Pageline), and the speculative draft/verify span (decode_spec — PR 14
# Specline)
PROGRAMS = (
    "train_flat", "train_probed", "train_sharded", "prefill", "decode",
    "decode_paged", "decode_spec",
)
DEFAULT_MESH_SPEC = "data=2,fsdp=2"


def build_programs(
    programs: Sequence[str] = PROGRAMS,
    geometry: str = "micro",
    mesh_spec: str = DEFAULT_MESH_SPEC,
) -> Dict[str, LintTarget]:
    """The flagship programs as lint targets — the SAME builds
    :func:`~perceiver_io_tpu.analysis.fingerprint.flagship_fingerprints`
    snapshots, so the lint gate and the contract gate cannot drift apart.
    The sharded step needs the ``mesh_spec`` submesh worth of devices
    (CLIs respawn with virtual CPU devices when the host is short)."""
    unknown = [p for p in programs if p not in PROGRAMS]
    if unknown:
        raise ValueError(f"unknown program(s) {unknown}; known: {PROGRAMS}")
    out: Dict[str, LintTarget] = {}
    flat = [
        p
        for p in ("train_flat", "prefill", "decode", "decode_paged", "decode_spec")
        if p in programs
    ]
    if flat:
        built = build_targets(
            geometry, targets=tuple({"train_flat": "train"}.get(p, p) for p in flat)
        )
        for p in flat:
            t = built[{"train_flat": "train"}.get(p, p)]
            out[p] = dataclasses.replace(t, name=p)
    if "train_probed" in programs:
        from perceiver_io_tpu.obs.probes import ProbeConfig

        t = build_targets(geometry, targets=("train",), probes=ProbeConfig())["train"]
        out["train_probed"] = dataclasses.replace(t, name="train_probed")
    if "train_sharded" in programs:
        from perceiver_io_tpu.parallel.mesh import mesh_from_spec

        t = build_targets(geometry, targets=("train",), mesh=mesh_from_spec(mesh_spec))["train"]
        out["train_sharded"] = dataclasses.replace(t, name="train_sharded")
    return out


def lint_programs(
    programs: Sequence[str] = PROGRAMS,
    geometry: str = "micro",
    mesh_spec: str = DEFAULT_MESH_SPEC,
    rules: Optional[Sequence[str]] = None,
    allow: Sequence[str] = (),
    compiled: Optional[bool] = None,
    features: Optional[Sequence[str]] = None,
) -> Dict[str, Report]:
    """Lint the flagship programs (``tools/graphlint.py --programs``,
    the ``tasks.py perf`` dataflow gate). Same ``features`` semantics as
    :func:`lint_flagship`."""
    with features_context(features):
        built = build_programs(programs, geometry=geometry, mesh_spec=mesh_spec)
        return {
            name: check(
                t.fn,
                t.args,
                rules=rules,
                allow=tuple(t.allow) + tuple(allow),
                policy=t.policy,
                compiled=compiled,
                name=name,
            )
            for name, t in built.items()
        }
