"""Pageline — the continuous-batching serving engine on a paged KV cache.

ROADMAP item 1, landed behind the PR-12 admission tier: where
:class:`~perceiver_io_tpu.serving.frontend.RequestFrontEnd` serializes
requests one worker at a time through the instrumented single-request path,
:class:`EngineFrontEnd` keeps a fixed set of **decode slots** hot and drives
them through ONE compiled batched step:

- **admission** is inherited verbatim — bounded queue, deadline projection,
  breaker, drain, clean books — plus a page-fit check: a request whose KV
  footprint could never fit the page pool sheds ``kv_pages_exhausted`` at
  admission (a first-class PR-12 shed, never a silent drop);
- **prefill/decode disaggregation**: a joining request's prompt runs the
  committed contiguous ``prefill`` program (batch 1 — prefill is
  compute-bound and token-exactness rides the existing program), then
  ``core.cache.commit_prefill`` lands its KV rows in freshly allocated
  pages (``serving.pages.PageAllocator``) and the slot enters the batch;
- **continuous batching**: every engine step decodes one token for every
  active slot (``generation.make_paged_step_fn`` — per-slot lengths, window
  counters, rng chains, so each slot's stream is token-exact vs the
  sequential path); finished/cancelled/expired slots retire between steps,
  their pages return to the free list, and queued requests join without
  draining the batch — the classic join/retire loop of *Ragged Paged
  Attention* (arXiv:2604.15464) and the Gemma-on-TPU serving comparison
  (arXiv:2605.25645);
- **telemetry**: per-request ``request`` events with TTFT, a real TPOT
  histogram, queue wait and the new optional ``batch_size_at_decode``
  field; ``engine_batch_fill_frac`` / ``engine_kv_pages_used`` gauges in
  the shared registry (rendered by ``tools/obs_report.py``); mid-decode
  kill/cancel/deadline land as terminal outcomes with the slot AND its
  pages freed — ``tools/chaos.py serve_engine_*`` certifies books + pages;
- **page-pressure eviction + crash recovery** (Evictline,
  docs/robustness.md#engine-eviction-and-recovery): with
  ``EngineConfig(eviction=True)`` a queued request that fits the pool but
  not the free list reclaims pages from the least-progressed in-flight
  slot — the victim is PARKED (prompt, served tokens, rng position kept)
  and later resumed **token-exactly** by replaying the existing prefill
  program over ``prompt + emitted prefix`` with the latent count grown by
  one per emitted token and the rng chain advanced one split per emitted
  token (``generation.advance_rng_chain``); the books identity extends to
  ``submitted == terminal + queued + in_flight + parked``. A
  ``serving.journal.RequestJournal`` makes the same replay survive the
  ENGINE's death: :meth:`EngineFrontEnd.recover` on a fresh engine
  re-admits every journaled non-terminal request and resumes it from its
  journaled progress — ``tools/chaos.py serve_evict_storm`` /
  ``serve_crash_recover`` certify both.
- **cross-request prefix sharing** (Shareline, docs/serving.md
  #prefix-sharing): every unshared join publishes its prompt's full
  context-region pages into a radix prefix index
  (``serving.prefix.PrefixIndex``, page-size token chunks content-hashed);
  a later request whose prompt matches a resident run joins through
  ``generation.make_shared_prefill_fn`` — the matched pages' CA rows are
  gathered straight out of the pool and prefill compute runs over the
  unshared SUFFIX only, so TTFT collapses and the refcounted allocator
  (``PageAllocator.alloc_tokens_shared``) holds ONE copy of the shared
  run. Token-exactness is structural, not approximate: context-region KV
  rows under rotate-at-write RoPE depend only on (token id, absolute
  position), the suffix carries ALL latents, and anything outside those
  conditions falls back to the unshared prefill. Eviction/recovery stay
  correct for free — a freed sharer only decrements refcounts, a page
  leaves the pool (and the index, via the ``free``→``expire_pages``
  seam) at its LAST release — ``tools/chaos.py serve_prefix_storm``
  certifies streams, single-prefill sharing, and refcount balance.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from perceiver_io_tpu.obs.trace import maybe_span
from perceiver_io_tpu.serving.frontend import FrontEndRecord, RequestFrontEnd, _Ticket
from perceiver_io_tpu.serving.pages import PageAllocator
from perceiver_io_tpu.serving.prefix import PrefixIndex


@dataclass
class EngineConfig:
    """Geometry/policy of the batched engine."""

    # decode slots (the max batch a step serves)
    slots: int = 4
    # tokens per KV page
    page_size: int = 8
    # per-slot token ceilings (prompt + decode budget); page-table width is
    # derived from these. Requests beyond them shed kv_pages_exhausted.
    max_ca_tokens: int = 64
    max_sa_tokens: int = 32
    # pool sizing in units of fully-loaded slots: 1.0 = exactly enough pages
    # for `slots` maxed-out requests (+ the scratch page). Below 1.0 the
    # allocator exerts real backpressure — the chaos scenarios run there.
    pool_headroom: float = 1.0
    # Specline speculative slot mode: spec_k > 0 drafts that many tokens per
    # engine step with a truncated-depth self-drafter (spec_depth latent SA
    # layers sharing the flagship's weights) and verifies them in ONE
    # batched flagship forward — a step emits m ∈ [1, spec_k+1] tokens per
    # slot. Requires max_ca_tokens <= model max_seq_len and max_sa_tokens
    # <= model max_latents (speculative decode never slides the window —
    # validated loudly at construction); per-slot pools grow by spec_k+1
    # slots of slack for the transient pre-rollback span.
    spec_k: int = 0
    spec_depth: int = 1
    # Shareline cross-request prefix sharing: joining prompts are matched
    # against the radix prefix index and prefill skips resident pages
    # (refcounted shared grants). Exactness-gated OFF automatically in
    # speculative slot mode and for int8 caches (see _share_supported);
    # this flag is the operator A/B seam — tools/loadgen.py's unshared
    # baseline leg runs the SAME workload with sharing disabled.
    prefix_sharing: bool = True
    # Evictline page-pressure preemption: when a queued request COULD fit
    # the pool but the free list is short, reclaim pages from the least-
    # progressed in-flight slot (parked resumable; resumed token-exactly by
    # prefill replay) instead of holding the queue. Requires the no-slide
    # window geometry (max_ca_tokens <= model max_seq_len, max_sa_tokens <=
    # model max_latents — validated loudly at construction): the replay
    # prefill reconstructs the victim's latent set as prompt-tail latents,
    # which a slid window cannot express.
    eviction: bool = False


class EngineFrontEnd(RequestFrontEnd):
    """The continuous-batching front end (see module docstring). Inherits
    the whole admission/books/drain surface of :class:`RequestFrontEnd`;
    only the SERVICE loop differs — batched join/step/retire instead of
    one-request-at-a-time ``_serve_next``.

    ``engine_config`` sizes the slot/page geometry. Everything else
    (events, registry, clock, injector, breaker, deadlines) follows the
    parent's contract, so the chaos machinery drives both unchanged.
    """

    def __init__(self, model, params, *, engine_config: Optional[EngineConfig] = None, **kw):
        super().__init__(model, params, **kw)
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.engine_config = ec = engine_config or EngineConfig()
        mcfg = model.config
        ps = ec.page_size
        self._spec = ec.spec_k > 0
        # verify spans transiently append spec_k+1 tokens before rollback;
        # per-slot page spans (and grants) carry that slack
        self._spec_slack = ec.spec_k + 1 if self._spec else 0
        if self._spec and (
            ec.max_ca_tokens > mcfg.max_seq_len or ec.max_sa_tokens > mcfg.max_latents
        ):
            raise ValueError(
                "speculative slot mode never slides the window: need "
                f"max_ca_tokens <= max_seq_len ({ec.max_ca_tokens} vs "
                f"{mcfg.max_seq_len}) and max_sa_tokens <= max_latents "
                f"({ec.max_sa_tokens} vs {mcfg.max_latents})"
            )
        if (ec.eviction or self.journal is not None) and (
            ec.max_ca_tokens > mcfg.max_seq_len or ec.max_sa_tokens > mcfg.max_latents
        ):
            # same no-slide contract as the speculative mode, for a
            # different reason: resume-by-prefill-replay rebuilds a parked
            # slot's latents as the last (num_latents + emitted) positions
            # of prompt + prefix — a window that slid mid-stream has
            # dropped latents the replay geometry cannot express. A journal
            # demands it too: its whole purpose is token-exact crash
            # recovery, which runs the same replay (:meth:`recover`)
            raise ValueError(
                "eviction and journal recovery resume by prefill replay and "
                "never slide the window: need max_ca_tokens <= max_seq_len "
                f"({ec.max_ca_tokens} vs {mcfg.max_seq_len}) and "
                f"max_sa_tokens <= max_latents ({ec.max_sa_tokens} vs "
                f"{mcfg.max_latents})"
            )
        self._ca_pages_per_slot = -(-(ec.max_ca_tokens + self._spec_slack) // ps)
        self._sa_pages_per_slot = -(-(ec.max_sa_tokens + self._spec_slack) // ps)
        ca_pool = 1 + max(2, int(round(ec.slots * self._ca_pages_per_slot * ec.pool_headroom)))
        sa_pool = 1 + max(2, int(round(ec.slots * self._sa_pages_per_slot * ec.pool_headroom)))
        self.ca_alloc = PageAllocator(ca_pool, ps)
        self.sa_alloc = PageAllocator(sa_pool, ps)
        # Shareline: the radix prefix index over CA pool pages (SA/latent
        # rows are never shareable — they pass through q_norm and the SA
        # stack, so they are request-specific by construction)
        self.prefix_index = PrefixIndex(ps)

        from perceiver_io_tpu.core.modules import CausalSequenceModel
        from perceiver_io_tpu.generation import (
            GenerationConfig,
            _maybe_quantize_weights,
            make_paged_step_fn,
        )
        from perceiver_io_tpu.obs.recompile import RecompileTracker

        self._gen_config = self.base_config or GenerationConfig()
        cache_dtype = self.cache_dtype if self.cache_dtype is not None else jnp.float32
        caches = CausalSequenceModel.init_paged_cache(
            mcfg, ec.slots, ps,
            ca_num_pages=ca_pool, ca_pages_per_slot=self._ca_pages_per_slot,
            sa_num_pages=sa_pool, sa_pages_per_slot=self._sa_pages_per_slot,
            dtype=cache_dtype,
        )
        self._decode_params, _ = _maybe_quantize_weights(model, params, self.weight_dtype)
        s = ec.slots
        self._state = {
            "cache": caches,
            "ca_start": jnp.zeros((s,), jnp.int32),
            "sa_start": jnp.zeros((s,), jnp.int32),
            "token": jnp.zeros((s,), jnp.int32),
            "rng": jnp.stack([jax.random.PRNGKey(0)] * s),
            "done": jnp.ones((s,), bool),
            "pad_slots": jnp.zeros((s, caches[0].capacity), bool),
            "pos_shift": jnp.zeros((s, 1), jnp.int32),
        }
        self._tracker = RecompileTracker(events=self._tracer)
        if self._spec:
            from perceiver_io_tpu.generation import (
                make_drafter,
                make_speculative_paged_step_fn,
            )

            # drafter pools mirror the flagship pools' geometry AND page
            # ids: a slot's grant indexes both pool families, so the page
            # allocator's books cover the drafter for free
            self._drafter = make_drafter(model, ec.spec_depth)
            self._state["draft_cache"] = CausalSequenceModel.init_paged_cache(
                self._drafter.config, s, ps,
                ca_num_pages=ca_pool, ca_pages_per_slot=self._ca_pages_per_slot,
                sa_num_pages=sa_pool, sa_pages_per_slot=self._sa_pages_per_slot,
                dtype=cache_dtype,
            )
            self._step_fn = self._tracker.wrap(
                make_speculative_paged_step_fn(
                    model, self._gen_config, k=ec.spec_k,
                    draft_depth=ec.spec_depth, weight_dtype=self.weight_dtype,
                ),
                "engine_decode_spec_step",
            )
        else:
            self._step_fn = self._tracker.wrap(
                make_paged_step_fn(model, self._gen_config, self.weight_dtype),
                "engine_decode_step",
            )
        self._prefill_fns: Dict[tuple, object] = {}
        self._shared_prefill_fns: Dict[tuple, object] = {}
        # sharing is exactness-gated: OFF for int8 caches (the scale-plane
        # gather is not implemented — make_shared_prefill_fn raises) and in
        # speculative slot mode (the drafter pool's shared pages would need
        # their own publish/commit discipline); both fall back to the
        # unshared prefill, so sharing is a no-op there, never a risk
        self._share_supported = (
            ec.prefix_sharing and not self._spec and not caches[0].quantized
        )
        self._join_fn = self._tracker.wrap(
            jax.jit(_join_state, donate_argnums=0), "engine_join"
        )
        self._retire_fn = self._tracker.wrap(
            jax.jit(_retire_state, donate_argnums=0), "engine_retire"
        )
        self._slots: List[Optional[_EngineSlot]] = [None] * s
        # Evictline: self._parked (inherited — books()/audit() close over
        # it) holds page-evicted slots parked resumable, FIFO: resume order
        # is admission order, the oldest preempted work re-enters first
        self._engine_steps = 0
        self._fill_sum = 0  # sum of active-slot counts over steps
        # request index -> decoded token ids (the streaming surface a real
        # consumer reads; the token-exactness tests compare these against
        # the sequential path)
        self.served_tokens: Dict[int, List[int]] = {}
        r = self.registry
        self._m_tokens = r.counter("generate_tokens_out_total")
        self._m_requests = r.counter("generate_requests_total")
        self._m_ttft = r.histogram("generate_ttft_s")
        self._m_tpot = r.histogram("generate_tpot_s")
        self._m_queue_wait = r.histogram("generate_queue_wait_s")
        self._m_fill = r.gauge("engine_batch_fill_frac")
        self._m_pages = r.gauge("engine_kv_pages_used")
        self._m_pages_frac = r.gauge("engine_kv_pages_frac")
        # the step and prefill odometers, at the boundaries of the
        # engine/step and engine/prefill spans: steps with tokens out give
        # the batch fill, prefill tokens what the joins cost
        self._m_steps = r.counter("engine_steps_total")
        self._m_prefills = r.counter("engine_prefills_total")
        self._m_prefill_tokens = r.counter("engine_prefill_tokens_total")
        # Evictline counters + the parked-depth gauge (its .peak high-water
        # mark feeds the LOAD artifact's parked_depth_peak)
        self._m_evictions = r.counter("serve_evictions_total")
        self._m_resumes = r.counter("serve_resumes_total")
        self._m_recovered = r.counter("serve_recovered_total")
        self._m_parked = r.gauge("serve_parked_depth")
        # Shareline counters (per-tenant labeled like the PR-16 set):
        # hits = joins whose prefill skipped at least one resident page,
        # pages_shared = pages those joins did NOT re-prefill
        self._m_prefix_hits = r.counter("serve_prefix_hits_total")
        self._m_prefix_pages = r.counter("serve_prefix_pages_shared")
        self._n_prefix_hits = 0
        self._n_prefix_pages_shared = 0
        if self._spec:
            # per-request drafter quality, recorded at retire: the A/B
            # inputs the graduation ledger and docs/performance.md cite
            self._m_accept = r.histogram("spec_acceptance_rate")
            self._m_tps = r.histogram("spec_tokens_per_step")
        # per-tenant pages held (feeds engine_kv_pages_used{tenant=...})
        self._tenant_pages: Dict[str, int] = {}
        self._admission_checks.append(self._page_fit_check)

    # -- the service clock (Simline's virtual-time seam) ---------------------

    def _now_s(self) -> float:
        """The clock service timing reads (ttft, step dt, service_s). The
        REAL engine times actual compute, so this is wall perf_counter even
        under an injected ManualClock (which does not advance during
        compiled steps); the discrete-event simulation overrides it to the
        injected virtual clock so sampled service times ARE the timeline."""
        return time.perf_counter()

    def _tenant_pages_delta(self, rec, n_pages: int) -> None:
        """Track pages held per tenant; mirrors every grant/free so the
        labeled ``engine_kv_pages_used{tenant=...}`` gauge (and its .peak)
        follows each tenant's live KV footprint."""
        if rec.tenant is None:
            return
        cur = self._tenant_pages.get(rec.tenant, 0) + n_pages
        self._tenant_pages[rec.tenant] = cur
        self._m_pages.labels(tenant=rec.tenant).set(cur)

    # -- admission -----------------------------------------------------------

    def _page_fit_check(self, spec, deadline_s):
        """Shed a request whose KV footprint can NEVER fit: prompt + budget
        over a per-slot ceiling (CA window OR SA latent stream — both
        UNCAPPED, exactly what :meth:`_try_join` will allocate: an SA
        stream beyond the slot's page span would clamp into its last page
        and overwrite live window slots) or over the whole pool. Transient
        shortage is backpressure (the request waits), never a shed."""
        ca_tokens = int(spec.prompt_len) + int(spec.max_new_tokens)
        sa_tokens = self.num_latents + int(spec.max_new_tokens)
        ec = self.engine_config
        fits = (
            ca_tokens <= ec.max_ca_tokens
            and sa_tokens <= ec.max_sa_tokens
            and self.ca_alloc.can_ever_fit(ca_tokens + self._spec_slack)
            and self.sa_alloc.can_ever_fit(sa_tokens + self._spec_slack)
        )
        if fits:
            return None
        return "kv_pages_exhausted", {
            "ca_tokens": ca_tokens,
            "max_ca_tokens": ec.max_ca_tokens,
            "sa_tokens": sa_tokens,
            "max_sa_tokens": ec.max_sa_tokens,
            "pool_pages": self.ca_alloc.num_allocatable,
        }

    # -- join ----------------------------------------------------------------

    # resume replay can hit a distinct (remaining, num_latents + n) point
    # per eviction progress mark — LRU-bound the program cache so a
    # long-lived engine under sustained pressure cannot grow it without
    # limit (an evicted entry re-compiles on next use; compile events
    # surface through the tracker either way)
    _PREFILL_CACHE_MAX = 64

    def _prefill_for(self, max_new: int, num_latents: Optional[int] = None):
        """The committed prefill program for one decode budget. ``num_latents``
        (default: the engine's) is the resume-replay seam: a parked request
        with ``n`` emitted tokens replays over ``prompt + prefix`` with
        ``num_latents + n`` latents — the SAME traced prefill, one latent
        per emitted token grown, so the replayed state IS the uninterrupted
        slot's (no new program family; recompiles surface as compile
        events through the tracker like any other geometry)."""
        num_latents = self.num_latents if num_latents is None else int(num_latents)
        key = (max_new, num_latents)
        if key not in self._prefill_fns:
            import dataclasses as _dc

            from perceiver_io_tpu.generation import make_decode_fns

            cfg = _dc.replace(self._gen_config, max_new_tokens=max_new)
            kwargs = {} if self.cache_dtype is None else {"cache_dtype": self.cache_dtype}
            prefill, _ = make_decode_fns(
                self.model, num_latents, cfg,
                weight_dtype=self.weight_dtype, **kwargs,
            )
            while len(self._prefill_fns) >= self._PREFILL_CACHE_MAX:
                self._prefill_fns.pop(next(iter(self._prefill_fns)))
            self._prefill_fns[key] = self._tracker.wrap(prefill, "engine_prefill")
        else:
            # LRU touch: re-insertion keeps hot geometries at the tail
            self._prefill_fns[key] = self._prefill_fns.pop(key)
        return self._prefill_fns[key]

    def _shared_prefill_for(self, skip_tokens: int, prompt_len: int, max_new: int):
        """The committed SHARED prefill program for one (skip, prompt,
        budget) geometry: gathers the matched run's CA rows from the pool
        and prefills the suffix alone (``generation.make_shared_prefill_fn``
        — page ids are traced, so one program serves every match of this
        geometry). LRU-bounded alongside :attr:`_prefill_fns` for the same
        reason: sustained mixed-geometry load must not grow it without
        limit."""
        key = (skip_tokens, prompt_len, max_new)
        if key not in self._shared_prefill_fns:
            import dataclasses as _dc

            from perceiver_io_tpu.generation import make_shared_prefill_fn

            cfg = _dc.replace(self._gen_config, max_new_tokens=max_new)
            kwargs = {} if self.cache_dtype is None else {"cache_dtype": self.cache_dtype}
            fn = make_shared_prefill_fn(
                self.model, self.num_latents, skip_tokens, prompt_len, cfg, **kwargs
            )
            while len(self._shared_prefill_fns) >= self._PREFILL_CACHE_MAX:
                self._shared_prefill_fns.pop(next(iter(self._shared_prefill_fns)))
            self._shared_prefill_fns[key] = self._tracker.wrap(
                fn, "engine_shared_prefill"
            )
        else:
            self._shared_prefill_fns[key] = self._shared_prefill_fns.pop(key)
        return self._shared_prefill_fns[key]

    def _match_prefix(self, ticket: _Ticket) -> tuple:
        """Longest shareable resident run for a joining prompt: the radix
        match, CAPPED to whole pages inside the request's context region
        (``skip <= prompt_len - num_latents``) — the suffix must carry ALL
        latents or the latent set (and the logits) would differ from the
        unshared prefill's. Empty tuple = join unshared."""
        if not self._share_supported:
            return ()
        rec = ticket.record
        max_pages = (rec.prompt_len - self.num_latents) // self.engine_config.page_size
        if max_pages < 1:
            return ()
        prompt = np.asarray(ticket.spec.input_ids).reshape(-1).tolist()
        return self.prefix_index.match(prompt)[:max_pages]

    def _publish_prefix(self, ticket: _Ticket, ca_grant) -> None:
        """Register a landed request's full context-region pages in the
        prefix index so later arrivals can share them. Runs AFTER the join
        committed the device rows (the pages hold real bytes the moment
        they become matchable). A shared join publishes too: its fresh
        suffix-context pages EXTEND the resident run; re-inserting the
        matched head is a no-op."""
        if not self._share_supported:
            return
        rec = ticket.record
        ps = self.engine_config.page_size
        n_ctx = (rec.prompt_len - self.num_latents) // ps
        if n_ctx < 1:
            return
        prompt = np.asarray(ticket.spec.input_ids).reshape(-1).tolist()
        self.prefix_index.insert(prompt[: n_ctx * ps], ca_grant.pages[:n_ctx])

    def _free_ca(self, grant) -> None:
        """Free a CA grant and EXPIRE the prefix-index entries of every page
        whose last reference this was — the one seam that keeps a recycled
        page from ever satisfying a future match. Every CA free in the
        engine funnels through here (retire, evict, failed joins/resumes)."""
        released = self.ca_alloc.free(grant)
        if released:
            self.prefix_index.expire_pages(released)

    def _fork_shared_append_page(self, ca_grant, append_pos: int):
        """Copy-on-write guard on the decode append path: if the CA page
        that token position ``append_pos`` writes into is SHARED (held by
        a prefix co-owner), fork it via ``PageAllocator.cow_fork`` and copy
        the page's device rows into the fresh page — the append then lands
        in bytes this grant exclusively owns, never in the co-owner's.

        Returns the (possibly forked) grant, or None when the pool has no
        fresh page to fork into (the caller sheds/backs off exactly like a
        failed allocation — the original grant is untouched). With the
        current whole-page sharing cap (``_match_prefix`` caps matches to
        whole pages strictly inside the context region) the append page is
        never shared and this is a no-op guard; a partially-filled shared
        tail page would hit the fork path.
        """
        ps = self.engine_config.page_size
        page_slot = append_pos // ps
        page = ca_grant.pages[page_slot]
        if page not in ca_grant.shared_pages:
            return ca_grant
        forked = self.ca_alloc.cow_fork(ca_grant, page)
        if forked is None:
            return None
        fresh = forked.pages[page_slot]
        # the device copy is the caller's job (pages.cow_fork contract):
        # duplicate the shared page's pool rows into the fresh page so the
        # co-owner's resident tokens survive this grant's appends
        caches = list(self._state["cache"])
        pool = caches[0]
        updates = dict(k=pool.k.at[fresh].set(pool.k[page]),
                       v=pool.v.at[fresh].set(pool.v[page]))
        if pool.k_scale is not None:
            updates["k_scale"] = pool.k_scale.at[fresh].set(pool.k_scale[page])
            updates["v_scale"] = pool.v_scale.at[fresh].set(pool.v_scale[page])
        caches[0] = pool.replace(**updates)
        self._state = dict(self._state, cache=tuple(caches))
        if "draft_cache" in self._state:
            # drafter CA pool mirrors the flagship's page ids — same copy
            dcaches = list(self._state["draft_cache"])
            dpool = dcaches[0]
            dupd = dict(k=dpool.k.at[fresh].set(dpool.k[page]),
                        v=dpool.v.at[fresh].set(dpool.v[page]))
            if dpool.k_scale is not None:
                dupd["k_scale"] = dpool.k_scale.at[fresh].set(dpool.k_scale[page])
                dupd["v_scale"] = dpool.v_scale.at[fresh].set(dpool.v_scale[page])
            dcaches[0] = dpool.replace(**dupd)
            self._state = dict(self._state, draft_cache=tuple(dcaches))
        return forked

    def _grant_pages(self, ca_tokens: int, sa_tokens: int, matched: tuple = (),
                     append_pos: Optional[int] = None):
        """Both page grants of one join or resume, or None when pages are
        short RIGHT NOW (nothing stays allocated): the CA grant (sharing the
        ``matched`` run), the SA grant, and the copy-on-write fork of a
        shared page the first decode append would write into."""
        with maybe_span(self._tracer, "engine/page_grant", pages=0, evicted=0) as sp:
            ca_grant = (
                self.ca_alloc.alloc_tokens_shared(ca_tokens, matched)
                if matched
                else self.ca_alloc.alloc_tokens(ca_tokens)
            )
            if ca_grant is None:
                return None
            sa_grant = self.sa_alloc.alloc_tokens(sa_tokens)
            if sa_grant is None:
                self._free_ca(ca_grant)
                return None
            if ca_grant.shared_pages:
                # COW guard: the first decode append (CA position prompt_len)
                # must never write into a page a prefix co-owner still reads
                forked = self._fork_shared_append_page(ca_grant, append_pos)
                if forked is None:
                    self._free_ca(ca_grant)
                    self.sa_alloc.free(sa_grant)
                    return None  # pool dry for the fork: wait like any alloc miss
                ca_grant = forked
            if sp is not None:
                sp.set("pages", ca_grant.n_pages + sa_grant.n_pages)
            return ca_grant, sa_grant

    def _open_request_span(self, slot: "_EngineSlot") -> None:
        """The slot's DETACHED span (no contextvar nesting): slot lifetimes
        overlap and close out of LIFO order, which the nested span stack
        cannot express — the span row is recorded at retire or eviction."""
        if self._tracer is None:
            return
        attrs = {"request_id": slot.request_id}
        if slot.ticket.record.tenant is not None:
            attrs["tenant"] = slot.ticket.record.tenant
        slot.span = self._tracer.detached("request", **attrs)

    def _try_join(self, ticket: _Ticket, slot_id: int) -> bool:
        """Prefill the ticket's request and land it in ``slot_id``. Returns
        False (ticket stays queued) when pages are short RIGHT NOW; raises
        nothing — a prefill failure books the request as a terminal error
        (pages freed), keeping the stream 1:1."""
        rec = ticket.record
        with maybe_span(self._tracer, "engine/join", request_index=rec.index,
                        prompt_len=rec.prompt_len) as sp:
            return self._join(ticket, slot_id, sp)

    def _join(self, ticket: _Ticket, slot_id: int, sp) -> bool:
        import jax

        jnp = self._jnp
        rec = ticket.record
        # spec slack rides the grant: the verify span transiently appends
        # spec_k+1 tokens past the request's budget before rollback
        ca_tokens = rec.prompt_len + rec.max_new_tokens + self._spec_slack
        sa_tokens = self.num_latents + rec.max_new_tokens + self._spec_slack
        matched = self._match_prefix(ticket)
        if sp is not None:
            sp.set("prefix_pages", len(matched))
        grants = self._grant_pages(ca_tokens, sa_tokens, matched, rec.prompt_len)
        if grants is None:
            return False
        ca_grant, sa_grant = grants
        self._queue.remove(ticket)
        self._set_queue_gauge()
        now = float(self._clock())
        rec.queue_wait_s = round(max(now - ticket.arrival_s, 0.0), 6)
        self._m_queue_wait.record(rec.queue_wait_s)
        slot = _EngineSlot(ticket=ticket, slot_id=slot_id,
                           ca_grant=ca_grant, sa_grant=sa_grant)
        slot.t_joined = self._now_s()
        self._tenant_pages_delta(rec, ca_grant.n_pages + sa_grant.n_pages)
        self._open_request_span(slot)
        if sp is not None:
            sp.set("request_id", slot.request_id)
        compiles0 = self._tracker.total_compiles
        t0 = self._now_s()
        try:
            with maybe_span(self._tracer, "engine/prefill", prompt_len=rec.prompt_len,
                            shared=bool(matched)) as psp:
                if self._injector is not None:
                    self._injector.before_attempt(rec.index)
                serve_params = (
                    self._injector.params_for(rec.index, self.params)
                    if self._injector is not None
                    else self.params
                )
                rng = jax.random.PRNGKey(int(ticket.spec.rng_seed))
                if matched:
                    # Shareline: the matched run's CA rows are already resident
                    # in pool pages — gather them and prefill the suffix alone.
                    # rng handling is IDENTICAL to the unshared prefill (one
                    # split for the first sample), so the stream is token-exact.
                    skip = len(matched) * self.engine_config.page_size
                    shared_prefill = self._shared_prefill_for(
                        skip, rec.prompt_len, rec.max_new_tokens
                    )
                    ca_pool = self._state["cache"][0]
                    token, pstate = shared_prefill(
                        serve_params,
                        jnp.asarray(ticket.spec.input_ids)[:, skip:],
                        ca_pool.k,
                        ca_pool.v,
                        jnp.asarray(matched, jnp.int32),
                        rng,
                    )
                else:
                    prefill = self._prefill_for(rec.max_new_tokens)
                    token, pstate = prefill(
                        serve_params, jnp.asarray(ticket.spec.input_ids), None, rng
                    )
                first = int(token[0])
                if psp is not None:
                    psp.set("compiled", self._tracker.total_compiles > compiles0)
        except Exception as e:  # noqa: BLE001 — books close, pages return
            self._free_ca(ca_grant)
            self.sa_alloc.free(sa_grant)
            self._tenant_pages_delta(rec, -(ca_grant.n_pages + sa_grant.n_pages))
            rec.error = repr(e)
            rec.attempts += 1
            self._retire_books(slot, "error", emit=True)
            return True  # the ticket reached a terminal outcome
        slot.ttft_s = self._now_s() - t0
        rec.attempts += 1
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(rec.prompt_len - len(matched) * self.engine_config.page_size)
        slot.compiled = self._tracker.total_compiles > compiles0
        slot.tokens_out = 1
        slot.first_token = first
        self.served_tokens[rec.index] = [first]
        if self.journal is not None:
            self.journal.append("progress", rec.index, tokens=[first])
        self._state = self._join_fn(
            self._state,
            jnp.int32(slot_id),
            jnp.asarray(ca_grant.pages, jnp.int32),
            jnp.asarray(sa_grant.pages, jnp.int32),
            pstate["cache"],
            (token[0].astype(jnp.int32), pstate["rng"],
             pstate["done"][0], pstate["pad_slots"][0], pstate["pos_shift"][0]),
        )
        self._slots[slot_id] = slot
        self._in_flight += 1
        # publish AFTER the join committed the device rows; a shared join
        # publishes its suffix-context pages, extending the resident run
        self._publish_prefix(ticket, ca_grant)
        if matched:
            ps = self.engine_config.page_size
            self._n_prefix_hits += 1
            self._n_prefix_pages_shared += len(matched)
            self._m_prefix_hits.inc()
            self._m_prefix_pages.inc(len(matched))
            if rec.tenant is not None:
                self._m_prefix_hits.labels(tenant=rec.tenant).inc()
                self._m_prefix_pages.labels(tenant=rec.tenant).inc(len(matched))
            if self.events is not None:
                row = dict(
                    request_index=rec.index,
                    pages_matched=len(matched),
                    pages_total=-(-rec.prompt_len // ps),
                    tokens_skipped=len(matched) * ps,
                )
                if rec.tenant is not None:
                    row["tenant"] = rec.tenant
                if slot.span is not None:
                    row["span_id"] = slot.span.span_id
                self._emit("serve.prefix_hit", **row)
        if not slot.compiled:
            self._m_ttft.record(slot.ttft_s)
        # the per-token seam fires for token 0 exactly like the sequential
        # path (injector stalls/kills, cancellation, deadline)
        self._token_seam(slot, 0)
        return True

    # -- the per-token seam (injector / cancel / deadline) -------------------

    def _token_seam(self, slot: "_EngineSlot", i: int) -> None:
        rec = slot.ticket.record
        rec.tokens_out = slot.tokens_out
        try:
            if self._injector is not None:
                self._injector.on_token(rec.index, i)
            if slot.ticket.cancelled:
                slot.outcome = "cancelled"
                return
            if (slot.ticket.deadline_at is not None
                    and self._clock() > slot.ticket.deadline_at):
                slot.outcome = "timeout"
        except Exception as e:  # noqa: BLE001 — injected kill
            slot.outcome = "error"
            rec.error = repr(e)

    # -- retire --------------------------------------------------------------

    def _retire_books(self, slot: "_EngineSlot", outcome: str, emit: bool) -> None:
        """Terminal accounting for one slot: books, pages, span, event."""
        rec = slot.ticket.record
        rec.ttft_s = None if slot.ttft_s is None else round(slot.ttft_s, 6)
        rec.tokens_out = slot.tokens_out
        rec.compiled = slot.compiled
        rec.decode_s = round(sum(slot.step_times), 6)
        rec.service_s = round(self._now_s() - slot.t_joined, 6)
        self._finish(slot.ticket, outcome)
        # speculative quality accounting (the measurement half of the
        # graduation story): raw drafter acceptance over the slot's verify
        # spans, and decode tokens emitted per batched step
        accept_rate = tokens_per_step = None
        if slot.spec_spans:
            accept_rate = slot.spec_accepted / (
                slot.spec_spans * max(self.engine_config.spec_k, 1)
            )
            tokens_per_step = max(slot.tokens_out - 1, 0) / slot.spec_spans
            self._m_accept.record(accept_rate)
            self._m_tps.record(tokens_per_step)
        if slot.span is not None:
            slot.span.set("outcome", outcome)
            slot.span.set("tokens_out", slot.tokens_out)
            self._tracer.record(slot.span)  # queued BEFORE the request row
        if emit and self.events is not None:
            row = dict(
                request_id=slot.request_id,
                batch=1,
                prompt_len=rec.prompt_len,
                new_tokens=rec.max_new_tokens,
                ttft_s=0.0 if slot.ttft_s is None else round(slot.ttft_s, 6),
                tokens_out=slot.tokens_out,
                outcome=outcome,
                compiled=slot.compiled,
                queue_wait_s=rec.queue_wait_s,
                decode_s=round(sum(slot.step_times), 6),
                tpot_hist=dict(sorted((str(k), v) for k, v in slot.hist.counts.items())),
            )
            if rec.tenant is not None:
                row["tenant"] = rec.tenant
            if slot.batch_sizes:
                row["batch_size_at_decode"] = round(
                    sum(slot.batch_sizes) / len(slot.batch_sizes), 3
                )
            if accept_rate is not None:
                row["acceptance_rate"] = round(accept_rate, 6)
                row["tokens_per_step"] = round(tokens_per_step, 6)
            if slot.span is not None:
                row["span_id"] = slot.span.span_id
            for p in (50, 90, 99):
                row[f"tpot_p{p}_s"] = slot.hist.percentile(p)
            if rec.error is not None:
                row["error"] = rec.error
            self._emit("request", **row)
        self._m_requests.inc()
        self._m_tokens.inc(slot.tokens_out)
        if self.events is not None:
            # snapshot cadence matches the instrumented wrapper: the engine
            # gauges (batch fill, page use) land in `metrics` rows while the
            # batch is still live, not only after the drain zeroes them
            self.registry.maybe_emit(
                self._tracer, min_interval_s=self.config.snapshot_interval_s
            )

    def _retire_slot(self, slot_id: int, outcome: str) -> None:
        with maybe_span(self._tracer, "engine/retire", outcome=outcome):
            slot = self._slots[slot_id]
            self._slots[slot_id] = None
            self._in_flight -= 1
            self._free_ca(slot.ca_grant)
            self.sa_alloc.free(slot.sa_grant)
            self._tenant_pages_delta(slot.ticket.record,
                                     -(slot.ca_grant.n_pages + slot.sa_grant.n_pages))
            self._state = self._retire_fn(self._state, self._jnp.int32(slot_id))
            self._retire_books(slot, outcome, emit=True)
            self._busy_until = float(self._clock())

    # -- eviction / park / resume (Evictline) --------------------------------

    def _select_victim(self) -> Optional[int]:
        """The least-progress/lowest-priority victim: fewest tokens emitted,
        ties broken toward the latest-admitted request (highest index) — the
        request that loses the least replay work and jumped the line last.
        Slots already terminal (outcome set) or budget-complete are never
        victims: their pages come back at the next sweep for free."""
        cands = [
            (s.tokens_out, -s.ticket.record.index, slot_id)
            for slot_id, s in enumerate(self._slots)
            if s is not None and s.outcome is None
            and s.tokens_out < s.ticket.record.max_new_tokens
        ]
        return min(cands)[2] if cands else None

    def _evict_slot(self, slot_id: int) -> None:
        """Preempt one in-flight slot: pages reclaimed, device slot released,
        the request PARKED resumable (prompt + served prefix + rng position
        — all it needs is already in ``served_tokens`` and its spec). NOT a
        terminal transition: the books identity moves it from in_flight to
        parked and :meth:`_try_resume` finishes the job later."""
        slot = self._slots[slot_id]
        self._slots[slot_id] = None
        self._in_flight -= 1
        pages_freed = slot.ca_grant.n_pages + slot.sa_grant.n_pages
        # refcount-aware: a freed sharer only DROPS references — a page
        # still held by sibling grants stays resident (and indexed), so
        # evicting one sharer never invalidates the others' page tables
        self._free_ca(slot.ca_grant)
        self.sa_alloc.free(slot.sa_grant)
        self._tenant_pages_delta(slot.ticket.record, -pages_freed)
        slot.ca_grant = slot.sa_grant = None
        self._state = self._retire_fn(self._state, self._jnp.int32(slot_id))
        slot.slot_id = -1
        slot.evictions += 1
        self._n_evictions += 1
        self._m_evictions.inc()
        rec = slot.ticket.record
        span_id = None
        if slot.span is not None:
            # the preempted SEGMENT's span closes here (slot lifetimes
            # overlap and a parked request may outlive many segments);
            # resume opens a fresh span under the same request_id
            slot.span.set("outcome", "evicted")
            slot.span.set("tokens_out", slot.tokens_out)
            span_id = slot.span.span_id
            self._tracer.record(slot.span)
        slot.span = None
        self._parked.append(slot)
        self._m_parked.set(len(self._parked))
        if self.journal is not None:
            self.journal.append("evict", rec.index, tokens_out=slot.tokens_out)
        if self.events is not None:
            row = dict(request_index=rec.index, tokens_out=slot.tokens_out,
                       pages_freed=pages_freed)
            if rec.tenant is not None:
                row["tenant"] = rec.tenant
            if span_id is not None:
                row["span_id"] = span_id
            self._emit("serve.evict", **row)

    def _evict_for(self, ticket: _Ticket) -> bool:
        """Reclaim pages for a queued request that fits the pool but not the
        free list: evict least-progress victims until it fits (True) or no
        victim remains (False — pure backpressure, exactly the pre-Evictline
        behavior). Admission already shed can-never-fit requests, so when
        every slot is evictable this always terminates in a fit."""
        if not self.engine_config.eviction:
            return False
        rec = ticket.record
        ca_tokens = rec.prompt_len + rec.max_new_tokens + self._spec_slack
        sa_tokens = self.num_latents + rec.max_new_tokens + self._spec_slack
        with maybe_span(self._tracer, "engine/page_grant", pages=0, evicted=0) as sp:
            evicted = 0
            while not (
                self.ca_alloc.can_fit_now(ca_tokens)
                and self.sa_alloc.can_fit_now(sa_tokens)
            ):
                victim = self._select_victim()
                if victim is None:
                    return False
                self._evict_slot(victim)
                evicted += 1
                if sp is not None:
                    sp.set("evicted", evicted)
            return True

    def _park_terminal(self, slot: "_EngineSlot", outcome: str) -> None:
        """A parked request reaching a terminal outcome WITHOUT re-entering a
        slot (cancelled while parked, deadline expired while parked): books
        close through the same retire path, no pages involved."""
        rec = slot.ticket.record
        rec.tokens_out = slot.tokens_out
        self._retire_books(slot, outcome, emit=True)

    def _try_resume(self, slot: "_EngineSlot", slot_id: int) -> bool:
        """Resume one parked request into ``slot_id`` by prefill replay:
        prefill over ``prompt + the n served tokens`` with ``num_latents +
        n`` latents (one latent per emitted token — the uninterrupted
        slot's exact latent set) and the rng chain advanced n splits
        (``generation.advance_rng_chain``), so the replayed prefill's own
        sample IS token n of the uninterrupted stream and every subsequent
        batched step matches token-exactly. Returns False only when pages
        are short RIGHT NOW (the request stays parked); a replay failure
        books a terminal ``error`` exactly like a join failure."""
        with maybe_span(self._tracer, "engine/resume", request_id=slot.request_id,
                        request_index=slot.ticket.record.index):
            return self._resume(slot, slot_id)

    def _resume(self, slot: "_EngineSlot", slot_id: int) -> bool:
        import jax

        jnp = self._jnp
        rec = slot.ticket.record
        idx = rec.index
        n = slot.tokens_out
        remaining = rec.max_new_tokens - n
        # page demand is the ORIGINAL join's: the replay's CA stream is
        # prompt + n + remaining = prompt + budget, and its SA stream is
        # (num_latents + n) + remaining = num_latents + budget
        ca_tokens = rec.prompt_len + rec.max_new_tokens + self._spec_slack
        sa_tokens = self.num_latents + rec.max_new_tokens + self._spec_slack
        grants = self._grant_pages(ca_tokens, sa_tokens)
        if grants is None:
            return False
        slot.ca_grant, slot.sa_grant = ca_grant, sa_grant = grants
        self._tenant_pages_delta(rec, ca_grant.n_pages + sa_grant.n_pages)
        emitted = self.served_tokens[idx]
        replay_ids = np.concatenate(
            [np.asarray(slot.ticket.spec.input_ids, np.int32),
             np.asarray([emitted], np.int32)],
            axis=1,
        )
        self._open_request_span(slot)
        compiles0 = self._tracker.total_compiles
        try:
            with maybe_span(self._tracer, "engine/prefill", prompt_len=replay_ids.shape[1],
                            shared=False) as psp:
                if self._injector is not None:
                    self._injector.before_attempt(idx)
                from perceiver_io_tpu.generation import advance_rng_chain

                prefill = self._prefill_for(remaining, num_latents=self.num_latents + n)
                serve_params = (
                    self._injector.params_for(idx, self.params)
                    if self._injector is not None
                    else self.params
                )
                rng = advance_rng_chain(jax.random.PRNGKey(int(slot.ticket.spec.rng_seed)), n)
                token, pstate = prefill(serve_params, jnp.asarray(replay_ids), None, rng)
                first = int(token[0])
                if psp is not None:
                    psp.set("compiled", self._tracker.total_compiles > compiles0)
        except Exception as e:  # noqa: BLE001 — books close, pages return
            self._free_ca(ca_grant)
            self.sa_alloc.free(sa_grant)
            self._tenant_pages_delta(rec, -(ca_grant.n_pages + sa_grant.n_pages))
            slot.ca_grant = slot.sa_grant = None
            rec.error = repr(e)
            rec.attempts += 1
            self._park_terminal(slot, "error")
            return True  # reached a terminal outcome
        rec.attempts += 1
        self._m_prefills.inc()
        self._m_prefill_tokens.inc(replay_ids.shape[1])
        slot.compiled = slot.compiled or self._tracker.total_compiles > compiles0
        slot.tokens_out = n + 1
        slot.slot_id = slot_id
        emitted.append(first)
        self._state = self._join_fn(
            self._state,
            jnp.int32(slot_id),
            jnp.asarray(ca_grant.pages, jnp.int32),
            jnp.asarray(sa_grant.pages, jnp.int32),
            pstate["cache"],
            (token[0].astype(jnp.int32), pstate["rng"],
             pstate["done"][0], pstate["pad_slots"][0], pstate["pos_shift"][0]),
        )
        self._slots[slot_id] = slot
        self._in_flight += 1
        # the replay's first (prompt_len - num_latents) rows ARE the fresh
        # join's context rows (same tokens, same absolute positions), so a
        # resumed request republishes its prefix run — this is also how
        # crash RECOVERY rebuilds the index: recovered requests re-enter
        # through this seam (or a plain join) and repopulate it
        self._publish_prefix(slot.ticket, ca_grant)
        self._n_resumes += 1
        self._m_resumes.inc()
        if self.journal is not None:
            self.journal.append("resume", idx, tokens_out=n)
            self.journal.append("progress", idx, tokens=[first])
        if self.events is not None:
            row = dict(request_index=idx, tokens_out=n)
            if rec.tenant is not None:
                row["tenant"] = rec.tenant
            if slot.span is not None:
                row["span_id"] = slot.span.span_id
            self._emit("serve.resume", **row)
        # the per-token seam fires for the replayed prefill's sample exactly
        # like a join's token 0 (injector / cancel / deadline)
        self._token_seam(slot, slot.tokens_out - 1)
        return True

    def _resume_parked(self) -> None:
        """Fill free slots from the parked queue FIRST (admission order —
        preempted work re-enters ahead of new joins), on NATURAL page
        availability only: a resume never evicts, which is what bounds the
        evict/resume interplay (every segment between preemptions emits at
        least one token, so total remaining work strictly shrinks)."""
        if not self._parked:
            return
        for slot_id, occupant in enumerate(self._slots):
            if occupant is not None:
                continue
            while self._parked:
                slot = self._parked[0]
                now = float(self._clock())
                if slot.ticket.cancelled:
                    self._parked.pop(0)
                    self._m_parked.set(len(self._parked))
                    self._park_terminal(slot, "cancelled")
                    continue
                if (slot.ticket.deadline_at is not None
                        and now > slot.ticket.deadline_at):
                    self._parked.pop(0)
                    self._m_parked.set(len(self._parked))
                    self._park_terminal(slot, "timeout")
                    continue
                if not self._try_resume(slot, slot_id):
                    return  # pages short: the parked head waits (FIFO)
                self._parked.pop(0)
                self._m_parked.set(len(self._parked))
                break  # slot filled (or the head reached terminal) — next slot
            if not self._parked:
                return

    # -- crash recovery (Evictline) ------------------------------------------

    def recover(self, journal, handoff_id: Optional[str] = None) -> dict:
        """Re-admit a dead engine's non-terminal requests from its
        write-ahead journal (``serving.journal.RequestJournal`` or a path)
        into THIS fresh engine, and adopt the journal so both incarnations'
        records share one file — the cross-restart books close over it.

        Replay is IDEMPOTENT on request index: an index this engine already
        carries (queued, in a slot, parked, or terminal) is skipped — so
        applying the same journal twice, or replaying a journal onto a
        survivor that already adopted some of its requests, is a no-op on
        the second pass (the ``skipped`` count in the summary says how many
        were deduped).

        Two recovery shapes share this seam. A fresh engine WITHOUT its own
        journal (the restart case) ADOPTS the journal — both incarnations
        append to one file. A survivor WITH its own journal (fleet
        failover, serving/router.py) KEEPS it: each adopted request is
        re-journaled (submitted/admitted/progress) into the survivor's own
        file where its terminal record will land, and the dead journal gets
        a ``recovered`` record carrying ``handoff=<handoff_id>`` (default:
        this engine's journal path) so its books close and a third replay
        cannot double-adopt (``RequestJournal.pending`` excludes handed-off
        entries).

        Every journaled ``submitted`` without a ``terminal`` comes back:
        requests with journaled progress are PARKED (prompt + progress
        tokens + implied rng position — exactly an evicted slot's state,
        so the standard :meth:`_try_resume` prefill replay finishes them
        token-exactly); progress-less ones re-enter the queue and join
        normally. Load-dependent admission checks (queue depth, deadline
        projection, breaker) don't re-run — the dead engine already
        admitted these — but the PAGE-FIT check does: a request THIS
        engine's pool/window can never fit (the geometry shrank across the
        restart) is booked ``shed kv_pages_exhausted`` instead of
        busy-spinning the drive loops forever. Deadlines RESTART from
        recovery time (the journal records the relative budget; the wall
        time lost to the crash is the operator's fault, not the
        request's). A journaled stream already at budget (or ending in
        eos) crashed in the emit-to-retire window: it is booked terminal
        ``ok`` here, nothing left to decode. Emits one span-attributed
        ``serve.recover`` event per request; returns a summary dict."""
        from perceiver_io_tpu.serving.journal import RequestJournal

        ec = self.engine_config
        # the sim-scale engine has no model (service times stand in for the
        # compiled programs) — and no window to slide, so no geometry check
        mcfg = getattr(self.model, "config", None)
        if mcfg is not None and (
            ec.max_ca_tokens > mcfg.max_seq_len or ec.max_sa_tokens > mcfg.max_latents
        ):
            # the construction-time no-slide check only fires when a journal
            # (or eviction) was configured — recover() can adopt a journal
            # onto any engine, so the replay's geometry contract re-checks
            raise ValueError(
                "journal recovery resumes by prefill replay and never "
                "slides the window: need max_ca_tokens <= max_seq_len "
                f"({ec.max_ca_tokens} vs {mcfg.max_seq_len}) and "
                f"max_sa_tokens <= max_latents ({ec.max_sa_tokens} vs "
                f"{mcfg.max_latents})"
            )
        if not isinstance(journal, RequestJournal):
            journal = RequestJournal(journal)
        handoff_mode = self.journal is not None and self.journal is not journal
        if handoff_mode:
            own = self.journal  # the survivor keeps its own ledger
            if handoff_id is None:
                handoff_id = own.path
        else:
            self.journal = journal
            own = journal
        now = float(self._clock())
        eos = self._gen_config.eos_token_id
        n = done_already = shed = skipped = 0
        known = {r.index for r in self.records}
        for entry in journal.pending():
            if entry.index in known:
                # idempotence: this engine already carries the index
                # (double-replay, or a failover racing an earlier adoption)
                skipped += 1
                continue
            spec = entry.spec()
            if handoff_mode:
                # re-journal the adopted request into the survivor's own
                # ledger (terminal will land there), then close it in the
                # dead one — every index terminal-exactly-once FLEET-wide
                jfields = dict(
                    prompt_len=int(entry.prompt_len),
                    max_new_tokens=int(entry.max_new_tokens),
                    input_ids=list(entry.input_ids),
                    rng_seed=int(entry.rng_seed),
                    deadline_s=(None if entry.deadline_s is None
                                else float(entry.deadline_s)),
                )
                if entry.tenant is not None:
                    jfields["tenant"] = entry.tenant
                own.append("submitted", entry.index, **jfields)
            rec = FrontEndRecord(
                index=entry.index,
                prompt_len=int(entry.prompt_len),
                max_new_tokens=int(entry.max_new_tokens),
                batch=1,
                tenant=entry.tenant,
            )
            rec.queue_wait_s = 0.0
            self.records.append(rec)
            with self._books_lock:
                self._n["submitted"] += 1
            self._m_submitted.inc()
            if rec.tenant is not None:
                self._m_submitted.labels(tenant=rec.tenant).inc()
            verdict = self._page_fit_check(spec, None)
            if verdict is not None:
                # the dead engine admitted this, but THIS engine's geometry
                # cannot ever fit it (the pool/window shrank across the
                # restart): booking it shed closes its books — re-queueing
                # it would busy-spin the drive loops forever on a request
                # no allocation can satisfy
                reason, detail = verdict
                rec.outcome, rec.shed_reason = "shed", reason
                with self._books_lock:
                    self._n["shed"] += 1
                self._m_shed.inc()
                if rec.tenant is not None:
                    self._m_shed.labels(tenant=rec.tenant).inc()
                own.append("terminal", entry.index, outcome="shed",
                           shed_reason=reason)
                if handoff_mode:
                    # close the dead ledger too: the shed verdict lives in
                    # the survivor's journal, the handoff marker here
                    journal.append("recovered", entry.index,
                                   tokens_resumed=0, handoff=str(handoff_id))
                self._emit_frontend_request(rec, shed_reason=reason,
                                            queue_depth=len(self._queue),
                                            **detail)
                shed += 1
                continue
            with self._books_lock:
                self._n["admitted"] += 1
            self._m_admitted.inc()
            if rec.tenant is not None:
                self._m_admitted.labels(tenant=rec.tenant).inc()
            ticket = _Ticket(
                spec=spec, record=rec, arrival_s=now,
                deadline_at=(
                    None if entry.deadline_s is None
                    else now + float(entry.deadline_s)
                ),
            )
            tokens = [int(t) for t in entry.tokens]
            slot = None
            if tokens:
                slot = _EngineSlot(ticket=ticket, slot_id=-1,
                                   ca_grant=None, sa_grant=None)
                slot.t_joined = self._now_s()
                slot.tokens_out = len(tokens)
                self.served_tokens[entry.index] = tokens
            self._n_recovered += 1
            self._m_recovered.inc()
            if handoff_mode:
                own.append("admitted", entry.index)
                if tokens:
                    # the adopted progress, re-journaled: a later crash of
                    # the SURVIVOR replays prompt + these + its own tokens
                    own.append("progress", entry.index, tokens=tokens)
                journal.append("recovered", entry.index,
                               tokens_resumed=len(tokens),
                               handoff=str(handoff_id))
            else:
                journal.append("recovered", entry.index,
                               tokens_resumed=len(tokens))
            if self.events is not None:
                row = dict(request_index=entry.index, tokens_resumed=len(tokens))
                if entry.tenant is not None:
                    row["tenant"] = entry.tenant
                if self._tracer is not None:
                    # the recover span carries the SAME request_id the
                    # request's later resume span / terminal row will (the
                    # parked slot mints it); a progress-less re-queue has
                    # no slot yet, so its span keys on request_index alone
                    # — the durable cross-restart identity either way
                    rid = (slot.request_id if slot is not None
                           else self._trace_mod.new_span_id())
                    with self._tracer.span(
                        "request", request_id=rid, request_index=entry.index
                    ) as sp:
                        sp.set("outcome", "recovered")
                        sp.set("tokens_resumed", len(tokens))
                    row["span_id"] = sp.span_id  # its row is queued BEFORE the recover row
                self._emit("serve.recover", **row)
            if slot is not None:
                if len(tokens) >= rec.max_new_tokens or (
                    eos is not None and tokens[-1] == eos
                ):
                    # crashed between the last emit and its retire: the
                    # stream is complete — close the books, skip the replay
                    self._park_terminal(slot, "ok")
                    done_already += 1
                else:
                    self._parked.append(slot)
            else:
                self._queue.append(ticket)
                self._set_queue_gauge()
            n += 1
        self._m_parked.set(len(self._parked))
        return {
            "recovered": n,
            "parked": len(self._parked),
            "queued": len(self._queue),
            "already_complete": done_already,
            "shed": shed,
            "skipped": skipped,
        }

    # -- the engine loop -----------------------------------------------------

    def _active_ids(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _fill_slots(self) -> None:
        """Batched prefill admission: resume parked requests first (natural
        page availability), then join queued requests into every free slot.
        Page backpressure stops the fill — with ``eviction`` enabled a
        blocked queue head may first reclaim pages from the least-progressed
        slot (:meth:`_evict_for`); it never sheds."""
        with maybe_span(self._tracer, "engine/fill", queue_depth=len(self._queue),
                        parked=len(self._parked), joins=0) as sp:
            joins = self._fill()
            if sp is not None:
                sp.set("joins", joins)

    def _fill(self) -> int:
        """The fill itself; returns how many queued tickets it joined (a
        join that ended in a terminal error counts: its prefill ran)."""
        joins = 0
        self._resume_parked()
        for slot_id, occupant in enumerate(self._slots):
            if occupant is not None:
                continue
            while self._queue:
                ticket = self._queue[0]
                now = float(self._clock())
                if ticket.cancelled:
                    self._queue.popleft()
                    self._set_queue_gauge()
                    ticket.record.queue_wait_s = round(max(now - ticket.arrival_s, 0.0), 6)
                    self._finish(ticket, "cancelled")
                    self._emit_frontend_request(ticket.record,
                                                queue_wait_s=ticket.record.queue_wait_s)
                    continue
                if ticket.deadline_at is not None and now > ticket.deadline_at:
                    self._m_queue_expired.inc()
                    self._queue.popleft()
                    self._set_queue_gauge()
                    ticket.record.queue_wait_s = round(max(now - ticket.arrival_s, 0.0), 6)
                    self._finish(ticket, "timeout")
                    self._emit_frontend_request(ticket.record,
                                                queue_wait_s=ticket.record.queue_wait_s,
                                                queue_expired=True)
                    continue
                if not self._try_join(ticket, slot_id):
                    # pages short RIGHT NOW: page-pressure eviction (when
                    # enabled) reclaims from the least-progressed slot so
                    # the queue head proceeds; otherwise backpressure
                    if not self._evict_for(ticket) or not self._try_join(ticket, slot_id):
                        return joins  # keep the queue; pages will come back
                joins += 1
                break  # joined (or terminally booked) — next slot
        self._update_gauges()
        return joins

    def sharing_audit(self) -> List[str]:
        """Cross-layer sharing invariants (empty = clean): both allocators'
        page books — refcount balance included — the prefix index's own
        structure, and the seam between them: every page the index names
        must be LIVE in the CA allocator (``free``'s released list drives
        :meth:`PrefixIndex.expire_pages`, so an indexed page with refcount
        0 is a leak of exactly that seam). ``serve_prefix_storm`` asserts
        this both mid-storm and at drain."""
        problems = (
            self.ca_alloc.audit() + self.sa_alloc.audit() + self.prefix_index.audit()
        )
        for page in self.prefix_index.pages():
            if self.ca_alloc.refcount(page) < 1:
                problems.append(
                    f"prefix index names page {page} with refcount 0 "
                    "(expire-on-release seam leaked)"
                )
        return problems

    def _update_gauges(self) -> None:
        active = len(self._active_ids())
        self._m_fill.set(active / max(self.engine_config.slots, 1))
        stats = self.ca_alloc.stats()
        self._m_pages.set(stats.pages_used + self.sa_alloc.stats().pages_used)
        self._m_pages_frac.set(stats.used_frac)
        self._m_parked.set(len(self._parked))

    def _sweep_terminal(self) -> None:
        """Retire slots whose outcome is ALREADY terminal (a kill at token
        0 in the join seam, a cancel/deadline landing between steps) before
        the next batched step decodes — and books — an extra token for a
        dead request; the sequential path retires at exactly the same
        boundary. A slot whose budget the PREFILL token already filled
        (max_new_tokens == 1) retires ``ok`` here for the same reason: it
        must not ride a batched step that can emit nothing — in spec mode
        that phantom span would record tokens_per_step == 0 and unemitted
        'accepted' drafts into the acceptance telemetry."""
        for slot_id, slot in enumerate(self._slots):
            if slot is None:
                continue
            if slot.outcome is not None:
                self._retire_slot(slot_id, slot.outcome)
            elif slot.tokens_out >= slot.ticket.record.max_new_tokens:
                self._retire_slot(slot_id, "ok")

    def _engine_step(self) -> int:
        """One batched decode step + per-slot accounting/retires; returns
        the tokens emitted. In the
        speculative slot mode a step emits ``m ∈ [1, spec_k+1]`` tokens per
        slot — EVERY emitted token streams through the same per-token seam
        (injector / cancel / deadline), so mid-SPAN cancellation retires the
        slot at the same token boundary the sequential path would; the
        span's remaining tokens are dropped, never served."""
        self._sweep_terminal()
        active = self._active_ids()
        if not active:
            return 0
        tr = self._tracer
        compiles0 = self._tracker.total_compiles
        t0 = self._now_s()
        with maybe_span(tr, "engine/decode_dispatch"):
            if self._spec:
                self._state, tokens, m = self._step_fn(self._decode_params, self._state)
            else:
                self._state, tokens = self._step_fn(self._decode_params, self._state)
        with maybe_span(tr, "engine/token_fetch"):
            if self._spec:
                tokens, m = np.asarray(tokens), np.asarray(m)
            else:
                tokens = np.asarray(tokens)[:, None]  # ONE host fetch either way
                m = np.ones(len(self._slots), np.int64)
        dt = self._now_s() - t0
        self._engine_steps += 1
        self._m_steps.inc()
        self._fill_sum += len(active)
        cold_step = self._tracker.total_compiles > compiles0
        with maybe_span(tr, "engine/account", tokens=0) as sp:
            n_tokens = self._account(active, tokens, m, dt, cold_step)
            if sp is not None:
                sp.set("tokens", n_tokens)
        self._update_gauges()
        return n_tokens

    def _account(self, active: List[int], tokens, m, dt: float, cold_step: bool) -> int:
        """The per-slot half of a step: every emitted token through the
        seam, the histograms, one journal append per slot, and the retires.
        Returns the tokens emitted."""
        batch_size = len(active)
        eos = self._gen_config.eos_token_id
        n_tokens = 0
        for slot_id in active:
            slot = self._slots[slot_id]
            rec = slot.ticket.record
            span = int(m[slot_id])
            # a span may overshoot the request's remaining budget — clip;
            # acceptance counters record the RAW span (drafter quality)
            n_emit = min(span, rec.max_new_tokens - slot.tokens_out)
            if self._spec:
                slot.spec_spans += 1
                slot.spec_accepted += span - 1
            per_tok = dt / max(n_emit, 1)
            finished = False
            emitted_now: List[int] = []
            for j in range(n_emit):
                tok = int(tokens[slot_id, j])
                slot.tokens_out += 1
                self.served_tokens[rec.index].append(tok)
                emitted_now.append(tok)
                slot.hist.record(per_tok)
                slot.step_times.append(per_tok)
                slot.batch_sizes.append(batch_size)
                if cold_step:
                    slot.compiled = True
                else:
                    self._m_tpot.record(per_tok)
                self._token_seam(slot, slot.tokens_out - 1)
                if slot.outcome is not None:  # killed / cancelled / deadline
                    break
                if eos is not None and tok == eos:
                    finished = True
                    break
            n_tokens += len(emitted_now)
            if self.journal is not None and emitted_now:
                # one progress record per slot per step (not per token):
                # delivery stays at-least-once — tokens emitted after the
                # last append a crash tore off are re-derived token-exactly
                # by the recovery replay (serving.journal module docstring)
                self.journal.append("progress", rec.index, tokens=emitted_now)
            if slot.tokens_out >= rec.max_new_tokens:
                finished = True
            if slot.outcome is not None:
                self._retire_slot(slot_id, slot.outcome)
            elif finished:
                self._retire_slot(slot_id, "ok")
        return n_tokens

    def cancel(self, request_index: int) -> bool:
        """Cancel a queued request, one live in a decode SLOT — the slot
        retires ``cancelled`` at its next token boundary (the same
        between-tokens seam the sequential path uses) — or a PARKED
        (page-evicted / journal-recovered) request, which books terminal
        ``cancelled`` when the resume loop next reaches it instead of
        burning a replay for a caller who hung up."""
        for slot in self._slots:
            if slot is not None and slot.ticket.record.index == request_index:
                slot.ticket.cancelled = True
                return True
        for slot in self._parked:
            if (slot.ticket.record.index == request_index
                    and not slot.ticket.cancelled):
                slot.ticket.cancelled = True
                return True
        return super().cancel(request_index)

    @property
    def mean_batch_fill(self) -> float:
        """Mean active-slot fraction over every decode step — the engine's
        occupancy figure of merit (1.0 = every step fully batched)."""
        denom = self._engine_steps * max(self.engine_config.slots, 1)
        return self._fill_sum / denom if denom else 0.0

    # -- driving (overrides the sequential service loop) ---------------------

    def _turn(self) -> None:
        """One turn of the service loop: fill, then the decode step, as one
        ``engine/step`` span. While it is open every span and event row
        stays in memory (``Tracer.hold``); what a reader waits for (a
        ``request`` row, a ``compile`` event) is written right after it
        closed, once, under ``engine/flush``."""
        tr = self._tracer
        if tr is None:
            self._fill_slots()
            self._engine_step()
            return
        with tr.hold(), tr.span("engine/step", step=self._engine_steps) as sp:
            self._fill_slots()
            sp.set("active", len(self._active_ids()))
            sp.set("tokens", self._engine_step() or 0)
        if tr.flush_due():
            with tr.span("engine/flush"):
                tr.flush()

    def _end_drive(self) -> None:
        """After a drive loop: nothing a finished ``pump``/``run_*`` recorded
        stays unwritten."""
        if self._tracer is not None:
            self._tracer.flush()

    def pump(self, max_requests: Optional[int] = None) -> int:
        """Drive the engine until the queue AND the batch drain (or until
        ``max_requests`` reached terminal outcomes)."""
        terminal0 = sum(self._n[o] for o in
                        ("ok", "error", "timeout", "cancelled"))
        done = 0
        # parked counts as live work: a recovered engine may start with
        # NOTHING queued or in a slot — everything it owes is parked
        while self._queue or self._active_ids() or self._parked:
            self._check_guard()
            self._turn()
            done = sum(self._n[o] for o in
                       ("ok", "error", "timeout", "cancelled")) - terminal0
            if max_requests is not None and done >= max_requests:
                break
        self._end_drive()
        return done

    def run_closed(self, specs, *, concurrency: int = 4,
                   deadline_s: Optional[float] = None):
        """Closed-loop drive through the ENGINE: ``concurrency`` requests
        admitted/in flight; completions admit the next. Same record/books
        contract as the parent's sequential loop."""
        if concurrency < 1:
            raise ValueError("run_closed needs concurrency >= 1")
        from collections import deque as _deque

        pending = _deque(specs)
        out = []

        def admit():
            while pending and (len(self._queue) + len(self._active_ids())) < concurrency:
                out.append(self.submit(pending.popleft(), deadline_s=deadline_s))

        admit()
        while self._queue or pending or self._active_ids() or self._parked:
            self._check_guard()
            admit()
            if not (self._queue or self._active_ids() or self._parked):
                continue
            self._turn()
        self._end_drive()
        if self._draining:
            self.drain()
        return out

    def run_open(self, specs, *, rate_rps: Optional[float] = None,
                 offsets: Optional[List[float]] = None,
                 deadline_s: Optional[float] = None, seed: int = 1):
        """Open-loop drive through the ENGINE (the item-1 certification
        remainder: rate floors at engine scale): arrivals at seeded Poisson
        offsets (or explicit ``offsets``); between arrivals the live batch
        keeps stepping, and every arrival whose time has passed joins at
        the next fill/step boundary — so the measured achieved-rps is the
        engine absorbing an externally-imposed rate, not self-throttling.
        Under a ``ManualClock`` the idle gaps advance the injected
        timeline; under a real clock the batched steps themselves move it."""
        from collections import deque as _deque

        specs = list(specs)
        offsets = self._resolve_offsets(specs, rate_rps, offsets, seed)
        t0 = float(self._clock())
        pending = _deque(zip(specs, offsets))
        out = []
        while pending or self._queue or self._active_ids() or self._parked:
            self._check_guard()
            # admit every arrival whose time has passed on the clock
            while pending and t0 + pending[0][1] <= float(self._clock()):
                spec, off = pending.popleft()
                out.append(self.submit(spec, arrival_s=t0 + off, deadline_s=deadline_s))
            if not (self._queue or self._active_ids() or self._parked):
                if pending:  # idle: jump to the next arrival
                    spec, off = pending.popleft()
                    self._advance_to(t0 + off)
                    out.append(
                        self.submit(spec, arrival_s=t0 + off, deadline_s=deadline_s)
                    )
                continue
            self._turn()
        self._end_drive()
        if self._draining:
            self.drain()
        return out

    # the engine keeps no per-request worker estimate: queue-wait projection
    # rides the parent's EWMA, updated here per retire via _busy_until


@dataclass
class _EngineSlot:
    """Host-side record of one occupied decode slot."""

    ticket: _Ticket
    slot_id: int
    ca_grant: object
    sa_grant: object
    tokens_out: int = 0
    ttft_s: Optional[float] = None
    compiled: bool = False
    first_token: Optional[int] = None
    outcome: Optional[str] = None  # set mid-decode by the token seam
    # Evictline: how many times this request was page-evicted (parked and
    # later resumed by prefill replay); 0 for a request that never left its
    # slot. Rides the slot object THROUGH the parked queue — a parked
    # request IS its slot record minus the device slot and the grants.
    evictions: int = 0
    # speculative slot mode: verify spans this slot rode and raw accepted
    # draft tokens across them (pre-budget-clip — drafter quality, not
    # serving accounting)
    spec_spans: int = 0
    spec_accepted: int = 0
    span = None

    def __post_init__(self):
        from perceiver_io_tpu.obs import trace as obs_trace
        from perceiver_io_tpu.obs.metrics import Histogram

        self.request_id = obs_trace.new_span_id()
        self.hist = Histogram("tpot_s")
        self.step_times: List[float] = []
        self.batch_sizes: List[int] = []
        self.t_joined = time.perf_counter()


# ---------------------------------------------------------------------------
# jitted state transitions (join / retire)
# ---------------------------------------------------------------------------


def _join_state(state, slot, ca_pages, sa_pages, prefill_cache, slot_row):
    """Land one prefilled request in decode slot ``slot``: commit its prompt
    KV into the granted pages and write its per-slot scalars. Donated —
    pools update in place."""
    import jax.numpy as jnp

    from perceiver_io_tpu.core.cache import commit_prefill

    first_token, rng, done0, pad_row_pre, pos_shift_row = slot_row
    caches = state["cache"]
    new_ca = commit_prefill(
        caches[0], slot, ca_pages, prefill_cache[0], prefill_cache[0].length
    )
    new_sas = tuple(
        commit_prefill(c, slot, sa_pages, pc, pc.length)
        for c, pc in zip(caches[1:], prefill_cache[1:])
    )
    extra = {}
    if "draft_cache" in state:
        # speculative slot mode: the drafter's caches are the flagship
        # prefill caches' PREFIX (shared trunk weights — generation.
        # make_drafter), committed into the mirrored drafter pools under
        # the SAME page ids the slot's grant names
        dcaches = state["draft_cache"]
        new_dca = commit_prefill(
            dcaches[0], slot, ca_pages, prefill_cache[0], prefill_cache[0].length
        )
        new_dsas = tuple(
            commit_prefill(c, slot, sa_pages, pc, pc.length)
            for c, pc in zip(dcaches[1:], prefill_cache[1:])
        )
        extra["draft_cache"] = (new_dca,) + new_dsas
    cap = caches[0].capacity
    pad_row = jnp.zeros((cap,), bool)
    n_pre = pad_row_pre.shape[0]
    pad_row = lax_update(pad_row, pad_row_pre, min(n_pre, cap))
    return dict(
        state,
        cache=(new_ca,) + new_sas,
        **extra,
        ca_start=state["ca_start"].at[slot].set(0),
        sa_start=state["sa_start"].at[slot].set(0),
        token=state["token"].at[slot].set(first_token),
        rng=state["rng"].at[slot].set(rng),
        done=state["done"].at[slot].set(done0),
        pad_slots=state["pad_slots"].at[slot].set(pad_row),
        pos_shift=state["pos_shift"].at[slot].set(pos_shift_row),
    )


def lax_update(row, prefix, n):
    """row[:n] = prefix[:n] with static n (helper kept tiny for jit reuse)."""
    return row.at[:n].set(prefix[:n])


def _retire_state(state, slot):
    """Device half of a retire: table row back to scratch, length 0, slot
    parked done with a neutral token."""
    from perceiver_io_tpu.core.cache import release_slot

    caches = tuple(release_slot(c, slot) for c in state["cache"])
    extra = {}
    if "draft_cache" in state:
        extra["draft_cache"] = tuple(
            release_slot(c, slot) for c in state["draft_cache"]
        )
    return dict(
        state,
        cache=caches,
        **extra,
        token=state["token"].at[slot].set(0),
        done=state["done"].at[slot].set(True),
        ca_start=state["ca_start"].at[slot].set(0),
        sa_start=state["sa_start"].at[slot].set(0),
        pad_slots=state["pad_slots"].at[slot].set(False),
    )
