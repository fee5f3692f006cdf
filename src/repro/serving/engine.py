"""Reasoning-serving facade over the layered serving stack.

The engine is now a thin orchestration layer; the real machinery lives in
three modules (DESIGN.md §4.4 + this PR's refactor):

  * ``serving/request.py``   — per-request lifecycle state machine
    (QUEUED -> PREFILLING -> DECODING -> EXITED/EXHAUSTED) carrying the EAT
    trace and exit-reason metadata,
  * ``serving/scheduler.py`` — slot allocation + FIFO admission policy for
    continuous batching (pure host Python, no jax),
  * ``serving/executor.py``  — every jitted device program (prefill,
    chunked decode with the inlined probe/monitor, admit, rollout, probe),
    built with explicit shardings from ``serve_state_pspecs`` /
    ``cache_pspecs`` and with the ServeState/cache DONATED so chunked
    decode updates the KV cache in place instead of re-allocating it.

``ReasoningEngine`` keeps the pre-refactor API (``start`` / ``reason`` /
``serve`` / ``force_answer`` / ``reason_with_trace`` ...) so examples,
benchmarks, and tests are untouched.  With a mesh on ``model.ctx`` the same
calls run data-parallel over batch rows and tensor-parallel over heads —
``tests/test_mesh_serve.py`` pins 8-way simulated-mesh ``serve()`` to the
single-device token stream.

Donation contract (inherited from the executor): ``reason()``, ``serve()``
and ``_admit()`` consume the ServeState they are handed — continue from the
returned state; the passed-in one is dead.

Per-sequence adaptivity in a batched TPU loop: exited sequences stay in
their slots with ``active=False`` — their sampled tokens are replaced by
PAD, their monitor state freezes, and cache writes become don't-cares
(nothing reads a finished sequence's future slots).

The same machinery provides the paper's evaluation harness:
``reason_with_trace`` generates one long chain and records, at every
evaluation point, EAT / confidence / forced-rollout answers — the offline
"simulated early exiting" protocol of App. H.
"""
from __future__ import annotations

import dataclasses
import time
from types import SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.eat import ProbeSpec
from repro.core.monitor import ReasoningMonitor
from repro.models.model import Model
from repro.serving.cache import (
    CacheConfig,
    alloc_cache,
    alloc_paged_template,
    page_align,
)
from repro.serving.executor import (
    Executor,
    ProxyExecutor,
    ServeState,
    positions_for,
)
from repro.serving.proxy import ProxyConfig, ProxyTier
from repro.serving.request import Request
from repro.serving.sampler import SamplerConfig, sample
from repro.serving.scheduler import (
    PageAllocator,
    SlotScheduler,
    pools_can_admit,
)

__all__ = ["CacheConfig", "EngineConfig", "ProxyConfig", "ReasoningEngine",
           "ServeState"]


@dataclasses.dataclass
class EngineConfig:
    max_reasoning_tokens: int = 1024
    capacity: int = 2048                 # cache slots (logical, when paged)
    pad_id: int = 0
    end_think_id: int = 1
    newline_id: int = 2
    eos_id: int = 3
    chunk_len: int = 32                  # decode steps per jitted dispatch
    sampler: SamplerConfig = dataclasses.field(default_factory=SamplerConfig)
    # KV-cache backend for serve(): ring (dense, capacity is a batch-
    # lifetime bound) or paged (block pool, capacity is per-block
    # bookkeeping — docs/serving.md)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)


class ReasoningEngine:
    """The serving facade, in one of two monitor modes:

    * ``monitor="self"`` (default): white-box — the reasoning model is also
      the EAT monitor model; the probe runs inline in the decode chunk.
    * ``monitor="proxy"`` (``proxy=ProxyConfig(...)``): black-box — the
      generator decodes whole chunks with NO inline probe (its executor
      never builds a probe program; no generator logits feed the exit
      decision), and a second model shadows the emitted chunks through a
      ``ProxyExecutor``, supplying ``eat_trace``/``exit_step`` through the
      executor's ``retract`` program.  Same-params proxies reproduce
      self-EAT serving bit-for-bit under greedy sampling
      (tests/test_proxy_serve.py).
    """

    def __init__(self, model: Model, params, ecfg: EngineConfig,
                 monitor: ReasoningMonitor | None = None,
                 proxy: ProxyConfig | None = None):
        from repro.core.stopping import EATStopper

        # the decode-attention impl is an EngineConfig.cache knob
        # (--attn-impl): bake it into the model so every executor program —
        # chunk, probe, rollout, shadow — traces the same read path, and
        # pin the ring comparator's block size to the paged page size (the
        # per-impl paged==ring bit-exactness contract, docs/architecture.md)
        ccfg = ecfg.cache
        if (model.paged_attn_impl != ccfg.attn_impl
                or model.paged_attn_page != ccfg.page_size):
            model = dataclasses.replace(model,
                                        paged_attn_impl=ccfg.attn_impl,
                                        paged_attn_page=ccfg.page_size)
        self.model = model
        self.ecfg = ecfg
        if monitor is None:
            monitor = ReasoningMonitor(
                stopper=EATStopper(),
                probe=ProbeSpec((ecfg.end_think_id,)),
                newline_id=ecfg.newline_id,
            )
        self.monitor = monitor
        self.executor = Executor(model, params, ecfg, monitor)
        # place params on the mesh once so per-dispatch in_shardings never
        # re-transfer them (no-op on single device)
        self.params = self.executor.shard_params(params)
        self.proxy = proxy
        self.proxy_executor = None
        self.proxy_params = None
        if proxy is not None:
            if model.cfg.arch_type in ("ssm", "hybrid"):
                raise ValueError(
                    "monitor='proxy' needs a slot-addressed generator cache "
                    "to retract overshoot tokens; SSM/hybrid recurrences "
                    "cannot be rewound to the proxy's exit step."
                )
            pccfg = proxy.cache or ecfg.cache
            proxy_model = proxy.model
            if (proxy_model.paged_attn_impl != pccfg.attn_impl
                    or proxy_model.paged_attn_page != pccfg.page_size):
                proxy_model = dataclasses.replace(
                    proxy_model, paged_attn_impl=pccfg.attn_impl,
                    paged_attn_page=pccfg.page_size)
            self.proxy_executor = ProxyExecutor(proxy_model, proxy.params,
                                                ecfg, monitor)
            self.proxy_params = self.proxy_executor.shard_params(proxy.params)

    @property
    def monitor_mode(self) -> str:
        return "proxy" if self.proxy is not None else "self"

    def _across_tiers(self, tree):
        """Ferry per-row scalars between the generator's and the proxy's
        meshes (a host hop of a few KB, both directions; identity when the
        tiers share a ctx — the common case)."""
        if self.proxy_executor.ctx.mesh is self.executor.ctx.mesh:
            return tree
        return jax.tree_util.tree_map(np.asarray, tree)

    def _positions(self, pos1d):
        return positions_for(self.model.cfg, pos1d)

    # engine internals the benchmarks/tests poke at, now delegated
    @property
    def _decode_fn(self):
        return self.executor.decode_step

    # ------------------------------------------------------------- prefill
    def start(self, prompts: jax.Array, prompt_len: jax.Array, rng,
              *, frames=None, image_embeds=None,
              capacity: int | None = None) -> ServeState:
        """prompts: (B, S) LEFT-padded token ids; prompt_len: (B,).

        Positions are 0..len-1 per sequence (pad slots get -1 = masked).
        ``capacity`` overrides ``EngineConfig.capacity`` (the paged serve
        path prefills into a prompt-sized dense cache and packs it into the
        page pool afterwards).
        """
        model, ecfg = self.model, self.ecfg
        B, S = prompts.shape
        pad = S - prompt_len                                # (B,)
        pos1d = jnp.arange(S, dtype=jnp.int32)[None, :] - pad[:, None]
        pos1d = jnp.where(pos1d >= 0, pos1d, -1)
        n_img = 0
        if image_embeds is not None:
            n_img = image_embeds.shape[1]
            img_pos = jnp.broadcast_to(
                jnp.arange(n_img, dtype=jnp.int32)[None], (B, n_img)
            )
            pos1d = jnp.concatenate([img_pos, jnp.where(pos1d >= 0, pos1d + n_img, -1)], 1)
        cache = alloc_cache(model.cfg, B, capacity or ecfg.capacity)
        hidden, cache = self.executor.prefill(
            self.params, prompts, self._positions(pos1d), pos1d, cache,
            frames=frames, image_embeds=image_embeds,
        )
        next_pos = prompt_len + n_img
        logits_last = self.model.logits(self.params, hidden[:, -1:])[:, 0]
        rng, sub = jax.random.split(rng)
        first = sample(sub, logits_last, model.cfg.vocab, ecfg.sampler)
        buf = jnp.full((B, ecfg.max_reasoning_tokens + 8), ecfg.pad_id, jnp.int32)
        buf = buf.at[:, 0].set(first)
        mon = self.monitor.init(B)
        return ServeState(
            cache=cache,
            rng=rng,
            active=jnp.ones((B,), bool),
            next_pos=next_pos.astype(jnp.int32),
            last_token=first,
            n_reasoning=jnp.ones((B,), jnp.int32),
            monitor=mon,
            ended_think=(first == ecfg.end_think_id),
            out_tokens=buf,
            out_len=jnp.ones((B,), jnp.int32),
        )

    # ------------------------------------------------------------- loop
    def reason(self, state: ServeState, *, max_tokens: int | None = None,
               use_monitor: bool = True,
               chunk_len: int | None = None) -> ServeState:
        """Run the reasoning loop until all sequences exit (EAT stop, natural
        </think>, or token budget).  Device-resident: each iteration is one
        jitted ``decode_chunk`` dispatch advancing up to ``chunk_len``
        tokens; the only host sync is the per-chunk ``active.any()``.
        CONSUMES ``state`` (the chunk program donates its buffers)."""
        if use_monitor and self.proxy is not None:
            raise ValueError(
                "monitor='proxy' runs through serve() (the proxy tier must "
                "prefill the prompts the scheduler admits — a bare "
                "ServeState does not carry them); use serve(), or pass "
                "use_monitor=False for an unmonitored reason()."
            )
        budget = jnp.asarray(max_tokens or self.ecfg.max_reasoning_tokens,
                             jnp.int32)
        # chunk_len <= 0 would make the device loop a no-op and spin the
        # host loop forever
        chunk = jnp.asarray(max(1, chunk_len or self.ecfg.chunk_len), jnp.int32)
        while True:
            state = self.executor.decode_chunk(self.params, state, budget,
                                               chunk, use_monitor=use_monitor)
            if not bool(state.active.any()):
                break
        return state

    def _reason_per_token(self, state: ServeState, *,
                          max_tokens: int | None = None,
                          use_monitor: bool = True) -> ServeState:
        """The pre-chunking host loop: one jitted dispatch per token plus two
        host syncs per iteration.  Kept verbatim as the baseline for
        ``benchmarks/engine_throughput.py``."""
        ecfg = self.ecfg
        budget = max_tokens or ecfg.max_reasoning_tokens
        while bool(state.active.any()) and int(state.n_reasoning.max()) < budget:
            state = self.executor.decode_step(self.params, state)
            if use_monitor:
                due = self.monitor.due(state.monitor, state.last_token)
                if bool((due & state.active).any()):
                    eat = self.executor.probe(self.params, state.cache,
                                              state.next_pos)
                    mon = self.monitor.update(state.monitor, eat, due, state.active)
                    state = state._replace(monitor=mon)
                else:
                    state = state._replace(
                        monitor=self.monitor.tick_no_eval(state.monitor, state.active)
                    )
                exits = state.monitor.stop_flag
            else:
                exits = jnp.zeros_like(state.active)
            over = state.n_reasoning >= budget
            state = state._replace(active=state.active & ~exits & ~state.ended_think & ~over)
        return state

    # ------------------------------------------------ continuous batching
    def _admit(self, state: ServeState, one: ServeState, slot: int) -> ServeState:
        """Recycle batch ``slot`` with the single-sequence state ``one``
        (one jitted dispatch; ``slot`` is a traced scalar, so admissions
        into different slots share the compilation).  CONSUMES ``state``."""
        return self.executor.admit(state, one, slot)

    def _serve_setup(self, prompts, prompt_len, rng, *, batch_size: int,
                     max_tokens: int | None, use_monitor: bool,
                     chunk_len: int | None,
                     overlap: bool = False) -> SimpleNamespace:
        """Shared front half of both serve loops (sync below, overlapped in
        ``serving.pipeline``): parse the request list, build the scheduler /
        page allocator / proxy tier, prefill + pack the initial cohort, and
        run the setup-time capacity checks.  Returns the namespace the loop
        bodies consume; ``cur0`` is the post-prefill ring pointer (already
        synced by the capacity check — the overlapped loop seeds its host
        mirror from it instead of re-syncing).  ``overlap`` widens the
        auto-sized page pool by one row allotment: the pipeline parks a
        harvested row's pages on the in-flight fence for one boundary, so
        a slot's old and new occupant briefly double-book its footprint."""
        prompts_np = np.asarray(prompts)
        plen_np = np.asarray(prompt_len)
        n_req = prompts_np.shape[0]
        S = prompts_np.shape[1]
        B = min(batch_size, n_req)
        budget = int(max_tokens or self.ecfg.max_reasoning_tokens)
        budget_dev = jnp.asarray(budget, jnp.int32)
        chunk_py = max(1, chunk_len or self.ecfg.chunk_len)
        chunk = jnp.asarray(chunk_py, jnp.int32)

        t0 = time.perf_counter()
        requests = [
            Request(rid=i, prompt=prompts_np[i], prompt_len=int(plen_np[i]),
                    submitted_at=t0)
            for i in range(n_req)
        ]
        sched = SlotScheduler(requests, B, capacity=self.ecfg.capacity,
                              budget=budget)

        # ---- cache backend (docs/serving.md): the paged path keeps the
        # ring's logical addressing but backs it with a page pool, so the
        # host loop additionally (a) maps pages for every slot range a
        # dispatch may write, (b) pushes the allocator's table before each
        # dispatch, (c) frees a request's pages at harvest
        ccfg = self.ecfg.cache
        paged = ccfg.kind == "paged"
        alloc = None
        C_pre = None
        probe_m = len(self.monitor.probe)
        if paged:
            ps = ccfg.page_size
            C_log = page_align(self.ecfg.capacity, ps)
            n_blocks = C_log // ps
            num_pages = ccfg.num_pages or (
                B * n_blocks + 1 + (n_blocks if overlap else 0))
            alloc = PageAllocator(num_pages, ps, n_blocks, B)
            C_pre = page_align(S, ps)      # prompt-sized prefill capacity

        # ---- proxy tier (monitor="proxy"): the generator chunk runs with
        # its inline monitor OFF — the black-box contract — and the proxy
        # shadows each chunk, feeding exits back through retract
        proxy_mode = use_monitor and self.proxy is not None
        ptier = None
        self._ptier = None       # kept for post-serve stats (tests/benches)
        if proxy_mode:
            ptier = self._ptier = ProxyTier(
                self.proxy_executor, self.proxy_params, self.ecfg,
                self.monitor, self.proxy.cache or ccfg,
                self.proxy.capacity or self.ecfg.capacity, budget,
            )
        gen_monitor = use_monitor and not proxy_mode

        cohort = sched.start_batch()
        rng, sub = jax.random.split(rng)
        state = self.start(jnp.asarray(prompts_np[:B]),
                           jnp.asarray(plen_np[:B]), sub,
                           capacity=C_pre if paged else None)
        if paged:
            for req in cohort:
                alloc.ensure(req.slot, 0, S - 1)       # the prompt pages
            template = alloc_paged_template(
                self.model.cfg, B, C_log, ps, num_pages, alloc=alloc,
                native=ccfg.attn_impl != "gather")
            state = state._replace(cache=self.executor.pack_paged(
                template, state.cache, alloc.table))
        if ptier is not None:
            ptier.start_batch(prompts_np[:B], plen_np[:B],
                              [req.slot for req in cohort])
        for req in cohort:
            req.begin_decode()
        cur0 = int(state.cache["cur"])
        sched.check_capacity(cur0, "the initial batch")
        if ptier is not None:
            ptier.check_capacity("the initial batch")

        # the generator only pays a probe tail when IT runs the probe; in
        # proxy mode that tail belongs to the proxy tier's pool
        gen_tail = 0 if proxy_mode else probe_m
        return SimpleNamespace(
            prompts_np=prompts_np, plen_np=plen_np, n_req=n_req, S=S, B=B,
            budget=budget, budget_dev=budget_dev, chunk_py=chunk_py,
            chunk=chunk, requests=requests, sched=sched, paged=paged,
            alloc=alloc, C_pre=C_pre, proxy_mode=proxy_mode, ptier=ptier,
            gen_monitor=gen_monitor, gen_tail=gen_tail, rng=rng, state=state,
            cur0=cur0,
        )

    def serve(self, prompts, prompt_len, rng, *, batch_size: int,
              max_tokens: int | None = None, use_monitor: bool = True,
              chunk_len: int | None = None, answer_len: int = 0,
              record_trace: bool = False, overlap: bool = False,
              pipeline_hooks=None) -> list[dict]:
        """Continuous-batching serving loop over N requests with
        ``batch_size`` slots.

        prompts: (N, S) LEFT-padded; prompt_len: (N,).  Each request runs
        the QUEUED -> PREFILLING -> DECODING -> EXITED/EXHAUSTED lifecycle
        (``serving.request``); the FIFO slot policy lives in
        ``serving.scheduler``; all device work is executor programs.
        Sequences that exit early free their slot mid-flight: the result is
        harvested, the next queued prompt is prefilled (B=1) and merged into
        the slot, and the chunked decode resumes with the batch still full.

        With ``EngineConfig.cache.kind == "paged"`` the KV store is the
        block-paged pool (docs/serving.md): an exiting request's pages are
        reclaimed at harvest and back the very next admission, and the
        token streams/exit steps/EAT trajectories are bit-identical to the
        ring path's.  Backpressure is admission-time only — an admission
        waits (rather than failing) while the pool is momentarily full,
        but the optimistic prompt+one-page admission rule means a pool
        undersized for the RESIDENT batch (below ~batch * (prompt + budget
        + probe) / page_size pages) can still exhaust mid-decode, which
        fails fast with a sizing hint rather than corrupting neighbours.

        In ``monitor="proxy"`` mode the same loop runs black-box: the
        generator chunk decodes unmonitored, the proxy tier shadows the
        emitted tokens (its own prefills/pages in lock-step with the
        scheduler), and the executor's ``retract`` reconciles each chunk —
        rewinding rows the proxy stopped mid-chunk and syncing the proxy's
        monitor state so harvest, traces, and exit reasons read identically
        to self-EAT.  Admissions gate on BOTH page pools
        (``scheduler.pools_can_admit``): an exhausted proxy pool defers
        admission independently of the generator pool.

        Returns one dict per request (in request order): the pre-refactor
        keys (``reasoning_tokens``, ``n_reasoning``, ``ended_think``, and —
        when ``answer_len`` > 0 — the greedy forced-answer
        ``answer_tokens``) plus the request metadata: ``exit_reason``
        (``eat`` / ``end_think`` / ``budget``), terminal ``status``,
        per-request ``latency_s``, and — with ``record_trace`` — the
        chunk-boundary ``eat_trace`` (n_reasoning, n_evals, ema_var)
        snapshots.

        With ``overlap=True`` the loop is the double-buffered pipeline of
        ``serving.pipeline``: chunk N+1 is dispatched before chunk N's
        boundary is harvested, admissions/page-table pushes move into the
        overlap window, and in proxy mode the shadow of chunk N runs
        concurrently with generator chunk N+1 (retract lands one boundary
        late — exit latency +≤1 chunk, token streams unchanged).  Under
        greedy sampling the results are bit-identical to ``overlap=False``
        (tests/test_async_serve.py); with temperature sampling the rng
        split schedule differs, so streams may diverge (still valid
        samples).  ``pipeline_hooks`` (a ``serving.pipeline.PipelineHooks``)
        is the test seam for forcing adversarial interleavings.
        """
        ss = self._serve_setup(prompts, prompt_len, rng,
                               batch_size=batch_size, max_tokens=max_tokens,
                               use_monitor=use_monitor, chunk_len=chunk_len,
                               overlap=overlap)
        if overlap:
            from repro.serving.pipeline import serve_overlapped
            try:
                return serve_overlapped(self, ss, answer_len=answer_len,
                                        record_trace=record_trace,
                                        hooks=pipeline_hooks)
            finally:
                if ss.ptier is not None:
                    # drop the proxy tier's device buffers; host-side
                    # allocator stats stay readable via ``_ptier``
                    ss.ptier.state = None
        # ---- synchronous loop (--overlap off): one host round trip per
        # chunk boundary.  The overlapped loop must stay bit-exact with
        # this body under greedy sampling — change them together.
        sched, state, rng = ss.sched, ss.state, ss.rng
        alloc, ptier, paged = ss.alloc, ss.ptier, ss.paged
        proxy_mode, gen_monitor = ss.proxy_mode, ss.gen_monitor
        S, budget_dev = ss.S, ss.budget_dev
        budget, chunk_py, chunk = ss.budget, ss.chunk_py, ss.chunk
        gen_tail, C_pre = ss.gen_tail, ss.C_pre

        def ensure_pages(span: int, *, clamp_to_budget: bool = False):
            """Occupied-slot pages for the next generator dispatch — the
            shared sizing rule lives in ``Executor.ensure_chunk_pages``."""
            return self.executor.ensure_chunk_pages(
                alloc, state, [s for s, _ in sched.bound()], span,
                tail=gen_tail, budget=budget if clamp_to_budget else None,
            )

        while sched.running:
            if bool(state.active.any()):
                if paged:
                    # a chunk writes <= chunk_len decode tokens (fewer for
                    # rows near their budget), each probe another
                    # len(probe) slots past the decode slot
                    state = ensure_pages(chunk_py + gen_tail,
                                         clamp_to_budget=True)
                # host copy BEFORE the dispatch: the chunk donates ``state``
                n_start = np.asarray(state.out_len) if proxy_mode else None
                state = self.executor.decode_chunk(
                    self.params, state, budget_dev, chunk,
                    use_monitor=gen_monitor,
                )
                if proxy_mode:
                    # shadow the chunk through the proxy, then reconcile:
                    # rewind overshoot rows to the proxy's exit step and
                    # sync its monitor into the state (executor.retract)
                    n_emitted = np.asarray(state.out_len) - n_start
                    ptier.begin_chunk(chunk_py,
                                      [s for s, _ in sched.bound()])
                    new_n, pmon = ptier.observe(
                        self._across_tiers(state.out_tokens), n_start,
                        n_emitted, chunk_py,
                    )
                    state = self.executor.retract(
                        state, self._across_tiers(new_n),
                        self._across_tiers(pmon),
                    )
            active_np = np.asarray(state.active)
            if record_trace:
                n_np = np.asarray(state.n_reasoning)
                ev_np = np.asarray(state.monitor.n_evals)
                var_np = np.asarray(
                    self.monitor.stopper.debiased_var(state.monitor.stop_state)
                )
                for s, req in sched.bound():
                    req.record_trace(n_np[s], ev_np[s], var_np[s])
            done = sched.finished_slots(active_np)
            if not done:
                continue
            # harvest results (answers roll out from the still-intact cache
            # rows) BEFORE any slot is overwritten by an admission
            ans = None
            if answer_len:
                if paged:
                    # a rollout writes </think> + answer_len slots past cur
                    state = ensure_pages(answer_len + 1)
                toks, _ = self.force_answer(state, answer_len, greedy=True)
                ans = np.asarray(toks)
            out_tokens = np.asarray(state.out_tokens)
            out_len = np.asarray(state.out_len)
            n_reasoning = np.asarray(state.n_reasoning)
            ended = np.asarray(state.ended_think)
            eat_stop = np.asarray(state.monitor.stop_flag)
            for s, req in done:
                sched.release(s)
                req.finish(
                    reasoning_tokens=out_tokens[s, :out_len[s]].copy(),
                    n_reasoning=int(n_reasoning[s]),
                    ended_think=bool(ended[s]),
                    eat_stop=bool(eat_stop[s]),
                    answer_tokens=ans[s].copy() if ans is not None else None,
                )
                if paged:
                    # reclaim the moment a request exits: these pages back
                    # the admissions below, in the same batch
                    alloc.free_row(s)
                if ptier is not None:
                    # the proxy's shadow pages are reclaimed in the same
                    # breath — a proxy-driven exit frees BOTH pools
                    ptier.free_row(s)
            # admission sweeps EVERY free slot, not just this round's
            # harvested ones: a paged admission deferred earlier (pool
            # momentarily full) left its slot empty, and the pages freed
            # just above are what let it proceed now.  (For the ring this
            # is identical to sweeping ``done``: a ring slot is only ever
            # left empty once the queue has drained.)
            for s in (s for s, r in enumerate(sched.slots) if r is None):
                if sched.pending == 0:
                    continue
                # refuse BEFORE popping the queue: a capacity failure must
                # leave the scheduler consistent (no stranded PREFILLING
                # request holding a slot).  The logical-ring wrap guard
                # applies to BOTH backends (paged keeps ring addressing);
                # the paged page check DEFERS instead of refusing — the
                # request stays queued until an exit frees enough pages.
                sched.check_capacity(int(state.cache["cur"]),
                                     "another admission")
                if ptier is not None:
                    ptier.check_capacity("another admission")
                # both pools must cover the prompt (all-or-nothing): an
                # exhausted proxy pool defers the admission exactly like an
                # exhausted generator pool — the request stays queued until
                # a harvest frees pages in whichever pool is short
                if not pools_can_admit(S, alloc,
                                       ptier.alloc if ptier else None):
                    for a in (alloc, ptier.alloc if ptier else None):
                        if a is not None and not a.can_admit(S):
                            a.deferrals += 1
                    continue
                nxt = sched.admit_next(s)
                rng, sub = jax.random.split(rng)
                one = self.start(jnp.asarray(nxt.prompt[None]),
                                 jnp.asarray([nxt.prompt_len]), sub,
                                 capacity=C_pre if paged else None)
                if paged:
                    row_table = alloc.admit_row(s, S,
                                                int(state.cache["cur"]))
                    state = self.executor.admit_paged(state, one, s,
                                                      row_table)
                else:
                    state = self._admit(state, one, s)
                if ptier is not None:
                    ptier.admit(s, nxt.prompt, nxt.prompt_len, S)
                nxt.begin_decode()
            if sched.pending and not sched.running:
                # every slot is empty yet the queue cannot drain — name the
                # pool that is actually too small to hold one request
                if paged and not alloc.can_admit(S):
                    raise RuntimeError(
                        f"paged KV cache cannot hold a single request: "
                        f"{alloc.free_pages} pages free with every slot "
                        f"empty, but a prompt needs "
                        f"{alloc.blocks_for(S) + 1} pages. "
                        f"Raise CacheConfig.num_pages."
                    )
                if ptier is not None and not ptier.can_admit(S):
                    raise RuntimeError(
                        f"proxy paged KV cache cannot hold a single "
                        f"request: {ptier.alloc.free_pages} pages free with "
                        f"every slot empty, but a prompt needs "
                        f"{ptier.alloc.blocks_for(S) + 1} pages. "
                        f"Raise ProxyConfig.cache.num_pages."
                    )
        if ptier is not None:
            # drop the proxy tier's device buffers (its KV cache/pool is
            # the tier's largest allocation); the host-side allocator
            # stats stay readable via ``_ptier`` for tests and benches
            ptier.state = None
        return [r.to_result() for r in ss.requests]

    # ------------------------------------------------------------- answers
    def force_answer(self, state: ServeState, n_tokens: int, rng=None,
                     *, greedy: bool = False):
        """GenTillEoS(Q, <think>, R, </think>; theta) — Eq. (10)/Alg. 1 line 11.
        Returns (tokens (B,n), logprobs (B,n))."""
        rng = rng if rng is not None else state.rng
        return self.executor.rollout(
            self.params, state.cache, state.next_pos, state.last_token, rng,
            n=n_tokens, greedy=greedy,
        )

    def rollout_answers(self, state: ServeState, k: int, n_tokens: int, rng):
        """K independent forced rollouts (for Pass@1 / #UA@K).  Returns
        tokens (K, B, n)."""
        rngs = jax.random.split(rng, k)
        outs = [self.executor.rollout(self.params, state.cache, state.next_pos,
                                      state.last_token, r, n=n_tokens)[0]
                for r in rngs]
        return jnp.stack(outs)

    def eval_eat_now(self, state: ServeState) -> jax.Array:
        return self.executor.probe(self.params, state.cache, state.next_pos)

    # ------------------------------------------------------------- tracing
    def reason_with_trace(
        self, state: ServeState, *, max_tokens: int, rollout_k: int = 0,
        rollout_len: int = 8, answer_extract: Optional[Callable] = None,
        confidence_len: int = 0,
    ) -> tuple[ServeState, list[dict]]:
        """Generate one long chain; at every due point record EAT (and
        optionally K rollout answers + confidence).  The offline evaluation
        protocol of App. H — no early exit is taken.

        Reuses the device-resident chunk step with ``chunk_len`` matched to
        the evaluation schedule (1 for the paragraph schedule — a due point
        can fall on any token — ``every_n`` for the fixed stride), so the
        per-evaluation host hooks below still run between chunks."""
        trace: list[dict] = []
        # a copy, not a reference: the chunk below donates ``state``, and
        # its rng buffer with it
        rng = jnp.copy(state.rng)
        newline_sched = self.monitor.schedule == "newline"
        chunk = jnp.asarray(1 if newline_sched else self.monitor.every_n,
                            jnp.int32)
        budget = jnp.asarray(max_tokens, jnp.int32)
        while bool(state.active.any()):
            # host copy BEFORE the chunk: the chunk donates ``state``, so a
            # live reference to its n_reasoning buffer would be invalidated
            prev_n = np.asarray(state.n_reasoning)
            state = self.executor.decode_chunk(self.params, state, budget,
                                               chunk, use_monitor=False)
            if newline_sched:
                due = state.last_token == self.monitor.newline_id
            else:
                due = jnp.ones_like(state.active)
            # mask by "emitted a token this chunk", not post-chunk active:
            # the chunk latches active=False in the same device step that
            # reaches the budget, but the budget-th token's evaluation point
            # still belongs in the trace (App. H records it)
            emitted = state.n_reasoning > prev_n
            due = due & emitted
            if bool(due.any()):
                rec: dict = {
                    "n_tokens": np.asarray(state.n_reasoning),
                    "due": np.asarray(due),
                    "eat": np.asarray(self.eval_eat_now(state)),
                }
                if rollout_k:
                    rng, sub = jax.random.split(rng)
                    rolls = self.rollout_answers(state, rollout_k, rollout_len, sub)
                    rec["rollouts"] = np.asarray(rolls)
                    if answer_extract is not None:
                        rec["answers"] = np.stack(
                            [answer_extract(np.asarray(rolls[i])) for i in range(rollout_k)]
                        )
                if confidence_len:
                    _, lps = self.force_answer(state, confidence_len, greedy=True)
                    rec["confidence"] = np.asarray(jnp.exp(lps.mean(-1)))
                mon = self.monitor.update(state.monitor, jnp.asarray(rec["eat"]),
                                          due, emitted)
                state = state._replace(monitor=mon)
                rec["ema_var"] = np.asarray(
                    self.monitor.stopper.debiased_var(mon.stop_state)
                )
                trace.append(rec)
        return state, trace
