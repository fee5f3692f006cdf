"""Black-box EAT monitoring with a proxy model (paper §4.2, Fig. 5).

The reasoning model theta is a black box: only its *verbal* token stream is
visible (e.g. a streaming API).  A small local proxy model phi maintains its
own KV cache over the same stream — chunks are prefilled as they arrive —
and EAT is computed from phi's next-token distribution after a virtual
``</think>`` (+ prefix).  Because chunk prefill + probe on the small proxy
is much faster than the big model's generation (Fig. 5b), monitoring
overlaps with the stream and adds no wall-clock latency; we measure that
headroom in benchmarks/fig5_blackbox.py.

NOTE: theta and phi must share a tokenizer family for the stream to be
re-tokenized faithfully (the paper pairs DeepSeek-R1 distills, or
re-tokenizes Claude text with Qwen's tokenizer).  In this framework both
ends speak the synthetic task tokenizer.

Two layers live here:

* ``ProxyMonitor`` — the standalone streaming monitor the examples drive by
  hand (one prefill+probe per arriving chunk, host loop);
* ``ProxyConfig`` + ``ProxyTier`` — the serving-stack integration: one
  ``ProxyTier`` per ``serve()`` run orchestrates a
  ``serving.executor.ProxyExecutor`` (shadow-decode programs, own KV
  cache/page pool) in lock-step with the generator's scheduler — prompt
  prefills at admission, page bookkeeping before each chunk, page frees at
  harvest — so proxy-driven exits recycle slots and pages exactly like
  self-EAT exits.  ``ReasoningEngine(..., proxy=ProxyConfig(...))`` turns
  it on (``monitor="proxy"`` mode; docs/serving.md §Black-box monitoring).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.monitor import MonitorState, ReasoningMonitor
from repro.models.model import Model
from repro.serving.cache import (
    CacheConfig,
    alloc_cache,
    alloc_paged_template,
    page_align,
)
from repro.serving.executor import (
    ProxyExecutor,
    ServeState,
    build_stream_monitor_programs,
    positions_for,
)
from repro.serving.scheduler import PageAllocator
from repro.serving.telemetry import Telemetry


@dataclasses.dataclass
class ProxyMonitor:
    """Streaming EAT monitor around a proxy model."""

    model: Model
    params: dict
    monitor: ReasoningMonitor
    capacity: int = 2048

    def __post_init__(self):
        # every jitted program comes from the executor layer — proxy.py is
        # host orchestration only (the layering contract, tools/audit)
        self._consume, self._probe, self._prefill = \
            build_stream_monitor_programs(self.model, self.monitor.probe)

    def start(self, prompts: jax.Array, prompt_len: jax.Array):
        """Feed the question prompt (left-padded).  Returns opaque state."""
        B, S = prompts.shape
        pad = S - prompt_len
        pos1d = jnp.arange(S, dtype=jnp.int32)[None, :] - pad[:, None]
        pos1d = jnp.where(pos1d >= 0, pos1d, -1)
        cache = alloc_cache(self.model.cfg, B, self.capacity)
        pos3 = (jnp.broadcast_to(pos1d[..., None], pos1d.shape + (3,))
                if self.model.cfg.mrope_sections else pos1d)
        _, cache = self._prefill(self.params, prompts, pos3, pos1d, cache)
        return {
            "cache": cache,
            "next_pos": prompt_len.astype(jnp.int32),
            "monitor": self.monitor.init(B),
            "probe_seconds": [],
        }

    def observe_chunk(self, state: dict, chunk: jax.Array,
                      active: jax.Array | None = None, *,
                      next_pos: jax.Array | None = None) -> dict:
        """Consume a chunk of streamed reasoning tokens and evaluate EAT.

        chunk: (B, c) token ids (PAD-right for finished sequences).
        ``next_pos`` (B,) is the authoritative stream offset from the
        generator's request state; when omitted the monitor falls back to
        its internal counter.  Pass it whenever rows can be re-seeded
        mid-stream (deferred admissions, slot recycling): the internal
        counter only tracks chunks THIS monitor consumed, so a recycled
        row's counter is stale and the probe would land at the previous
        occupant's offset.  Returns updated state;
        ``state['monitor'].stop_flag`` is the exit signal to send back to
        the black-box generator.
        """
        B, c = chunk.shape
        if active is None:
            active = jnp.ones((B,), bool)
        base_pos = (state["next_pos"] if next_pos is None
                    else jnp.asarray(next_pos, jnp.int32))
        t0 = time.perf_counter()
        cache, next_pos = self._consume(self.params, state["cache"], chunk, base_pos)
        eat = self._probe(self.params, cache, next_pos)
        eat.block_until_ready()
        dt = time.perf_counter() - t0
        due = jnp.ones((B,), bool)   # chunk arrival = evaluation point
        mon = self.monitor.update(state["monitor"], eat, due, active)
        return {
            "cache": cache,
            "next_pos": next_pos,
            "monitor": mon,
            "probe_seconds": state["probe_seconds"] + [dt],
            "last_eat": eat,
        }

    def should_stop(self, state: dict) -> jax.Array:
        return state["monitor"].stop_flag


# --------------------------------------------------------------------------
# Serving-stack integration: the proxy tier behind ``monitor="proxy"``
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ProxyConfig:
    """The proxy tier's build recipe, handed to ``ReasoningEngine``.

    ``model``/``params`` are the monitor model phi — typically much smaller
    than the generator, possibly on its own (smaller) mesh via
    ``model.ctx``.  ``cache``/``capacity`` default to the engine's own
    backend and logical capacity (the proxy shadows the same stream, so the
    same sizing rules apply); override them to give the proxy its own page
    pool budget (``tests/test_proxy_serve.py`` exercises a deliberately
    undersized proxy pool deferring admissions independently of the
    generator's).
    """

    model: Model
    params: dict
    cache: Optional[CacheConfig] = None     # None -> inherit the engine's
    capacity: Optional[int] = None          # None -> EngineConfig.capacity


class ProxyTier:
    """One ``serve()`` run's host-side orchestration of the proxy tier.

    Owns the proxy's device state (a ``ServeState`` driven exclusively by
    ``ProxyExecutor`` programs) and its page allocator, and exposes the
    hooks the engine's serve loop calls at each lifecycle point:

        start_batch   prefill the initial cohort's prompts
        begin_chunk   map pages the shadow decode may write, push the table
        observe       shadow one generator chunk -> (new_n, proxy monitor)
        free_row      return an exiting row's proxy pages (harvest)
        can_admit     proxy-pool admission gate (defer, don't refuse)
        check_capacity  proxy ring-wrap guard (refuse, like the scheduler's)
        admit         prefill + merge an admitted prompt into a proxy slot

    The tier never sees generator logits and never decides tokens — it
    consumes the emitted stream and returns exit decisions, which the
    engine applies through the generator executor's ``retract`` program.
    Its blocking reads of the proxy state go through the serve call's
    ``telemetry`` (``serve.wait`` spans), its page pushes run in
    ``serve.pages`` spans of pool ``proxy``.
    """

    def __init__(self, executor: ProxyExecutor, params, ecfg,
                 monitor: ReasoningMonitor, cache_cfg: CacheConfig,
                 capacity: int, budget: int, telemetry: Telemetry):
        self.ex = executor
        self.tel = telemetry
        self.params = params
        self.ecfg = ecfg
        self.monitor = monitor
        self.ccfg = cache_cfg
        self.capacity = capacity
        self.budget = budget
        self.paged = cache_cfg.kind == "paged"
        self.probe_m = len(monitor.probe)
        self.state: ServeState | None = None
        self.alloc: PageAllocator | None = None
        self._C_pre: int | None = None

    # ------------------------------------------------------------ lifecycle
    def _fresh(self, prompts: jax.Array, prompt_len: jax.Array,
               capacity: int) -> ServeState:
        """Prompt-prefilled proxy state.  Unlike ``engine.start`` nothing is
        sampled — the proxy never chooses tokens, so ``rng``/``last_token``/
        ``out_tokens`` are inert placeholders; ``n_reasoning`` starts at 1
        to mirror the generator's already-emitted first token."""
        cfg = self.ex.cfg
        B, S = prompts.shape
        pad = S - prompt_len
        pos1d = jnp.arange(S, dtype=jnp.int32)[None, :] - pad[:, None]
        pos1d = jnp.where(pos1d >= 0, pos1d, -1)
        cache = alloc_cache(cfg, B, capacity)
        _, cache = self.ex.prefill(self.params, prompts,
                                   positions_for(cfg, pos1d), pos1d, cache)
        return ServeState(
            cache=cache,
            rng=jax.random.PRNGKey(0),
            active=jnp.ones((B,), bool),
            next_pos=prompt_len.astype(jnp.int32),
            last_token=jnp.zeros((B,), jnp.int32),
            n_reasoning=jnp.ones((B,), jnp.int32),
            monitor=self.monitor.init(B),
            ended_think=jnp.zeros((B,), bool),
            out_tokens=jnp.full((B, 1), self.ecfg.pad_id, jnp.int32),
            out_len=jnp.ones((B,), jnp.int32),
        )

    def start_batch(self, prompts_np, plen_np, rows: list[int]) -> None:
        """Prefill the initial cohort (same rows the scheduler admitted)."""
        B, S = prompts_np.shape
        prompts = jnp.asarray(prompts_np)
        plen = jnp.asarray(plen_np)
        if not self.paged:
            self.state = self._fresh(prompts, plen, self.capacity)
            return
        ps = self.ccfg.page_size
        C_log = page_align(self.capacity, ps)
        n_blocks = C_log // ps
        num_pages = self.ccfg.num_pages or (B * n_blocks + 1)
        self.alloc = PageAllocator(num_pages, ps, n_blocks, B,
                                   sizing_knob="ProxyConfig.cache.num_pages")
        self._C_pre = page_align(S, ps)
        st = self._fresh(prompts, plen, self._C_pre)
        for row in rows:
            self.alloc.ensure(row, 0, S - 1)
        # mirror the engine's template setup: page-native shadow decodes
        # read through the proxy pool's own compacted page list
        template = alloc_paged_template(
            self.ex.cfg, B, C_log, ps, num_pages, alloc=self.alloc,
            native=self.ccfg.attn_impl != "gather")
        self.state = st._replace(cache=self.ex.pack_paged(
            template, st.cache, self.alloc.table))

    # ------------------------------------------------------- chunk shadowing
    def begin_chunk(self, chunk_py: int, bound: list[int]) -> None:
        """Map (and push) pages covering the slots this chunk's shadow
        decode may write: up to ``chunk_py`` consumed tokens (clamped per
        row to its remaining budget) plus the probe tail — the same
        ``Executor.ensure_chunk_pages`` rule the generator loop uses, over
        the proxy's own pool and state."""
        if not self.paged:
            return
        with self.tel.span("serve.pages", pool="proxy"):
            cur, n_r = self.tel.fetch("proxy_pages", (
                self.state.cache["cur"], self.state.n_reasoning))
            self.state = self.ex.ensure_chunk_pages(
                self.alloc, self.state, bound, chunk_py + self.probe_m,
                cur=int(cur), tail=self.probe_m, budget=self.budget,
                n_reasoning=n_r,
            )

    def observe(self, gen_out_tokens, n_start, n_emitted, chunk_py: int):
        """Shadow one generator chunk; returns ``(new_n, proxy monitor)``
        for the generator executor's ``retract``.  ``gen_out_tokens`` is the
        post-chunk emitted-token buffer; ``n_start``/``n_emitted`` the
        per-row host copies the engine took around the chunk dispatch."""
        self.state = self.ex.observe_chunk(
            self.params, self.state, gen_out_tokens, n_start, n_emitted,
            chunk_py,
        )
        return self.state.n_reasoning, self.state.monitor

    # ------------------------------------------------------ harvest / admit
    def free_row(self, slot: int) -> None:
        if self.paged:
            self.alloc.free_row(slot)

    def can_admit(self, prompt_tokens: int) -> bool:
        """Paged-pool admission gate — defers (stays queued), never raises."""
        return (not self.paged) or self.alloc.can_admit(prompt_tokens)

    def check_capacity(self, when: str) -> None:
        """Ring-wrap guard for an explicitly undersized proxy ring (the
        proxy's ``cur`` never outruns the generator's, so with inherited
        capacity the scheduler's own guard always fires first)."""
        if self.paged:
            return
        used = int(self.tel.fetch("proxy_cur", self.state.cache["cur"]))
        if used + self.budget > self.capacity:
            raise RuntimeError(
                f"proxy cache capacity {self.capacity} cannot hold {when}: "
                f"{used} slots committed + up to {self.budget} decode steps "
                f"would wrap the proxy ring. Raise ProxyConfig.capacity "
                f"(or leave it None to inherit EngineConfig.capacity)."
            )

    def admit(self, slot: int, prompt_np, prompt_len: int, S: int) -> None:
        """Prefill + merge an admitted prompt into proxy ``slot`` — the
        lock-step mirror of the generator's admit/admit_paged dispatch."""
        one = self._fresh(jnp.asarray(prompt_np[None]),
                          jnp.asarray([prompt_len]),
                          self._C_pre if self.paged else self.capacity)
        if self.paged:
            cur = int(self.tel.fetch("proxy_cur", self.state.cache["cur"]))
            row_table = self.alloc.admit_row(slot, S, cur)
            self.state = self.ex.admit_paged(self.state, one, slot,
                                             row_table)
        else:
            self.state = self.ex.admit(self.state, one, slot)
