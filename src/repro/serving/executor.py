"""Executor layer: every jitted device program of the serving stack.

This is the bottom of the three-layer serving architecture
(``request.py`` -> ``scheduler.py`` -> ``executor.py``; contracts and
diagram in docs/architecture.md):

  * ``request``   — per-request lifecycle state machine (host metadata),
  * ``scheduler`` — slot allocation + admission policy (pure host Python),
  * ``executor``  — the device programs those layers drive.

Contract: ALL jax lives at or below this layer (the request/scheduler
layers are host-only), and every program that mutates decode state follows
the consumes-state donation rule spelled out below — a caller that passes
a state to a donating program must treat that state as dead.

The executor owns the canonical single-token EAT step (``make_eat_step`` —
moved here from ``launch.serve_step`` so exactly one serve-step definition
exists in the tree) and builds every program the engine dispatches:

  prefill        prompt -> cache fill            (cache arg DONATED)
  decode_chunk   lax.while_loop of EAT steps     (ServeState DONATED)
  decode_chunk_snapshot  the chunk + a packed host-facing snapshot of the
                 harvest scalars in FRESH buffers (ServeState DONATED) —
                 the overlap pipeline's variant: the state is donated into
                 the next dispatch before the host reads anything, so the
                 host must never hold a reference into the state itself
  decode_step    one unmonitored step            (per-token baseline, no
                                                  donation: benchmarks call
                                                  it repeatedly on one state)
  probe          non-committing EAT evaluation   (never donated — the cache
                                                  must survive the probe)
  admit          slot recycling row-merge        (resident state DONATED)
  admit_paged    row-merge through a page table  (resident state DONATED)
  pack_paged     dense prefill -> page pool      (paged cache DONATED)
  rollout        forced answer generation        (NOT donated: callers keep
                                                  decoding from / re-rolling
                                                  the same live cache)
  retract        proxy-mode chunk reconciliation (ServeState DONATED)
  retract_lagged overlap-mode reconciliation one chunk late: only proxy-
                 stopped rows rewind; the rest pass through untouched
                                                  (ServeState DONATED)

The black-box (``monitor="proxy"``) tier adds a second program store:
``ProxyExecutor`` drives a *different* model that shadows the generator's
emitted token chunks (``observe_chunk`` — forced-input decode + the same
probe/monitor transition the self-EAT step runs) and owns its own KV cache,
page pool, and mesh context.  In proxy mode the generator executor builds
NO probe program and no monitored chunk — the black-box contract: no
generator logits feed the exit decision (audited by key inspection on
``_programs`` in tests/test_proxy_serve.py).

Every jitted function is named after what it does (``decode_chunk``,
``rollout``, ``observe_chunk`` ...), so its lowered module — and the
device trace's ``XLA Modules`` line — reads ``jit_<name>``, one name per
program family (tests/test_audit.py).

Programs are built once per ``(batch, variant)`` and cached.  With a mesh
in ``model.ctx`` (threaded from ``launch.mesh``) every program is jitted
with explicit ``in_shardings``/``out_shardings`` derived from
``sharding.partition.serve_state_pspecs`` / ``serving.cache.cache_pspecs``
/ ``param_pspecs`` — batch rows ride the data axis, heads/ffn ride the
model axis — so ``reason()``/``serve()`` run data- + tensor-parallel with
no host-side resharding between dispatches.  ``launch.dryrun`` lowers
``build_serve_step_program`` from this module, so the program the roofline
analyses cost out is the program the engine dispatches.

Donation contract: ``decode_chunk`` and ``admit`` consume the ServeState
they are passed (the KV cache is updated in place instead of being
re-allocated every chunk — ``input_output_alias`` in the compiled HLO,
asserted by ``tests/test_executor.py``).  Callers must treat a state they
hand to those programs as dead and continue from the returned state.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.eat import ProbeSpec, eval_eat
from repro.core.monitor import MonitorState, ReasoningMonitor
from repro.core.stopping import EATStopper
from repro.models.model import Model
from repro.serving.cache import (
    cache_pspecs,
    freeze_inactive_rows,
    merge_cache_row,
    merge_paged_row,
    pack_paged_cache,
)
from repro.serving.sampler import SamplerConfig, logprob_of, sample
from repro.sharding.partition import (
    param_pspecs,
    proxy_stream_pspecs,
    serve_snapshot_pspecs,
    serve_state_pspecs,
)


# --------------------------------------------------------------------------
# The program-store contract, exported for the static analyzer
# (src/repro/analysis/, docs/analysis.md).  tools/audit enumerates every
# builder across the full key matrix and checks these against the compiled
# artifacts — keep them in sync with the builders below.
# --------------------------------------------------------------------------

#: Every program family a serving run can dispatch, by key[0].
PROGRAM_FAMILIES = ("chunk", "decode", "prefill", "probe", "admit", "pack",
                    "retract", "rollout", "shadow")

#: family -> donated argument index (None = deliberately functional).  The
#: donation audit asserts input/output aliasing in the compiled artifact for
#: every donating program and its ABSENCE for the functional ones ("chunk"
#: keys carry an explicit donate flag at key[3]; the audit honours it).
DONATION_CONTRACT = {
    "chunk": 1,       # ServeState
    "decode": None,   # benchmarks re-time it against one fixed state
    "prefill": 4,     # the freshly allocated cache
    "probe": None,    # the probe must not consume the live cache
    "admit": 0,       # the resident batch state (ring AND paged variants)
    "pack": 0,        # the paged template
    "retract": 0,     # ServeState
    "rollout": None,  # functional read of a live cache
    "shadow": 1,      # the proxy's ServeState
}

#: Families waived from the program-key completeness lint, with the reason.
#: A waiver is a claim that the un-keyed inputs cannot silently change the
#: traced program: prefill always runs over a dense cache (paged serves
#: prefill dense, then ``pack_paged`` scatters), so the cache kind / decode
#: attention impl never reach its graph, and a pytree-structure change in
#: the cache argument retraces (or fails loudly on a mesh) rather than
#: serving a stale program.
KEY_EXEMPT = {
    "prefill": "dense prompt prefill; cache kind/attn impl never reach the "
               "traced graph, structure changes retrace",
}


def cache_kind(cache: dict) -> str:
    """'paged' when the cache routes K/V through a page table, else 'ring'.
    Program-cache keys include this: the two kinds have different pytree
    structures, so their jitted programs (and mesh in/out shardings) are
    built separately.  Executor keys additionally carry the decode-attention
    impl (``Executor._kind``): a "paged+xla" program reads K/V through the
    compacted page list, a plain "paged" one gathers — different traced
    graphs even over the same pytree structure."""
    return "paged" if "page_table" in cache else "ring"


def mesh_ns(ctx, spec: P) -> NamedSharding:
    """One PartitionSpec -> NamedSharding on the ctx mesh."""
    return NamedSharding(ctx.mesh, spec)


def mesh_shardings(ctx, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree on the ctx mesh — the
    single spec->sharding hop for every executor program (the Executor
    methods and the dry-run's ``build_serve_step_program`` both route
    through here, so the lowered and the dispatched programs cannot drift
    in how specs become shardings)."""
    return jax.tree_util.tree_map(lambda s: mesh_ns(ctx, s), spec_tree)


def positions_for(cfg, pos1d):
    """Model-facing positions from 1-D positions: mrope configs broadcast
    to the 3-section layout, everyone else passes through.  THE single
    definition — prefill (engine.start), the EAT step, and rollouts must
    agree or cached and probed positions silently diverge."""
    if cfg.mrope_sections:
        return jnp.broadcast_to(pos1d[..., None], pos1d.shape + (3,))
    return pos1d


class ServeState(NamedTuple):
    """Device-resident batched decode state (one row per slot)."""

    cache: dict
    rng: jax.Array
    active: jax.Array          # (B,) still reasoning
    next_pos: jax.Array        # (B,) next token position (left-pad aware)
    last_token: jax.Array      # (B,)
    n_reasoning: jax.Array     # (B,) reasoning tokens generated
    monitor: MonitorState
    ended_think: jax.Array     # (B,) emitted </think> naturally
    out_tokens: jax.Array      # (B, T_buf) generated reasoning tokens
    out_len: jax.Array         # (B,)


#: Row order of the packed (len(SNAP_ROWS), B) int32 block of a chunk
#: snapshot (``Executor.decode_chunk_snapshot``) — the overlap pipeline
#: indexes the host copy by position in this tuple.  ``cur`` is the cache's
#: shared ring pointer broadcast per row so the whole int snapshot is one
#: fused buffer.
SNAP_ROWS = ("active", "n_reasoning", "out_len", "ended_think", "stop_flag",
             "n_evals", "cur")


# --------------------------------------------------------------------------
# The canonical single-token EAT-monitored decode step — ONE program, every
# driver: the engine's device-resident chunks scan it, the dry-runs lower it.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeStepConfig:
    window: int = 0
    probe: ProbeSpec = ProbeSpec((1, 6))        # </think> + "final answer:" prefix
    stopper: EATStopper = EATStopper(alpha=0.2, delta=1e-3)
    sampler: SamplerConfig = SamplerConfig()
    with_probe: bool = True
    # §Perf: fuse the probe into the decode forward (one weight pass per
    # step instead of two; see Model.decode_and_probe)
    fused_probe: bool = False


def serve_monitor(scfg: ServeStepConfig) -> ReasoningMonitor:
    """The dry-run's evaluation schedule: probe every token, no warmup —
    the most expensive (upper-bound) configuration of the monitored step."""
    return ReasoningMonitor(stopper=scfg.stopper, probe=scfg.probe,
                            schedule="every_n", every_n=1, min_evals=0)


def make_eat_step(
    model: Model,
    monitor: ReasoningMonitor | None,
    sampler: SamplerConfig,
    *,
    window: int | None = None,
    probe_cond: bool = True,
    fused_probe: bool = False,
):
    """Build ``step(params, cache, token, pos1d, mon, active, rng)``
    -> ``(next_token, cache, mon, stop, rng)``.

    token/pos1d: (B,1); mon: MonitorState; active: (B,) bool.  ``stop`` is
    the latched per-sequence exit mask (``mon.stop_flag``).

    ``probe_cond=True`` wraps the probe+update in ``lax.cond`` on
    ``(due & active).any()`` so chunks where no sequence hits an evaluation
    point pay zero probe FLOPs (the engine's sparse-schedule case);
    ``probe_cond=False`` probes unconditionally (the dry-run's every-token
    schedule, where the cond would always take the probe branch anyway).
    """
    cfg = model.cfg

    def _positions(pos1d):
        return positions_for(cfg, pos1d)

    def step(params, cache, token, pos1d, mon: MonitorState, active, rng):
        if monitor is not None and fused_probe:
            B = token.shape[0]
            m = len(monitor.probe)
            probe_toks = jnp.broadcast_to(
                jnp.asarray(monitor.probe.tokens, jnp.int32), (B, m)
            )
            pos_all = pos1d[:, :1] + jnp.arange(1 + m, dtype=jnp.int32)[None]
            logits, eat, cache = model.decode_and_probe(
                params, token, _positions(pos_all), pos_all, cache, probe_toks,
                window=window,
            )
            rng, sub = jax.random.split(rng)
            nxt = sample(sub, logits[:, -1], cfg.vocab, sampler)
            mon = monitor.update(mon, eat, monitor.due(mon, nxt), active)
            return nxt, cache, mon, mon.stop_flag, rng

        logits, cache = model.decode_step(
            params, token, _positions(pos1d), pos1d, cache, window=window
        )
        rng, sub = jax.random.split(rng)
        nxt = sample(sub, logits[:, -1], cfg.vocab, sampler)
        if monitor is None:
            return nxt, cache, mon, jnp.zeros(nxt.shape, bool), rng

        next_pos = pos1d[:, -1] + 1
        eat_fn = lambda: eval_eat(model, params, cache, monitor.probe, next_pos)  # noqa: E731
        mon = monitor.observe(mon, eat_fn, nxt, active, lazy=probe_cond)
        return nxt, cache, mon, mon.stop_flag, rng

    return step


def make_shadow_step(model: Model, monitor: ReasoningMonitor):
    """Build the proxy-side forced-token EAT step
    ``step(params, cache, tok_in, tok_out, next_pos, mon, valid)``
    -> ``(cache, mon, next_pos)``.

    The mirror of ``make_eat_step`` for a model that does not choose the
    tokens: ``tok_in`` (B,1) is the token the GENERATOR fed at this step
    (committed into the proxy cache), ``tok_out`` (B,) the token the
    generator emitted (the monitor's due-check input), ``valid`` (B,) the
    mask of rows still consuming the stream.  Invalid rows write at
    position -1 (masked) and their monitor state freezes — exactly the
    inactive-row handling of the self-EAT step, so a proxy running the
    generator's own params reproduces the self-EAT EMA trajectory
    bit-for-bit (tests/test_proxy_serve.py).
    """
    cfg = model.cfg

    def step(params, cache, tok_in, tok_out, next_pos, mon: MonitorState,
             valid):
        pos1d = jnp.where(valid, next_pos, -1)[:, None]
        _, new_cache = model.decode_step(
            params, tok_in, positions_for(cfg, pos1d), pos1d, cache
        )
        if cfg.arch_type in ("ssm", "hybrid"):
            new_cache = freeze_inactive_rows(new_cache, cache, valid)
        new_pos = next_pos + valid.astype(jnp.int32)
        eat_fn = lambda: eval_eat(model, params, new_cache, monitor.probe, new_pos)  # noqa: E731
        mon = monitor.observe(mon, eat_fn, tok_out, valid, lazy=True)
        return new_cache, mon, new_pos

    return step


def build_serve_step_program(model: Model, scfg: ServeStepConfig,
                             cache_struct, params_struct):
    """The decode-shape dry-run program: ONE every-token EAT step, jitted
    with explicit shardings and the cache donated — the exact program shape
    ``launch.dryrun`` lowers and costs out.

    Returns ``(jitted_fn, mon_struct)``; call as
    ``jitted_fn(params, cache, token, pos1d, mon, rng)``.
    """
    ctx, cfg = model.ctx, model.cfg
    monitor = serve_monitor(scfg) if scfg.with_probe else None
    step = make_eat_step(
        model, monitor, scfg.sampler, window=scfg.window,
        probe_cond=False, fused_probe=scfg.fused_probe,
    )

    def serve_step(params, cache, token, pos1d, mon: MonitorState, rng):
        """token/pos1d: (B,1).  Returns (next_token, cache, mon, stop, rng)."""
        active = jnp.ones(token.shape[:1], bool)
        return step(params, cache, token, pos1d, mon, active, rng)

    B = cache_struct["pos"].shape[0]
    mon_struct = jax.eval_shape(lambda: serve_monitor(scfg).init(B))
    if ctx.mesh is None:
        return jax.jit(serve_step, donate_argnums=1), mon_struct

    b = ctx.batch_entry_for(B)
    in_sh = (
        mesh_shardings(ctx, param_pspecs(params_struct, cfg, ctx)),
        mesh_shardings(ctx, cache_pspecs(cfg, ctx, cache_struct)),
        mesh_ns(ctx, P(b, None)),
        mesh_ns(ctx, P(b, None)),
        jax.tree_util.tree_map(lambda _: mesh_ns(ctx, P(b)), mon_struct),
        mesh_ns(ctx, P()),
    )
    return jax.jit(serve_step, in_shardings=in_sh, donate_argnums=1), mon_struct


def init_params(model: Model, key) -> dict:
    """Random parameters, made where they live: on a mesh, ``model.init``
    is jitted with the ``param_pspecs`` out-shardings, so each device
    computes only its own shards and no parameter ever exists whole on one
    device (a model that only several chips can hold is initialised on
    them).  jax's partitionable threefry makes the values independent of
    the mesh."""
    ctx = model.ctx
    if ctx.mesh is None:
        return model.init(key)
    struct = jax.eval_shape(model.init, key)
    out_sh = mesh_shardings(ctx, param_pspecs(struct, model.cfg, ctx))
    return jax.jit(model.init, out_shardings=out_sh)(key)


def build_stream_monitor_programs(model: Model, probe: ProbeSpec):
    """Jitted programs for the host-streaming ``ProxyMonitor``
    (serving/proxy.py): ``(consume, probe_fn, prefill)``.

    ``consume(params, cache, tokens, next_pos)`` prefills an arriving chunk
    into the monitor's cache; ``probe_fn(params, cache, next_pos)`` is the
    non-committing EAT evaluation; ``prefill`` is the plain prompt prefill
    (re-traced per prompt shape by jit's signature cache).  Built here so
    proxy.py stays a host-orchestration layer — the executor module is the
    only place in ``serving/`` that constructs jitted programs (the
    layering contract checked by tools/audit)."""

    def _positions(pos1d):
        return positions_for(model.cfg, pos1d)

    @jax.jit
    def stream_consume(params, cache, tokens, next_pos):
        B, m = tokens.shape
        pos1d = next_pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None]
        _, cache = model.prefill(params, tokens, _positions(pos1d), pos1d,
                                 cache)
        return cache, next_pos + m

    @jax.jit
    def stream_probe(params, cache, next_pos):
        return eval_eat(model, params, cache, probe, next_pos)

    @jax.jit
    def stream_prefill(params, prompts, positions, pos1d, cache):
        return model.prefill(params, prompts, positions, pos1d, cache)

    return stream_consume, stream_probe, stream_prefill


# --------------------------------------------------------------------------
# Executor: the engine-facing program store
# --------------------------------------------------------------------------

class Executor:
    """Builds and caches every jitted program ``ReasoningEngine`` dispatches.

    One instance per ``(model, EngineConfig, monitor)``; programs are built
    lazily per batch size (shardings depend on whether the batch divides the
    data axis) and cached for the executor's lifetime.
    """

    def __init__(self, model: Model, params, ecfg, monitor: ReasoningMonitor):
        self.model = model
        self.ecfg = ecfg
        self.monitor = monitor
        self.ctx = model.ctx
        self.cfg = model.cfg
        self._programs: dict = {}
        self._param_sh = None
        if self.ctx.mesh is not None:
            self._param_sh = self._sh(param_pspecs(params, self.cfg, self.ctx))
        self._step_mon = make_eat_step(model, monitor, ecfg.sampler,
                                       probe_cond=True)
        self._step_plain = make_eat_step(model, None, ecfg.sampler)

    def _kind(self, cache: dict) -> str:
        """``cache_kind`` + the model's decode-attention impl — the program
        key component the ``--attn-impl`` knob threads through, so a
        page-native program can never be served from a gather key (or vice
        versa) even if two executors share a program store in a test."""
        kind = cache_kind(cache)
        impl = self.model.paged_attn_impl
        return kind if impl == "gather" else f"{kind}+{impl}"

    # ---------------------------------------------------------- shardings
    def _ns(self, spec: P):
        return mesh_ns(self.ctx, spec)

    def _sh(self, spec_tree):
        return mesh_shardings(self.ctx, spec_tree)

    def _batch_entry(self, B: int):
        return self.ctx.batch_entry_for(B)

    def _state_sh(self, state: ServeState):
        return self._sh(serve_state_pspecs(self.cfg, self.ctx, state))

    def shard_params(self, params):
        """Place the parameter pytree on the mesh once, so per-dispatch
        ``in_shardings`` never trigger a host->device re-transfer."""
        if self.ctx.mesh is None:
            return params
        return jax.device_put(params, self._param_sh)

    # ---------------------------------------------------------- programs
    def _advance(self, params, state: ServeState, budget, step_fn) -> ServeState:
        """One monitored decode step + engine bookkeeping, all masked."""
        cfg, ecfg = self.cfg, self.ecfg
        tok = state.last_token[:, None]
        # inactive rows still ride through the batched step, but their
        # KV write must be invisible: pos=-1 keeps the duplicate-position
        # entry out of every later attention mask (q_pos >= kv_pos >= 0)
        pos1d = jnp.where(state.active, state.next_pos, -1)[:, None]
        nxt, cache, mon, stop, rng = step_fn(
            params, state.cache, tok, pos1d, state.monitor,
            state.active, state.rng,
        )
        if cfg.arch_type in ("ssm", "hybrid"):
            cache = freeze_inactive_rows(cache, state.cache, state.active)
        nxt = jnp.where(state.active, nxt, ecfg.pad_id)
        ended = state.ended_think | (state.active & (nxt == ecfg.end_think_id))
        out_tokens = state.out_tokens.at[
            jnp.arange(nxt.shape[0]), state.out_len
        ].set(nxt)
        inc = state.active.astype(jnp.int32)
        n_reasoning = state.n_reasoning + inc
        over = n_reasoning >= budget
        return ServeState(
            cache=cache,
            rng=rng,
            active=state.active & ~stop & ~ended & ~over,
            next_pos=state.next_pos + inc,
            last_token=nxt,
            n_reasoning=n_reasoning,
            monitor=mon,
            ended_think=ended,
            out_tokens=out_tokens,
            out_len=state.out_len + inc,
        )

    def chunk_program(self, state: ServeState, use_monitor: bool,
                      donate: bool = True):
        # ``donate=False`` exists ONLY for the donation audit
        # (tests/test_executor.py), which A/Bs the compiled memory stats of
        # the same program with and without the in-place cache alias.
        B = int(state.active.shape[0])
        key = ("chunk", B, use_monitor, donate, self._kind(state.cache))
        if key not in self._programs:
            step_fn = self._step_mon if use_monitor else self._step_plain

            def decode_chunk(params, st: ServeState, budget, chunk_len):
                def cond(carry):
                    i, s = carry
                    return (i < chunk_len) & s.active.any()

                def body(carry):
                    i, s = carry
                    return i + 1, self._advance(params, s, budget, step_fn)

                _, st = jax.lax.while_loop(
                    cond, body, (jnp.zeros((), jnp.int32), st)
                )
                return st

            dn = (1,) if donate else ()
            if self.ctx.mesh is None:
                jitted = jax.jit(decode_chunk, donate_argnums=dn)
            else:
                ssh = self._state_sh(state)
                jitted = jax.jit(
                    decode_chunk,
                    in_shardings=(self._param_sh, ssh, self._ns(P()),
                                  self._ns(P())),
                    out_shardings=ssh,
                    donate_argnums=dn,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def decode_chunk(self, params, state: ServeState, budget, chunk_len,
                     *, use_monitor: bool = True) -> ServeState:
        """Advance up to ``chunk_len`` monitored tokens in ONE dispatch
        (``lax.while_loop`` over the EAT step).  DONATES ``state``."""
        return self.chunk_program(state, use_monitor)(
            params, state, budget, chunk_len
        )

    # ----------------------------------------------- overlap-mode programs
    #
    # The async pipeline (serving/pipeline.py) dispatches chunk N+1 before
    # the host has read anything of chunk N, and the chunk donates its
    # ServeState into that next dispatch — so a host reference into any
    # state buffer would be invalidated mid-read.  Every host-facing value
    # therefore comes back as a SEPARATE snapshot: ``_snapshot_of`` routes
    # each field through stack/concatenate, whose output shapes differ from
    # every state field, so XLA can never alias a snapshot buffer to an
    # output that a later dispatch donates away.

    def _snapshot_of(self, st: ServeState) -> dict:
        B = st.active.shape[0]
        cur = jnp.broadcast_to(
            jnp.asarray(st.cache["cur"], jnp.int32).reshape(()), (B,))
        ints = jnp.stack([
            st.active.astype(jnp.int32),
            st.n_reasoning.astype(jnp.int32),
            st.out_len.astype(jnp.int32),
            st.ended_think.astype(jnp.int32),
            st.monitor.stop_flag.astype(jnp.int32),
            st.monitor.n_evals.astype(jnp.int32),
            cur,
        ], 0)
        var = self.monitor.stopper.debiased_var(st.monitor.stop_state)
        toks = jnp.concatenate([st.out_tokens, st.out_len[:, None]], 1)
        return {"ints": ints, "var": var.astype(jnp.float32), "tokens": toks}

    def chunk_snapshot_program(self, state: ServeState, use_monitor: bool):
        B = int(state.active.shape[0])
        key = ("chunk", B, use_monitor, True, self._kind(state.cache), "snap")
        if key not in self._programs:
            step_fn = self._step_mon if use_monitor else self._step_plain

            def decode_chunk_snapshot(params, st: ServeState, budget,
                                      chunk_len):
                def cond(carry):
                    i, s = carry
                    return (i < chunk_len) & s.active.any()

                def body(carry):
                    i, s = carry
                    return i + 1, self._advance(params, s, budget, step_fn)

                _, st = jax.lax.while_loop(
                    cond, body, (jnp.zeros((), jnp.int32), st)
                )
                return st, self._snapshot_of(st)

            if self.ctx.mesh is None:
                jitted = jax.jit(decode_chunk_snapshot, donate_argnums=(1,))
            else:
                ssh = self._state_sh(state)
                jitted = jax.jit(
                    decode_chunk_snapshot,
                    in_shardings=(self._param_sh, ssh, self._ns(P()),
                                  self._ns(P())),
                    out_shardings=(ssh,
                                   self._sh(serve_snapshot_pspecs(self.ctx,
                                                                  B))),
                    donate_argnums=(1,),
                )
            self._programs[key] = jitted
        return self._programs[key]

    def decode_chunk_snapshot(self, params, state: ServeState, budget,
                              chunk_len, *, use_monitor: bool = True
                              ) -> tuple[ServeState, dict]:
        """``decode_chunk`` plus the packed harvest snapshot the overlap
        pipeline reads one boundary late: ``(state, {ints, var, tokens})``
        where ``ints`` is the (len(SNAP_ROWS), B) int32 block (row order
        ``SNAP_ROWS``), ``var`` the debiased EMA variance the traces record,
        and ``tokens`` the (B, T+1) out_tokens copy (last column = out_len).
        DONATES ``state``; the snapshot buffers are fresh and stay valid
        after the state is donated into the next dispatch."""
        return self.chunk_snapshot_program(state, use_monitor)(
            params, state, budget, chunk_len
        )

    def decode_program(self, state: ServeState):
        key = ("decode", int(state.active.shape[0]), self._kind(state.cache))
        if key not in self._programs:
            def decode_step(params, st: ServeState):
                no_budget = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
                return self._advance(params, st, no_budget, self._step_plain)

            if self.ctx.mesh is None:
                jitted = jax.jit(decode_step)
            else:
                ssh = self._state_sh(state)
                jitted = jax.jit(decode_step,
                                 in_shardings=(self._param_sh, ssh),
                                 out_shardings=ssh)
            self._programs[key] = jitted
        return self._programs[key]

    def decode_step(self, params, state: ServeState) -> ServeState:
        """One unmonitored decode step — ``_advance`` with no budget.  The
        per-token baseline for ``benchmarks/engine_throughput.py`` and unit
        tests (so the two paths can never diverge).  No donation: the
        benchmarks re-time it against one fixed state."""
        return self.decode_program(state)(params, state)

    def prefill_program(self, cache, B: int, has_frames: bool = False,
                        has_image: bool = False):
        key = ("prefill", B, has_frames, has_image)
        if key not in self._programs:
            model = self.model

            if has_frames:
                def prefill(params, tokens, positions, pos1d, cache, frames):
                    return model.prefill(params, tokens, positions, pos1d,
                                         cache, frames=frames)
            elif has_image:
                def prefill(params, tokens, positions, pos1d, cache,
                            image_embeds):
                    return model.prefill(params, tokens, positions, pos1d,
                                         cache, image_embeds=image_embeds)
            else:
                def prefill(params, tokens, positions, pos1d, cache):
                    return model.prefill(params, tokens, positions, pos1d,
                                         cache)

            if self.ctx.mesh is None:
                jitted = jax.jit(prefill, donate_argnums=4)
            else:
                b = self._batch_entry(B)
                pos_spec = (P(b, None, None) if self.cfg.mrope_sections
                            else P(b, None))
                in_sh = [
                    self._param_sh,
                    self._ns(P(b, None)),
                    self._ns(pos_spec),
                    self._ns(P(b, None)),
                    self._sh(cache_pspecs(self.cfg, self.ctx, cache)),
                ]
                if has_frames or has_image:
                    in_sh.append(self._ns(P(b, None, None)))
                jitted = jax.jit(prefill, in_shardings=tuple(in_sh),
                                 donate_argnums=4)
            self._programs[key] = jitted
        return self._programs[key]

    def prefill(self, params, tokens, positions, pos1d, cache, *,
                frames=None, image_embeds=None):
        """Prompt prefill; returns (hidden, cache).  DONATES ``cache`` (the
        engine always hands it a freshly allocated one)."""
        prog = self.prefill_program(cache, int(tokens.shape[0]),
                                    frames is not None,
                                    image_embeds is not None)
        extras = [x for x in (frames, image_embeds) if x is not None]
        return prog(params, tokens, positions, pos1d, cache, *extras)

    def probe_program(self, cache, B: int):
        key = ("probe", B, self._kind(cache))
        if key not in self._programs:
            model, monitor = self.model, self.monitor

            def probe(params, cache, next_pos):
                return eval_eat(model, params, cache, monitor.probe, next_pos)

            if self.ctx.mesh is None:
                jitted = jax.jit(probe)
            else:
                b = self._batch_entry(B)
                jitted = jax.jit(probe, in_shardings=(
                    self._param_sh,
                    self._sh(cache_pspecs(self.cfg, self.ctx, cache)),
                    self._ns(P(b)),
                ))
            self._programs[key] = jitted
        return self._programs[key]

    def probe(self, params, cache, next_pos):
        """Non-committing EAT probe over the live cache.  Never donated —
        the whole point is that the cache survives the evaluation."""
        return self.probe_program(cache, int(next_pos.shape[0]))(
            params, cache, next_pos
        )

    def admit_program(self, state: ServeState, one: ServeState):
        key = ("admit", int(state.active.shape[0]))
        if key not in self._programs:
            def admit(state: ServeState, one: ServeState, slot
                      ) -> ServeState:
                def put(big, small):
                    return big.at[slot].set(small[0])

                return ServeState(
                    cache=merge_cache_row(state.cache, one.cache, slot),
                    rng=state.rng,
                    active=put(state.active, one.active),
                    next_pos=put(state.next_pos, one.next_pos),
                    last_token=put(state.last_token, one.last_token),
                    n_reasoning=put(state.n_reasoning, one.n_reasoning),
                    monitor=jax.tree_util.tree_map(put, state.monitor,
                                                   one.monitor),
                    ended_think=put(state.ended_think, one.ended_think),
                    out_tokens=put(state.out_tokens, one.out_tokens),
                    out_len=put(state.out_len, one.out_len),
                )

            if self.ctx.mesh is None:
                jitted = jax.jit(admit, donate_argnums=0)
            else:
                ssh = self._state_sh(state)
                jitted = jax.jit(
                    admit,
                    in_shardings=(ssh, self._state_sh(one), self._ns(P())),
                    out_shardings=ssh,
                    donate_argnums=0,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def admit(self, state: ServeState, one: ServeState, slot) -> ServeState:
        """Recycle a batch slot: overwrite row ``slot`` of every per-
        sequence array (and the cache row, see ``merge_cache_row``) with
        the freshly-prefilled single-sequence state ``one``.  One fused
        dispatch; ``slot`` is traced so admissions into different slots
        share the compilation.  DONATES ``state`` (the resident batch)."""
        return self.admit_program(state, one)(
            state, one, jnp.asarray(slot, jnp.int32)
        )

    # ------------------------------------------------------ paged programs
    def pack_paged_program(self, paged_cache: dict, dense_cache: dict):
        B = int(paged_cache["pos"].shape[0])
        C_pre = int(dense_cache["pos"].shape[1])
        key = ("pack", B, C_pre)
        if key not in self._programs:
            def pack_paged(paged_cache, dense_cache, table):
                return pack_paged_cache(paged_cache, dense_cache, table)

            if self.ctx.mesh is None:
                jitted = jax.jit(pack_paged, donate_argnums=0)
            else:
                jitted = jax.jit(
                    pack_paged,
                    in_shardings=(
                        self._sh(cache_pspecs(self.cfg, self.ctx, paged_cache)),
                        self._sh(cache_pspecs(self.cfg, self.ctx, dense_cache)),
                        self._ns(P(None, None)),
                    ),
                    out_shardings=self._sh(
                        cache_pspecs(self.cfg, self.ctx, paged_cache)),
                    donate_argnums=0,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def pack_paged(self, paged_cache: dict, dense_cache: dict, table) -> dict:
        """Scatter a freshly prefilled dense cache into an empty paged
        cache (serve()-start conversion).  DONATES ``paged_cache`` — the
        pools are updated in place, same contract as every other
        cache-consuming program."""
        return self.pack_paged_program(paged_cache, dense_cache)(
            paged_cache, dense_cache, jnp.asarray(table, jnp.int32)
        )

    def admit_paged_program(self, state: ServeState, one: ServeState):
        key = ("admit", int(state.active.shape[0]), "paged",
               int(one.cache["pos"].shape[1]))
        if key not in self._programs:
            def admit_paged(state: ServeState, one: ServeState, slot,
                            row_table) -> ServeState:
                def put(big, small):
                    return big.at[slot].set(small[0])

                return ServeState(
                    cache=merge_paged_row(state.cache, one.cache, slot,
                                          row_table),
                    rng=state.rng,
                    active=put(state.active, one.active),
                    next_pos=put(state.next_pos, one.next_pos),
                    last_token=put(state.last_token, one.last_token),
                    n_reasoning=put(state.n_reasoning, one.n_reasoning),
                    monitor=jax.tree_util.tree_map(put, state.monitor,
                                                   one.monitor),
                    ended_think=put(state.ended_think, one.ended_think),
                    out_tokens=put(state.out_tokens, one.out_tokens),
                    out_len=put(state.out_len, one.out_len),
                )

            if self.ctx.mesh is None:
                jitted = jax.jit(admit_paged, donate_argnums=0)
            else:
                ssh = self._state_sh(state)
                jitted = jax.jit(
                    admit_paged,
                    in_shardings=(ssh, self._state_sh(one), self._ns(P()),
                                  self._ns(P(None))),
                    out_shardings=ssh,
                    donate_argnums=0,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def admit_paged(self, state: ServeState, one: ServeState, slot,
                    row_table) -> ServeState:
        """Paged-cache slot recycling: like ``admit``, but the cache merge
        routes the admitted prompt K/V through ``row_table`` (the
        allocator's fresh page mapping for the slot — prompt blocks plus
        one decode page).  ``slot`` and ``row_table`` are traced, so
        admissions into different slots share the compilation.  DONATES
        ``state``."""
        return self.admit_paged_program(state, one)(
            state, one, jnp.asarray(slot, jnp.int32),
            jnp.asarray(row_table, jnp.int32)
        )

    def put_page_table(self, state: ServeState, table,
                       blocks: tuple | None = None) -> ServeState:
        """Swap the host allocator's page table — and, in page-native mode,
        its compacted mapped-page buckets ``(pages, logical, counts)`` —
        into the state (replicated on the mesh).  Host->device upload of a
        few KB of int32 — called once per chunk boundary, never inside a
        jitted program.  A bucket-width change simply retraces the next
        dispatch (the NamedShardings are shape-agnostic)."""
        from repro.serving.cache import blocks_arrays

        def rep(x, spec):
            dev = jnp.asarray(x, jnp.int32)
            if self.ctx.mesh is not None:
                dev = jax.device_put(dev, self._ns(spec))
            return dev

        cache = dict(state.cache)
        cache["page_table"] = rep(table, P(None, None))
        if blocks is not None:
            pages, logical, counts = blocks
            dev = blocks_arrays(pages, logical, counts)
            dev = {"pages": rep(dev["pages"], P(None, None)),
                   "logical": rep(dev["logical"], P(None, None)),
                   "count": rep(dev["count"], P(None))}
            cache["blocks"] = dev
        return state._replace(cache=cache)

    def ensure_chunk_pages(self, alloc, state: ServeState, slots, span: int,
                           *, tail: int = 0, budget: int | None = None,
                           cur: int | None = None, n_reasoning=None,
                           slack: int = 0) -> ServeState:
        """Map (and push) pages covering the next ``span`` logical slots
        for every slot in ``slots`` before a writing dispatch — THE page-
        sizing rule for a chunk, shared by the generator loop and the
        proxy tier's shadow decode.  With ``budget`` the span is clamped
        per row to the tokens it can still emit plus the probe ``tail``
        (a row never decodes past its budget, so pages past it would be
        reserved-but-never-written — enough waste to break the documented
        pool sizing rule when the chunk exceeds the remaining budget).
        The table upload is skipped while the mapping is unchanged
        (steady decode inside a block).

        ``cur`` / ``n_reasoning`` override the host reads of the state's
        ring pointer and per-row counts: the overlap pipeline passes its
        mirrors from the last retired fence so mapping never blocks on an
        in-flight chunk.  Mirrors lag the device by up to one dispatched
        chunk, so the pipeline also passes ``slack`` (extra leading slots,
        mapped on top of the per-row clamp) to cover the writes of the
        not-yet-harvested dispatch; pessimistic by at most one chunk of
        pages per row."""
        cur0 = int(state.cache["cur"]) if cur is None else int(cur)
        n_r = None
        if budget is not None:
            n_r = (np.asarray(state.n_reasoning) if n_reasoning is None
                   else np.asarray(n_reasoning))
        for s in slots:
            sp = span
            if n_r is not None:
                left = max(1, budget - int(n_r[s]))
                sp = min(span, left + tail)
            alloc.ensure(s, cur0, cur0 + slack + sp)
        if not alloc.dirty:
            return state
        # page-native caches carry the compacted read index: re-derive it
        # from the (just-mutated) table so the two can never drift
        blocks = (alloc.block_buckets(alloc.bucket_width())
                  if "blocks" in state.cache else None)
        return self.put_page_table(state, alloc.snapshot(), blocks)

    def retract_program(self, state: ServeState):
        key = ("retract", int(state.active.shape[0]),
               self._kind(state.cache))
        if key not in self._programs:
            ecfg = self.ecfg

            def retract(state: ServeState, new_n, pmon: MonitorState
                        ) -> ServeState:
                overshoot = state.n_reasoning - new_n
                next_pos = state.next_pos - overshoot
                cache = dict(state.cache)
                cache["pos"] = jnp.where(
                    cache["pos"] >= next_pos[:, None], -1, cache["pos"]
                )
                cols = jnp.arange(state.out_tokens.shape[1],
                                  dtype=jnp.int32)[None]
                keep = cols < new_n[:, None]
                last = jnp.take_along_axis(
                    state.out_tokens, (new_n - 1)[:, None], 1)[:, 0]
                # re-derive the </think> latch over the KEPT tokens only: a
                # natural end the generator hit past the proxy's stop point
                # never happened in self-EAT terms
                ended = (jnp.where(keep, state.out_tokens, -1)
                         == ecfg.end_think_id).any(-1)
                return ServeState(
                    cache=cache,
                    rng=state.rng,
                    active=state.active & ~pmon.stop_flag,
                    next_pos=next_pos,
                    last_token=last,
                    n_reasoning=new_n,
                    monitor=pmon,
                    ended_think=ended,
                    out_tokens=jnp.where(keep, state.out_tokens, ecfg.pad_id),
                    out_len=new_n,
                )

            if self.ctx.mesh is None:
                jitted = jax.jit(retract, donate_argnums=0)
            else:
                ssh = self._state_sh(state)
                b = self._batch_entry(int(state.active.shape[0]))
                jitted = jax.jit(
                    retract,
                    in_shardings=(
                        ssh,
                        self._ns(P(b)),
                        jax.tree_util.tree_map(lambda _: self._ns(P(b)),
                                               state.monitor),
                    ),
                    out_shardings=ssh,
                    donate_argnums=0,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def retract(self, state: ServeState, new_n, pmon: MonitorState
                ) -> ServeState:
        """Proxy-mode chunk-boundary reconciliation: rewind every row to the
        proxy's exit decision and sync the proxy monitor into the state.

        In ``monitor="proxy"`` serving the generator decodes whole chunks
        blind (no inline probe), so a row the proxy stopped at emitted-token
        count ``new_n[b] < n_reasoning[b]`` has overshot: extra tokens in
        ``out_tokens``, extra KV committed past the exit position.  This
        program truncates the token buffer back to ``new_n``, rewinds
        ``next_pos``/``n_reasoning``/``out_len``, position-masks the
        overshoot KV (``pos >= new next_pos`` -> -1, slot-agnostic so it
        works for ring AND paged caches — masked slots contribute exact
        zeros to every later attention sum, the paged==ring invariant), and
        re-derives ``ended_think`` over the kept tokens.  ``pmon`` (the
        proxy's MonitorState) replaces the generator's inert monitor so
        harvest/traces read the proxy's stop flags and EMA state.  A row
        with no overshoot passes through unchanged.  DONATES ``state``.
        """
        return self.retract_program(state)(
            state, jnp.asarray(new_n, jnp.int32), pmon
        )

    def retract_lagged_program(self, state: ServeState):
        key = ("retract", int(state.active.shape[0]),
               self._kind(state.cache), "lagged")
        if key not in self._programs:
            ecfg = self.ecfg

            def retract_lagged(state: ServeState, new_n, pmon: MonitorState
                               ) -> ServeState:
                stop = pmon.stop_flag
                # only proxy-STOPPED rows rewind: the others have already
                # decoded one more chunk whose tokens the proxy has not
                # observed yet — their counts must survive this dispatch
                eff = jnp.where(stop, new_n, state.n_reasoning)
                overshoot = state.n_reasoning - eff
                next_pos = state.next_pos - overshoot
                cache = dict(state.cache)
                cache["pos"] = jnp.where(
                    cache["pos"] >= next_pos[:, None], -1, cache["pos"]
                )
                cols = jnp.arange(state.out_tokens.shape[1],
                                  dtype=jnp.int32)[None]
                keep = cols < eff[:, None]
                last = jnp.take_along_axis(
                    state.out_tokens, (eff - 1)[:, None], 1)[:, 0]
                ended = (jnp.where(keep, state.out_tokens, -1)
                         == ecfg.end_think_id).any(-1)
                return ServeState(
                    cache=cache,
                    rng=state.rng,
                    active=state.active & ~stop,
                    next_pos=next_pos,
                    last_token=last,
                    n_reasoning=eff,
                    monitor=pmon,
                    ended_think=ended,
                    out_tokens=jnp.where(keep, state.out_tokens, ecfg.pad_id),
                    out_len=eff,
                )

            if self.ctx.mesh is None:
                jitted = jax.jit(retract_lagged, donate_argnums=0)
            else:
                ssh = self._state_sh(state)
                b = self._batch_entry(int(state.active.shape[0]))
                jitted = jax.jit(
                    retract_lagged,
                    in_shardings=(
                        ssh,
                        self._ns(P(b)),
                        jax.tree_util.tree_map(lambda _: self._ns(P(b)),
                                               state.monitor),
                    ),
                    out_shardings=ssh,
                    donate_argnums=0,
                )
            self._programs[key] = jitted
        return self._programs[key]

    def retract_lagged(self, state: ServeState, new_n, pmon: MonitorState
                       ) -> ServeState:
        """Overlap-mode reconciliation, applied one chunk boundary late:
        ``new_n``/``pmon`` are the proxy's verdict on chunk N while
        ``state`` has already decoded chunk N+1.  Rows the proxy stopped
        rewind exactly as ``retract`` does (their chunk-N overshoot AND
        their whole speculative chunk N+1 are position-masked away); every
        other row passes through untouched — its chunk-N+1 tokens are
        valid and still awaiting the proxy's next observation.  The proxy
        monitor replaces the generator's inert one wholesale, same as the
        sync retract.  DONATES ``state``."""
        return self.retract_lagged_program(state)(
            state, jnp.asarray(new_n, jnp.int32), pmon
        )

    def rollout_program(self, cache, B: int, n: int, greedy: bool):
        key = ("rollout", B, n, greedy, self._kind(cache))
        if key not in self._programs:
            model, cfg, ecfg = self.model, self.cfg, self.ecfg

            def positions(pos1d):
                return positions_for(cfg, pos1d)

            def rollout(params, cache, next_pos, last_token, rng):
                et = jnp.full((B, 1), ecfg.end_think_id, jnp.int32)
                pos1d = next_pos[:, None]
                logits, cache2 = model.decode_step(
                    params, et, positions(pos1d), pos1d, cache
                )
                scfg = dataclasses.replace(ecfg.sampler, greedy=greedy)

                def step(carry, _):
                    cache_c, pos_c, logit_c, rng_c = carry
                    rng_c, sub = jax.random.split(rng_c)
                    tok = sample(sub, logit_c, cfg.vocab, scfg)
                    lp = logprob_of(logit_c, tok, cfg.vocab)
                    p1 = pos_c[:, None]
                    lg, cache_c = model.decode_step(
                        params, tok[:, None], positions(p1), p1, cache_c
                    )
                    return (cache_c, pos_c + 1, lg[:, -1], rng_c), (tok, lp)

                (_, _, _, _), (toks, lps) = jax.lax.scan(
                    step, (cache2, next_pos + 1, logits[:, -1], rng),
                    None, length=n,
                )
                return jnp.moveaxis(toks, 0, 1), jnp.moveaxis(lps, 0, 1)

            if self.ctx.mesh is None:
                jitted = jax.jit(rollout)
            else:
                b = self._batch_entry(B)
                jitted = jax.jit(rollout, in_shardings=(
                    self._param_sh,
                    self._sh(cache_pspecs(self.cfg, self.ctx, cache)),
                    self._ns(P(b)),
                    self._ns(P(b)),
                    self._ns(P()),
                ))
            self._programs[key] = jitted
        return self._programs[key]

    def rollout(self, params, cache, next_pos, last_token, rng, *, n: int,
                greedy: bool = False):
        """Forced answer rollout: append </think> then generate n tokens.
        Returns (tokens (B,n), logprobs (B,n)).  The cache is NOT donated:
        rollouts are functional reads of a live cache the caller keeps
        decoding from (``reason_with_trace``) or re-rolls K times
        (``rollout_answers``) — donation here would corrupt the sequence."""
        return self.rollout_program(cache, int(next_pos.shape[0]), n, greedy)(
            params, cache, next_pos, last_token, rng
        )


# --------------------------------------------------------------------------
# ProxyExecutor: the black-box monitor tier's program store
# --------------------------------------------------------------------------

class ProxyExecutor(Executor):
    """Program store for the proxy (black-box monitor) model.

    The proxy tier (paper §4.2, Fig. 5) is a SECOND model — own params, own
    KV cache (ring or paged, own page pool), own mesh context — that shadows
    the generator's emitted token chunks and computes EAT from *its* logits.
    Its decode state is a regular ``ServeState`` (the ``rng`` /
    ``last_token`` / ``out_tokens`` rows are inert bookkeeping), so every
    structural program is inherited from ``Executor`` unchanged: ``prefill``
    for prompts, ``admit`` / ``admit_paged`` for slot recycling in lock-step
    with the generator's admissions, ``pack_paged`` / ``put_page_table`` for
    the proxy's own page pool.  The one new program is ``observe_chunk`` —
    the forced-input shadow decode.  The generator executor, by contrast,
    never builds a probe or monitored-chunk program in proxy mode (the
    black-box contract; audited in tests/test_proxy_serve.py).
    """

    def __init__(self, model: Model, params, ecfg,
                 monitor: ReasoningMonitor):
        super().__init__(model, params, ecfg, monitor)
        self._shadow = make_shadow_step(model, monitor)

    def observe_chunk(self, params, pstate: ServeState, gen_tokens,
                      n_start, n_emitted, chunk_len) -> ServeState:
        """Shadow one generator chunk through the proxy model.

        ``gen_tokens`` (B, T) is the generator's ``out_tokens`` buffer after
        the chunk; ``n_start`` (B,) the per-row emitted count before it and
        ``n_emitted`` (B,) the tokens it added.  Step ``i`` re-feeds the
        token the generator consumed (``gen_tokens[b, n_start+i-1]``) into
        the proxy cache and due-checks the token it emitted
        (``gen_tokens[b, n_start+i]``), replaying the self-EAT monitor
        transition on the proxy's logits.  A row stops consuming the moment
        its stop latches (``monitor.stop_flag``) — the proxy cache never
        ingests overshoot tokens, so it stays aligned with the retracted
        generator stream.  ``pstate.n_reasoning`` tracks the corrected
        emitted count (the ``retract`` program's ``new_n``).  DONATES
        ``pstate``.
        """
        return self.observe_chunk_program(pstate, int(gen_tokens.shape[1]))(
            params, pstate, jnp.asarray(gen_tokens, jnp.int32),
            jnp.asarray(n_start, jnp.int32),
            jnp.asarray(n_emitted, jnp.int32),
            jnp.asarray(chunk_len, jnp.int32),
        )

    def observe_chunk_program(self, pstate: ServeState, T: int):
        B = int(pstate.active.shape[0])
        key = ("shadow", B, T, self._kind(pstate.cache))
        if key not in self._programs:
            shadow = self._shadow

            def observe_chunk(params, st: ServeState, toks, n_start,
                              n_emitted, chunk_len) -> ServeState:
                def valid_of(s, i):
                    return (i < n_emitted) & ~s.monitor.stop_flag

                def cond(carry):
                    i, s = carry
                    return (i < chunk_len) & valid_of(s, i).any()

                def body(carry):
                    i, s = carry
                    valid = valid_of(s, i)
                    tok_in = jnp.take_along_axis(
                        toks, (n_start + i - 1)[:, None], 1)
                    tok_out = jnp.take_along_axis(
                        toks, (n_start + i)[:, None], 1)[:, 0]
                    cache, mon, new_pos = shadow(
                        params, s.cache, tok_in, tok_out, s.next_pos,
                        s.monitor, valid,
                    )
                    inc = valid.astype(jnp.int32)
                    s = s._replace(
                        cache=cache,
                        monitor=mon,
                        next_pos=new_pos,
                        last_token=jnp.where(valid, tok_out, s.last_token),
                        n_reasoning=s.n_reasoning + inc,
                        out_len=s.out_len + inc,
                        active=valid & ~mon.stop_flag,
                    )
                    return i + 1, s

                _, st = jax.lax.while_loop(
                    cond, body, (jnp.zeros((), jnp.int32), st)
                )
                return st

            if self.ctx.mesh is None:
                jitted = jax.jit(observe_chunk, donate_argnums=1)
            else:
                ssh = self._state_sh(pstate)
                tok_sp, row_sp = proxy_stream_pspecs(self.ctx, B)
                jitted = jax.jit(
                    observe_chunk,
                    in_shardings=(self._param_sh, ssh, self._ns(tok_sp),
                                  self._ns(row_sp), self._ns(row_sp),
                                  self._ns(P())),
                    out_shardings=ssh,
                    donate_argnums=1,
                )
            self._programs[key] = jitted
        return self._programs[key]
