"""Pass 2 — lowered-program audit: trace/lower every serving program and
check the artifacts, not the source.

The other passes read the AST; this one asks jax.  Every executor /
ProxyExecutor program family (``executor.PROGRAM_FAMILIES``) is built over
the full key matrix

    monitor tier {self, proxy} x cache kind {ring, paged}
    x decode-attention impl {gather, xla, pallas (interpret mode, asked
      for through ``Model.interpret``: the audit runs on the CPU)}
    x delta regime {exit-at-first-eval, run-to-budget} for the monitored
      families (chunk / shadow / serve_step — delta is a traced constant)

using ``jax.eval_shape`` structs only (no device arrays, no model init:
auditing is shape-level).  Three artifact checks per program:

  sync-point       the jaxpr (recursively, through cond/while/scan
                   branches) and the lowered StableHLO must contain no
                   host callbacks (``pure_callback`` / ``io_callback`` /
                   ``debug_callback``) and no infeed/outfeed — a callback
                   inside a decode chunk serializes every dispatch on the
                   host;
  float-widening   no ``convert_element_type`` that widens a non-scalar
                   float array (a silent fp32 upcast of a bf16 cache
                   doubles the serving footprint);
  donation         ``DONATION_CONTRACT``: compiled programs of donating
                   families must alias input to output buffers
                   (``memory_analysis().alias_size_in_bytes > 0`` — the KV
                   cache is updated in place), and the deliberately
                   functional families (decode / probe / rollout) must
                   alias nothing.  Compiling is the expensive step, so the
                   contract is checked once per family in designated cells
                   (donation is impl-independent); every other cell stops
                   at trace + lower.

The proxy tier additionally gets the black-box assertion from
docs/architecture.md: after building a proxy cell, the GENERATOR executor's
program store must contain no probe program and no monitored chunk — no
generator logits feed the exit decision.

``launch.dryrun`` imports ``scan_hlo_text`` from here (lazily, inside
``run_one``) so the roofline artifacts get the same sync-point screen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.common import PassResult, Violation

# audit-sized serving geometry: tiny model, 2 rows, 4 blocks of 8 slots
B = 2
CAP = 32
PAGE = 8
NB = CAP // PAGE
NUM_PAGES = B * NB + 1          # ring-equivalent pool + trash page
C_PRE = 16                      # dense prefill capacity (page multiple)
T_BUF = 16                      # out_tokens buffer / shadow stream width
PROMPT = 8

TIERS = ("self", "proxy")
KINDS = ("ring", "paged")
IMPLS = ("gather", "xla", "pallas")
REGIMES = (("exit", 1e-3), ("never", 1e9))

#: (tier, kind) cells in which each family's donation contract is compiled
#: and checked — once per family, on the gather impl (donation is a buffer
#: aliasing property of the jit call, not of the attention algorithm).
_DONATION_CELLS = {
    ("self", "ring"): ("chunk", "chunk_snapshot", "decode", "prefill",
                       "probe", "admit", "rollout", "serve_step"),
    ("self", "paged"): ("pack", "admit"),
    ("proxy", "ring"): ("shadow", "retract", "retract_lagged"),
}


#: host-sync primitives whose names do not say "callback" (jax.debug.print
#: binds ``debug_print``)
_HOST_SYNC_PRIMITIVES = ("debug_print", "infeed", "outfeed")


def _i32(shape=()):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------------- scans
def _subjaxprs(value):
    if hasattr(value, "jaxpr"):
        yield value.jaxpr
    elif hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def scan_jaxpr(jaxpr, where: str) -> list:
    """Sync-point + float-widening screen over a (closed) jaxpr, recursing
    through control-flow sub-jaxprs."""
    out = []
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if "callback" in name or name in _HOST_SYNC_PRIMITIVES:
                out.append(Violation(
                    "lowered", where, "sync-point",
                    f"jaxpr contains host sync primitive '{name}' — a "
                    f"callback inside a serving program serializes every "
                    f"dispatch on the host"))
            elif name == "convert_element_type":
                old = eqn.invars[0].aval
                new = eqn.outvars[0].aval
                if (getattr(old, "ndim", 0) >= 2
                        and jnp.issubdtype(old.dtype, jnp.floating)
                        and jnp.issubdtype(new.dtype, jnp.floating)
                        and new.dtype.itemsize > old.dtype.itemsize):
                    out.append(Violation(
                        "lowered", where, "float-widening",
                        f"non-scalar float widening "
                        f"{old.dtype.name}->{new.dtype.name} on shape "
                        f"{tuple(old.shape)} — silent upcasts multiply the "
                        f"serving footprint"))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return out


def scan_hlo_text(text: str, where: str = "hlo") -> list:
    """Sync-point screen over lowered StableHLO/HLO text (belt to the
    jaxpr's braces: callbacks that lower to custom calls keep 'callback'
    in the target name)."""
    out = []
    for marker in ("callback", "infeed", "outfeed"):
        if marker in text:
            out.append(Violation(
                "lowered", where, "sync-point",
                f"lowered program text contains '{marker}'"))
    return out


def check_donation(compiled, family: str, donate: bool, where: str) -> list:
    """``DONATION_CONTRACT`` against the compiled artifact's aliasing."""
    mem = compiled.memory_analysis()
    if mem is None or not hasattr(mem, "alias_size_in_bytes"):
        return []
    alias = int(mem.alias_size_in_bytes)
    if donate and alias <= 0:
        return [Violation(
            "lowered", where, "donation",
            f"family '{family}' must donate (update the cache in place) "
            f"but the compiled program aliases 0 bytes")]
    if not donate and alias > 0:
        return [Violation(
            "lowered", where, "donation",
            f"family '{family}' is contractually functional but the "
            f"compiled program aliases {alias} bytes of its inputs")]
    return []


# ------------------------------------------------------------- cell set-up
def _monitor(delta: float):
    from repro.core.eat import make_probe
    from repro.core.monitor import ReasoningMonitor
    from repro.core.stopping import EATStopper

    return ReasoningMonitor(
        stopper=EATStopper(alpha=0.2, delta=delta),
        probe=make_probe(1, (4,)),
        schedule="every_n", every_n=4, min_evals=1,
    )


def _ecfg(kind: str, impl: str):
    from repro.serving.cache import CacheConfig
    from repro.serving.engine import EngineConfig
    from repro.serving.sampler import SamplerConfig

    return EngineConfig(
        max_reasoning_tokens=T_BUF, capacity=CAP, chunk_len=8,
        sampler=SamplerConfig(greedy=True),
        cache=CacheConfig(kind=kind, page_size=PAGE, num_pages=NUM_PAGES,
                          attn_impl=impl if kind == "paged" else "gather"),
    )


def _model(name: str, impl: str):
    from repro.configs.base import get_config
    from repro.models import Model

    model = Model(get_config(name), attn_impl="xla",
                  paged_attn_impl=impl, paged_attn_page=PAGE,
                  interpret=impl == "pallas")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, params


def _cache_struct(cfg, kind: str, impl: str, batch: int):
    from repro.serving.cache import alloc_cache, alloc_paged_template
    from repro.serving.scheduler import PageAllocator

    def mk():
        if kind == "ring":
            return alloc_cache(cfg, batch, CAP)
        native = impl != "gather"
        alloc = PageAllocator(NUM_PAGES, PAGE, NB, batch) if native else None
        return alloc_paged_template(cfg, batch, CAP, PAGE, NUM_PAGES,
                                    alloc=alloc, native=native)

    return jax.eval_shape(mk)


def _dense_struct(cfg, batch: int, capacity: int):
    from repro.serving.cache import alloc_cache

    return jax.eval_shape(lambda: alloc_cache(cfg, batch, capacity))


def _state_struct(cfg, monitor, cache_struct, batch: int):
    from repro.serving.executor import ServeState

    def mk():
        return ServeState(
            cache=jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), cache_struct),
            rng=jax.random.PRNGKey(0),
            active=jnp.ones((batch,), bool),
            next_pos=jnp.zeros((batch,), jnp.int32),
            last_token=jnp.zeros((batch,), jnp.int32),
            n_reasoning=jnp.zeros((batch,), jnp.int32),
            monitor=monitor.init(batch),
            ended_think=jnp.zeros((batch,), bool),
            out_tokens=jnp.zeros((batch, T_BUF), jnp.int32),
            out_len=jnp.zeros((batch,), jnp.int32),
        )

    return jax.eval_shape(mk)


def _rng_struct():
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


# --------------------------------------------------------------- the audit
class _Audit:
    def __init__(self):
        self.violations: list[Violation] = []
        self.keys: set = set()
        self.n_lowered = 0
        self.n_donation_checked = 0
        self.families: set = set()

    def program(self, tag: tuple, family: str, prog, args, *,
                donate: bool | None = None, compile_donation: bool = False):
        """tag = (tier, kind, impl, regime, program-key)."""
        where = "/".join(str(t) for t in tag)
        try:
            jaxpr = prog.trace(*args).jaxpr
            self.violations += scan_jaxpr(jaxpr, where)
            lowered = prog.lower(*args)
            self.violations += scan_hlo_text(lowered.as_text(), where)
            self.keys.add(tag)
            self.families.add(family)
            self.n_lowered += 1
            if compile_donation and donate is not None:
                self.violations += check_donation(
                    lowered.compile(), family, donate, where)
                self.n_donation_checked += 1
        except Exception as e:  # surface, don't abort the whole audit
            self.violations.append(Violation(
                "lowered", where, "lowering-failed",
                f"{type(e).__name__}: {e}"))


def _audit_self_cell(a: _Audit, kind: str, impl: str):
    from repro.serving.executor import (
        DONATION_CONTRACT,
        Executor,
        ServeStepConfig,
        build_serve_step_program,
    )
    from repro.serving.sampler import SamplerConfig

    model, params = _model("tiny", impl)
    cfg = model.cfg
    ecfg = _ecfg(kind, impl)
    don_fams = _DONATION_CELLS.get(("self", kind), ()) if impl == "gather" \
        else ()

    def dc(family):
        return family in don_fams

    s0 = _i32()
    for regime, delta in REGIMES:
        monitor = _monitor(delta)
        ex = Executor(model, params, ecfg, monitor)
        cache = _cache_struct(cfg, kind, impl, B)
        state = _state_struct(cfg, monitor, cache, B)

        def tag(prog_key, rg=regime):
            return ("self", kind, impl, rg, str(prog_key))

        # monitored chunk: the delta regime is traced into the stop rule
        prog = ex.chunk_program(state, True)
        a.program(tag(("chunk", B, True, True)), "chunk", prog,
                  (params, state, s0, s0), donate=True,
                  compile_donation=dc("chunk") and regime == "exit")
        # overlap-mode variant: the chunk plus its packed host snapshot —
        # same delta sensitivity, and the snapshot outputs must NOT break
        # the state donation (the pipeline reads them after the state has
        # been donated into the next dispatch)
        a.program(tag(("chunk", B, True, True, "snap")), "chunk_snapshot",
                  ex.chunk_snapshot_program(state, True),
                  (params, state, s0, s0), donate=True,
                  compile_donation=dc("chunk_snapshot") and regime == "exit")

        if regime != "exit":
            continue           # the remaining programs don't read delta

        a.program(tag(("chunk", B, False, True)), "chunk",
                  ex.chunk_program(state, False), (params, state, s0, s0))
        a.program(tag(("decode", B)), "decode", ex.decode_program(state),
                  (params, state),
                  donate=DONATION_CONTRACT["decode"] is not None,
                  compile_donation=dc("decode"))
        a.program(tag(("probe", B)), "probe", ex.probe_program(cache, B),
                  (params, cache, _i32((B,))),
                  donate=DONATION_CONTRACT["probe"] is not None,
                  compile_donation=dc("probe"))
        a.program(tag(("rollout", B, 4, True)), "rollout",
                  ex.rollout_program(cache, B, 4, True),
                  (params, cache, _i32((B,)), _i32((B,)), _rng_struct()),
                  donate=DONATION_CONTRACT["rollout"] is not None,
                  compile_donation=dc("rollout"))

        dense = _dense_struct(cfg, B, C_PRE)
        a.program(tag(("prefill", B)), "prefill",
                  ex.prefill_program(dense, B),
                  (params, _i32((B, PROMPT)), _i32((B, PROMPT)),
                   _i32((B, PROMPT)), dense),
                  donate=True, compile_donation=dc("prefill"))

        if kind == "ring":
            one = _state_struct(cfg, monitor, _cache_struct(cfg, kind, impl, 1), 1)
            a.program(tag(("admit", B)), "admit", ex.admit_program(state, one),
                      (state, one, s0), donate=True,
                      compile_donation=dc("admit"))
        else:
            a.program(tag(("pack", B, C_PRE)), "pack",
                      ex.pack_paged_program(cache, dense),
                      (cache, dense, _i32((B, NB))),
                      donate=True, compile_donation=dc("pack"))
            one = _state_struct(cfg, monitor, _dense_struct(cfg, 1, C_PRE), 1)
            a.program(tag(("admit", B, "paged", C_PRE)), "admit",
                      ex.admit_paged_program(state, one),
                      (state, one, s0, _i32((NB,))),
                      donate=True, compile_donation=dc("admit"))

    # the dry-run's every-token step, both regimes — the exact program
    # launch.dryrun lowers and costs out (gather cells only: the regime
    # coverage is about the stop rule, not the attention impl)
    if impl == "gather":
        for regime, delta in REGIMES:
            from repro.core.stopping import EATStopper

            monitor = _monitor(delta)
            cache = _cache_struct(cfg, kind, impl, B)
            scfg = ServeStepConfig(
                probe=monitor.probe,
                stopper=EATStopper(alpha=0.2, delta=delta),
                sampler=SamplerConfig(greedy=True),
            )
            jitted, mon_struct = build_serve_step_program(
                model, scfg, cache, params)
            a.program(("self", kind, impl, regime, str(("serve_step", B))),
                      "serve_step", jitted,
                      (params, cache, _i32((B, 1)), _i32((B, 1)),
                       mon_struct, _rng_struct()),
                      donate=True,
                      compile_donation="serve_step" in
                      _DONATION_CELLS.get(("self", kind), ())
                      and regime == "exit")


def _audit_proxy_cell(a: _Audit, kind: str, impl: str):
    """Proxy tier: the generator decodes blind (no probe, no monitored
    chunk) and the ProxyExecutor shadows its emitted chunks."""
    from repro.serving.executor import (
        DONATION_CONTRACT,
        Executor,
        ProxyExecutor,
        build_stream_monitor_programs,
    )

    gmodel, gparams = _model("tiny", impl)
    pmodel, pparams = _model("tiny-proxy", impl)
    ecfg = _ecfg(kind, impl)
    don_fams = _DONATION_CELLS.get(("proxy", kind), ()) if impl == "gather" \
        else ()
    s0 = _i32()

    # generator side: monitor is inert in proxy mode (use_monitor=False)
    gen_monitor = _monitor(1e9)
    gex = Executor(gmodel, gparams, ecfg, gen_monitor)
    gcache = _cache_struct(gmodel.cfg, kind, impl, B)
    gstate = _state_struct(gmodel.cfg, gen_monitor, gcache, B)

    a.program(("proxy", kind, impl, "never", str(("chunk", B, False, True))),
              "chunk", gex.chunk_program(gstate, False),
              (gparams, gstate, s0, s0))
    a.program(("proxy", kind, impl, "never", str(("retract", B))),
              "retract", gex.retract_program(gstate),
              (gstate, _i32((B,)), jax.eval_shape(
                  lambda: gen_monitor.init(B))),
              donate=DONATION_CONTRACT["retract"] is not None,
              compile_donation="retract" in don_fams)
    # overlap-mode programs on the generator chain: the snapshot chunk the
    # pipeline dispatches ahead, and the one-boundary-late reconciliation
    a.program(("proxy", kind, impl, "never",
               str(("chunk", B, False, True, "snap"))),
              "chunk_snapshot", gex.chunk_snapshot_program(gstate, False),
              (gparams, gstate, s0, s0))
    a.program(("proxy", kind, impl, "never", str(("retract", B, "lagged"))),
              "retract_lagged", gex.retract_lagged_program(gstate),
              (gstate, _i32((B,)), jax.eval_shape(
                  lambda: gen_monitor.init(B))),
              donate=DONATION_CONTRACT["retract"] is not None,
              compile_donation="retract_lagged" in don_fams)

    # the black-box contract, checked on the artifacts: the generator
    # program store must hold no probe and no monitored chunk
    for key in gex._programs:
        if key[0] == "probe" or (key[0] == "chunk" and key[2]):
            a.violations.append(Violation(
                "lowered", f"proxy/{kind}/{impl}", "black-box",
                f"generator executor built {key} in proxy mode — generator "
                f"logits must not feed the exit decision"))

    # proxy side: shadow decode over both delta regimes
    for regime, delta in REGIMES:
        monitor = _monitor(delta)
        px = ProxyExecutor(pmodel, pparams, ecfg, monitor)
        pcache = _cache_struct(pmodel.cfg, kind, impl, B)
        pstate = _state_struct(pmodel.cfg, monitor, pcache, B)
        a.program(("proxy", kind, impl, regime, str(("shadow", B, T_BUF))),
                  "shadow", px.observe_chunk_program(pstate, T_BUF),
                  (pparams, pstate, _i32((B, T_BUF)), _i32((B,)),
                   _i32((B,)), s0),
                  donate=True,
                  compile_donation="shadow" in don_fams
                  and regime == "exit")

    # the host-streaming ProxyMonitor's programs (built by the executor
    # module for proxy.py — the layering fix this PR) — once is enough
    if kind == "ring" and impl == "gather":
        consume, probe_fn, _prefill = build_stream_monitor_programs(
            pmodel, _monitor(1e-3).probe)
        dense = _dense_struct(pmodel.cfg, B, CAP)
        a.program(("proxy", kind, impl, "exit", "('stream_consume',)"),
                  "stream", consume,
                  (pparams, dense, _i32((B, PROMPT)), _i32((B,))))
        a.program(("proxy", kind, impl, "exit", "('stream_probe',)"),
                  "stream", probe_fn, (pparams, dense, _i32((B,))))


def run(quick: bool = False) -> PassResult:
    a = _Audit()
    cells = [(t, k, i) for t in TIERS for k in KINDS for i in IMPLS]
    if quick:
        cells = [("self", "ring", "gather"), ("proxy", "paged", "xla")]
    for tier, kind, impl in cells:
        if tier == "self":
            _audit_self_cell(a, kind, impl)
        else:
            _audit_proxy_cell(a, kind, impl)

    covered = {(t, k, i) for (t, k, i, _, _) in a.keys}
    return PassResult("lowered", a.violations, {
        "cells": len(cells),
        "cells_covered": len(covered),
        "programs_lowered": a.n_lowered,
        "distinct_keys": len(a.keys),
        "donation_checked": a.n_donation_checked,
        "families": sorted(a.families),
        "quick": quick,
    })
