"""Overlapped (double-buffered) serve loop: the host layer between
scheduler and executor.

The synchronous loop in ``engine.serve`` pays one full host round trip per
chunk boundary: dispatch chunk N, block on its scalars, harvest, admit,
push page tables, dispatch chunk N+1.  As the mesh grows the device time
per chunk shrinks while the host time per boundary does not — the weak-
scaling cliff ``artifacts/BENCH_serve_scaling.json`` documents.  This
module restructures the loop around a one-deep software pipeline:

    tick t:   dispatch chunk F        (no host sync — the snapshot is a
                                       future, not a value)
              process boundary F-1    (np.asarray on chunk F-1's snapshot
                                       blocks only on F-1; F keeps running)

``Executor.decode_chunk_snapshot`` returns every host-facing scalar in
FRESH buffers (shapes distinct from all state fields, so XLA can never
alias them into the donated state), which is what lets chunk F be
dispatched before anything of F-1 has been read.  Harvests, admissions,
page-table pushes, and — in proxy mode — the shadow ``observe_chunk`` all
happen inside the overlap window; the proxy's ``retract`` reconciliation
lands one boundary late (``Executor.retract_lagged``), costing at most one
chunk of exit latency and zero tokens (token streams are bit-identical to
the sync loop under greedy sampling — ``tests/test_async_serve.py``).

Host-side consistency is the job of two pieces of pure-host bookkeeping:

* ``scheduler.InFlightLedger`` — dispatch fences.  A harvested row's KV
  pages stay OUT of the allocator free list until the fence open at
  harvest time retires (the in-flight chunk's page table still maps
  them); a slot re-admitted while chunk F is in flight is skipped in
  chunk F's snapshot (its row there belongs to the previous occupant).
* host **mirrors** of the ring pointer and per-row token counts, updated
  from each retired snapshot.  They lag the device by at most one
  dispatched chunk, so page mapping passes ``slack = chunk_len`` extra
  slots to over-cover the in-flight writes (see
  ``Executor.ensure_chunk_pages``) and the ring-capacity guard checks
  ``mirror_cur + chunk_len`` — admission under overlap therefore wants
  one chunk of extra capacity headroom (docs/serving.md).

Layering contract (enforced by ``tools/audit``): this module is DISPATCH
ONLY — it builds no jitted programs (executor-only-jit) and never calls
``jax.block_until_ready`` / ``device_get``; the sanctioned blocking reads
are ``Telemetry.fetch`` (``np.asarray`` in a ``serve.wait`` span) on a
*snapshot* or on the proxy chain's outputs (never on donated state).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.executor import SNAP_ROWS
from repro.serving.scheduler import InFlightLedger, pools_can_admit

# positional indices into the snapshot's (len(SNAP_ROWS), B) int block
(SNAP_ACTIVE, SNAP_NR, SNAP_OUTLEN, SNAP_ENDED, SNAP_STOP, SNAP_EVALS,
 SNAP_CUR) = range(len(SNAP_ROWS))


class PipelineHooks:
    """Observation/interference seam for the overlapped loop.

    Every pipeline event calls the matching no-op method below; tests
    subclass to (a) record the event order — asserting, e.g., that chunk
    F+1's dispatch precedes boundary F's harvest — and (b) FORCE
    adversarial schedules: a hook that blocks on the snapshot inside
    ``on_dispatch`` degenerates the pipeline to harvest-before-dispatch,
    pinning that correctness never depends on the overlap actually
    overlapping.  Hooks run on the host thread; raising aborts the serve.
    """

    def on_dispatch(self, fence: int, snap: dict) -> None:
        """Chunk ``fence`` dispatched; ``snap`` is its (unread) snapshot."""

    def on_retire(self, fence: int) -> None:
        """Boundary ``fence`` read back; its deferred page frees released."""

    def on_observe(self, fence: int, pstate) -> None:
        """Proxy shadow of chunk ``fence`` observed (proxy mode only)."""

    def on_retract(self, fence: int) -> None:
        """Lagged retract for boundary ``fence`` dispatched (proxy mode)."""

    def on_harvest(self, fence: int, slots: list[int]) -> None:
        """Requests in ``slots`` finished at boundary ``fence``."""

    def on_admit(self, fence: int, slot: int) -> None:
        """A queued request admitted into ``slot`` while ``fence`` flies."""


def serve_overlapped(engine, ss, *, answer_len: int = 0,
                     record_trace: bool = False,
                     hooks: PipelineHooks | None = None) -> int:
    """The overlapped serve loop body.  ``ss`` is the namespace from
    ``ReasoningEngine._serve_setup`` (prefilled initial cohort, scheduler,
    allocators, proxy tier, the call's telemetry); the requests it
    finishes are identical — under greedy sampling — to the sync loop's.
    Returns the ring pointer of the last retired boundary for the engine's
    shared finaliser."""
    ex = engine.executor
    ecfg = engine.ecfg
    tel = ss.tel
    sched, alloc, ptier = ss.sched, ss.alloc, ss.ptier
    paged, proxy_mode = ss.paged, ss.proxy_mode
    S, B, budget, chunk_py = ss.S, ss.B, ss.budget, ss.chunk_py
    state = ss.state
    rng = ss.rng
    hooks = hooks if hooks is not None else PipelineHooks()

    ledger = InFlightLedger()
    engine._ledger = ledger          # post-serve stats (tests/benches)
    for s, req in sched.bound():
        req.admitted_fence = ledger.mark_admitted(s)   # fence 0: never skipped

    # host mirrors from the last retired boundary (setup values to start);
    # lag the device by <= one dispatched chunk — all page/capacity math
    # below over-covers that lag with `slack`/`chunk_py` headroom
    mirror_nr = np.ones((B,), np.int32)
    mirror_outlen = np.ones((B,), np.int32)
    mirror_cur = ss.cur0

    def dispatch_tick():
        """Dispatch the next chunk without reading anything back."""
        nonlocal state
        bound = [(s, r) for s, r in sched.bound()]
        if paged:
            with tel.span("serve.pages", pool="gen"):
                state = ex.ensure_chunk_pages(
                    alloc, state, [s for s, _ in bound],
                    chunk_py + ss.gen_tail, tail=ss.gen_tail, budget=budget,
                    cur=mirror_cur, n_reasoning=mirror_nr,
                    slack=chunk_py if ledger.in_flight else 0,
                )
        with tel.span("serve.dispatch"):
            state, snap = ex.decode_chunk_snapshot(
                engine.params, state, ss.budget_dev, ss.chunk,
                use_monitor=ss.gen_monitor,
            )
        tel.count("chunks")
        fence = ledger.open_fence()
        hooks.on_dispatch(fence, snap)
        return fence, snap, bound

    def process_boundary(fence, snap, bound):
        """Read boundary ``fence``'s snapshot (blocks only on that chunk),
        reconcile, harvest, and admit — all while the next chunk flies."""
        nonlocal state, rng, mirror_cur
        ints, var_np, toks = tel.fetch(
            "snapshot", (snap["ints"], snap["var"], snap["tokens"]))
        toks = toks[:, :-1]
        active_np = ints[SNAP_ACTIVE].astype(bool)
        nr = ints[SNAP_NR]
        outlen = ints[SNAP_OUTLEN]
        ended = ints[SNAP_ENDED].astype(bool)
        stop = ints[SNAP_STOP].astype(bool)
        evals = ints[SNAP_EVALS]
        cur = int(ints[SNAP_CUR, 0])
        ledger.retire_fence(fence)          # releases deferred page frees
        hooks.on_retire(fence)
        # slots re-admitted while this chunk flew: their snapshot rows are
        # the PREVIOUS occupant's — ignore them everywhere below
        skip = ledger.admitted_after(fence)
        t_seen = time.perf_counter()
        for s, req in bound:
            if s not in skip:
                req.saw_tokens(t_seen)

        new_n = pstop = pevals = pvar = None
        if proxy_mode:
            # shadow this boundary's emitted tokens through the proxy (on
            # its own dispatch chain — concurrent with the generator's
            # in-flight chunk), then reconcile the generator ONE boundary
            # late: only proxy-stopped rows rewind (retract_lagged)
            with tel.span("serve.proxy"):
                n_start = mirror_outlen.copy()
                n_emitted = (outlen - n_start).astype(np.int32)
                for s in skip:
                    n_emitted[s] = 0
                ptier.begin_chunk(chunk_py, [s for s, _ in sched.bound()])
                new_n_dev, pmon = ptier.observe(toks, n_start, n_emitted,
                                                chunk_py)
                new_n, pstop, pevals, pvar = tel.fetch("proxy", (
                    new_n_dev, pmon.stop_flag, pmon.n_evals,
                    engine.monitor.stopper.debiased_var(pmon.stop_state)))
                pstop = pstop.astype(bool)
                hooks.on_observe(fence, ptier.state)
                state = ex.retract_lagged(
                    state, engine._across_tiers(new_n_dev, tel),
                    engine._across_tiers(pmon, tel))
                hooks.on_retract(fence)

        if record_trace:
            # ``bound`` was captured at dispatch — exactly the rows that
            # decoded this chunk; already-finished requests self-guard
            with tel.span("serve.trace"):
                for s, req in bound:
                    if proxy_mode:
                        req.record_trace(new_n[s], pevals[s], pvar[s])
                    else:
                        req.record_trace(nr[s], evals[s], var_np[s])

        if proxy_mode:
            active_eff = active_np & ~pstop
        else:
            active_eff = active_np
        done = [(s, r) for s, r in sched.finished_slots(active_eff)
                if s not in skip]

        if done:
            with tel.span("serve.harvest"):
                ans = None
                if answer_len:
                    if paged:
                        # rollout writes </think> + answer_len slots past
                        # cur; the in-flight chunk may already have
                        # advanced the ring, so over-map by one chunk of
                        # slack
                        with tel.span("serve.pages", pool="gen"):
                            state = ex.ensure_chunk_pages(
                                alloc, state, [s for s, _ in sched.bound()],
                                answer_len + 1, cur=cur,
                                slack=chunk_py if ledger.in_flight else 0,
                            )
                    toks_ans, _ = engine.force_answer(state, answer_len,
                                                      greedy=True)
                    ans = tel.fetch("answer", toks_ans)

                for s, req in done:
                    sched.release(s)
                    ledger.mark_released(s, fence)
                    if proxy_mode:
                        n_fin = int(new_n[s]) if pstop[s] else int(nr[s])
                        eat_s = bool(pstop[s])
                        # recompute off the truncated stream — the
                        # snapshot's flag may predate the lagged rewind
                        ended_s = bool(
                            (toks[s, :n_fin] == ecfg.end_think_id).any())
                    else:
                        n_fin = int(nr[s])
                        eat_s = bool(stop[s])
                        ended_s = bool(ended[s])
                    req.finish(
                        reasoning_tokens=toks[s, :n_fin].copy(),
                        n_reasoning=n_fin,
                        ended_think=ended_s,
                        eat_stop=eat_s,
                        answer_tokens=(ans[s].copy() if ans is not None
                                       else None),
                    )
                    if paged:
                        # the in-flight chunk's page table still maps this
                        # row's pages: park them on the ledger until its
                        # fence retires
                        ledger.defer_free(alloc, s)
                    if ptier is not None:
                        # the proxy chain was synced by the observe read
                        # above — its pages can go straight back to the pool
                        ptier.free_row(s)
            hooks.on_harvest(fence, [s for s, _ in done])

        # mirrors advance to this boundary's (post-verdict) values; skip
        # rows keep their admission-time values — their snapshot data here
        # belongs to the previous occupant
        for s in range(B):
            if s in skip:
                continue
            if proxy_mode and pstop[s]:
                mirror_nr[s] = mirror_outlen[s] = new_n[s]
            else:
                mirror_nr[s] = nr[s]
                mirror_outlen[s] = outlen[s]
        mirror_cur = cur

        # admission sweeps EVERY free slot (deferred admissions included);
        # the ring guard uses the mirror plus one in-flight chunk of
        # headroom — an upper bound on the true pointer
        for s in (s for s, r in enumerate(sched.slots) if r is None):
            if sched.pending == 0:
                continue
            used_ub = mirror_cur + (chunk_py if ledger.in_flight else 0)
            sched.check_capacity(used_ub, "another admission")
            if ptier is not None:
                ptier.check_capacity("another admission")
            if not pools_can_admit(S, alloc,
                                   ptier.alloc if ptier else None):
                for a in (alloc, ptier.alloc if ptier else None):
                    if a is not None and not a.can_admit(S):
                        a.deferrals += 1
                continue
            nxt = sched.admit_next(s)
            with tel.span("serve.admit", rid=nxt.rid):
                rng, sub = jax.random.split(rng)
                one = engine.start(jnp.asarray(nxt.prompt[None]),
                                   jnp.asarray([nxt.prompt_len]), sub,
                                   capacity=ss.C_pre)
                if paged:
                    row_table = alloc.admit_row(s, S, used_ub)
                    state = ex.admit_paged(state, one, s, row_table)
                else:
                    state = engine._admit(state, one, s)
                if ptier is not None:
                    ptier.admit(s, nxt.prompt, nxt.prompt_len, S)
                nxt.begin_decode()
            tel.count("admissions")
            nxt.admitted_fence = ledger.mark_admitted(s)
            mirror_nr[s] = mirror_outlen[s] = 1
            hooks.on_admit(ledger.fence, s)

    # ---- the pipeline: always dispatch-ahead, then read the PREVIOUS
    # boundary.  Chunks whose rows all turned inactive execute zero device
    # steps (the while_loop cond short-circuits), so the unconditional
    # dispatch never needs a host sync to decide — at most one trailing
    # no-op chunk per drain versus the sync loop.
    pend = None
    while True:
        while sched.running:
            nxt_pend = dispatch_tick()
            if pend is not None:
                process_boundary(*pend)
            pend = nxt_pend
        if pend is not None:
            process_boundary(*pend)   # retires the last fence; may admit
            pend = None
            continue
        if sched.pending == 0:
            break
        # every slot empty, queue non-empty, all fences retired and every
        # deferred free released: a pool genuinely too small — same
        # fail-fast sizing hints as the sync loop
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
        break

    return mirror_cur
