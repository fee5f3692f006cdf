"""Serve telemetry: the host spans and counters of one ``serve()`` call.

``ReasoningEngine.serve`` owns one ``Telemetry`` per call and hands it back
on its results (``ServeResults.telemetry``).  Both serve loops open a span
at each layer boundary and route every blocking device->host read through
``Telemetry.fetch``, so the host time of a job splits into named phases:

    serve            root; attributes loop, slots, requests
    serve.setup      cohort prefill and pack (the initial batch's admission)
    serve.pages      page mapping and the page-table push (attribute pool)
    serve.dispatch   the decode-chunk dispatch
    serve.proxy      the proxy's shadow of a chunk and the retract
    serve.trace      the EAT-trace snapshot of a boundary (record_trace)
    serve.harvest    the forced answer and ``Request.finish`` of the exits
    serve.admit      one admission into a freed slot (attribute rid)
    serve.wait       a blocking device->host read (attribute what)

Each span is also a ``jax.profiler.TraceAnnotation``: with the profiler on
it lands on the trace's host plane, on the device trace's clock.  Counters
(``count``) are integers the loops and their shared finaliser set:
``chunks``, ``decode_steps``, ``row_steps``, ``slots``, ``host_reads``,
``admissions``, and per page pool (``gen``, ``proxy``) ``<pool>.pool_pages``,
``<pool>.peak_pages``, ``<pool>.pages_reused``, ``<pool>.deferrals``.

Recording is always on.  It costs a few microseconds a span and is bounded
by chunks and requests, never by decode steps; it adds no device work and
no device->host read.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np


class Telemetry:
    """Spans ``(name, start_ns, end_ns, parent_index, attrs)`` on
    ``time.perf_counter_ns`` (parent -1 for a root) and integer counters."""

    def __init__(self):
        self.spans: list[tuple] = []
        self.counters: dict[str, int] = {}
        self._open: list[int] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        i = len(self.spans)
        parent = self._open[-1] if self._open else -1
        start = time.perf_counter_ns()
        self.spans.append((name, start, None, parent, attrs))
        self._open.append(i)
        try:
            with jax.profiler.TraceAnnotation(name, **attrs):
                yield
        finally:
            self._open.pop()
            self.spans[i] = (name, start, time.perf_counter_ns(), parent,
                             attrs)

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def fetch(self, what: str, tree):
        """Host copies (``np.asarray``) of a device value or pytree — THE
        blocking read of the serve loops, one ``host_reads`` per leaf."""
        self.count("host_reads", len(jax.tree_util.tree_leaves(tree)))
        with self.span("serve.wait", what=what):
            return jax.tree_util.tree_map(np.asarray, tree)

    def summary(self) -> dict:
        """JSON-able: per span name its count, total and self seconds (the
        duration less the part its child spans cover), and every counter.
        Spans still open are left out."""
        closed = [s for s in self.spans if s[2] is not None]
        child_ns = [0] * len(self.spans)
        for _, t0, t1, parent, _ in closed:
            if parent >= 0:
                child_ns[parent] += t1 - t0
        spans: dict[str, dict] = {}
        for i, (name, t0, t1, _, _) in enumerate(self.spans):
            if t1 is None:
                continue
            s = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0})
            s["count"] += 1
            s["total_s"] += (t1 - t0) * 1e-9
            s["self_s"] += (t1 - t0 - child_ns[i]) * 1e-9
        return {"spans": spans, "counters": dict(self.counters)}

    def dump(self) -> dict:
        """Every span and counter, JSON-able; ``load`` restores them."""
        return {"spans": [list(s) for s in self.spans],
                "counters": dict(self.counters)}

    @classmethod
    def load(cls, d: dict) -> "Telemetry":
        tel = cls()
        tel.spans = [tuple(s) for s in d["spans"]]
        tel.counters = dict(d["counters"])
        return tel


class ServeResults(list):
    """``serve()``'s per-request result dicts in request order — a plain
    list to every caller — carrying the call's ``telemetry``."""

    def __init__(self, results, telemetry: Telemetry):
        super().__init__(results)
        self.telemetry = telemetry
