"""Percentile and rate arithmetic for the end-to-end metrics."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest ranks of the sorted values (numpy's default rule)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_rates(jobs: list[dict]) -> dict:
    """End-to-end metrics over every job of a window.  Each job is
    ``{"seconds": wall time, "results": serve() results}``; tokens are the
    reasoning plus answer tokens of each completed request.  Rates are
    taken over the jobs' total wall time, ramp and drain included, and the
    tail over every request of every job."""
    seconds = sum(j["seconds"] for j in jobs)
    results = [r for j in jobs for r in j["results"]]
    tokens = sum(r["n_reasoning"] + len(r.get("answer_tokens", ()))
                 for r in results)
    return {
        "tokens_per_s": tokens / seconds,
        "requests_per_s": len(results) / seconds,
        "latency_p95_s": percentile([r["latency_s"] for r in results], 95),
        "seconds": seconds,
        "requests": len(results),
        "tokens": tokens,
    }
