"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, kernel time by name, the device
operations that took most time, and the longest idle gaps named by what
the host was doing.

The traced window is the host span named ``WINDOW_SPAN`` that the harness
opens around one whole job.  Host spans the harness opens around each
call into the program (``HOST_SPAN_PREFIX`` + the call's name) name the
idle gaps; a gap outside every such span belongs to the serve loop's own
host code.
"""
from __future__ import annotations

import re
from collections import defaultdict

WINDOW_SPAN = "bench_job"
HOST_SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
#: gaps shorter than this lie between the operations of one program
SHORT_GAP_NS = 10_000
SHORT_GAP = "between device ops (<10 us)"


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def device_planes(pd):
    """The chips' planes: device planes that carry an ``XLA Ops`` line
    (a TPU trace also holds device planes of no chip, such as
    ``/device:CUSTOM:Megascale Trace``)."""
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(ln.name == OPS_LINE for ln in p.lines)]


def short_name(name: str) -> str:
    """An op event's instruction name: a TPU trace names each op by its
    HLO text (``%paged_attention.24 = bf16[...] custom-call(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_events(plane):
    """(instruction name, start_ns, end_ns, is_leaf) of every device
    operation, in start order.  Control flow (``while``, ``conditional``)
    encloses the ops it runs; an op is a leaf when the next op starts at
    or after its end."""
    lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
    evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                  short_name(ev.name)) for ln in lines for ev in ln.events)
    for i, (s, e, name) in enumerate(evs):
        leaf = i + 1 == len(evs) or evs[i + 1][0] >= e
        yield name, s, e, leaf


def host_spans(pd):
    """(name, start_ns, end_ns) of every host event."""
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _op_family(name: str) -> str:
    """An op's name without its instance number (``fusion.12`` ->
    ``fusion``), so repeated instances of one kind add up."""
    return re.sub(r"[.\-_]?\d+$", "", name) or name


def reduce_trace(pd, kernels=("paged_attention", "entropy_probe")) -> dict:
    """Everything the per-layer readers need from one trace:

    ``window_s``   length of the traced job (the ``WINDOW_SPAN`` host span)
    ``busy_s``     union of device-op intervals inside it, mean over chips
    ``kernel_s``   {kernel: summed device duration}, ``kernel_calls`` counts
                   (ops whose instruction name starts with the kernel's)
    ``device_ops`` [[op family, seconds]] of the 10 largest leaf-op totals,
                   summed over chips
    ``idle_gaps``  [[host activity, seconds]] of the 10 largest idle totals
    ``n_devices``  device planes read
    """
    spans = list(host_spans(pd))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    t0, t1 = win[0]
    named = [(n[len(HOST_SPAN_PREFIX):], s, e) for n, s, e in spans
             if n.startswith(HOST_SPAN_PREFIX)]
    planes = device_planes(pd)
    if not planes:
        raise ValueError("trace has no device plane")
    busy_total = 0.0
    kernel_s = defaultdict(float)
    kernel_calls = defaultdict(int)
    ops = defaultdict(float)
    gaps = defaultdict(float)
    for plane in planes:
        evs = [e for e in op_events(plane) if e[2] > t0 and e[1] < t1]
        busy = _union(_clip([(s, e) for _, s, e, _ in evs], t0, t1))
        busy_total += sum(e - s for s, e in busy)
        for name, s, e, leaf in evs:
            d = min(e, t1) - max(s, t0)
            if leaf:
                ops[_op_family(name)] += d
            for k in kernels:
                if name.startswith(k):
                    kernel_s[k] += d
                    kernel_calls[k] += 1
        prev = t0
        for s, e in busy + [(t1, t1)]:
            if s > prev:
                owner = (SHORT_GAP if s - prev < SHORT_GAP_NS
                         else _gap_owner(named, prev, s))
                gaps[owner] += s - prev
            prev = max(prev, e)
    n = len(planes)
    top = lambda d: [[k, v * 1e-9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "kernel_s": {k: v * 1e-9 / n for k, v in kernel_s.items()},
        "kernel_calls": {k: v // n for k, v in kernel_calls.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
        "n_devices": n,
    }


def _gap_owner(named, s, e) -> str:
    """The harness span that overlaps the gap [s, e) most; the innermost
    (shortest) one on a tie.  Outside every span: the serve loop."""
    best, best_key = "serve loop (host)", (0, 0)
    for name, a, b in named:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            key = (ov, -(b - a))
            if key > best_key:
                best, best_key = name, key
    return best


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
