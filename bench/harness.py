"""One cell's run: the engine the launcher builds, set-up, the measured
window of back-to-back jobs, and the traced job.  Nothing here knows a
cell by name: a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<name>.json``) describe everything that differs.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import time
from pathlib import Path

import numpy as np

from bench import traffic as tr
from bench.devtrace import HOST_SPAN_PREFIX, WINDOW_SPAN

#: keys of a configuration file's ``model`` block that the registry holds
#: under another attribute name; every other key is the attribute's own
MODEL_ATTRS = {"head_dim": "resolved_head_dim"}


# ------------------------------------------------------------------ engine
def mesh_devices(cfg: dict) -> int:
    """Devices of the configuration's ``"mesh": "<data>x<model>"``
    (absent: one)."""
    data, model = (int(x) for x in cfg.get("mesh", "1x1").split("x"))
    return data * model


def launcher_argv(cfg: dict, mix: dict) -> list[str]:
    """The ``repro.launch.serve`` command line of a cell's engine."""
    argv = ["--arch", cfg["generator"]["arch"],
            "--mesh", cfg.get("mesh", "1x1"),
            "--requests", str(mix["requests_per_job"]),
            "--batch", str(mix["slots"]), "--budget", str(mix["budget"]),
            "--chunk", str(mix["chunk"]),
            "--eval-every", str(mix["eval_every"]),
            "--delta", repr(float(cfg["delta"])),
            "--alpha", repr(float(cfg["alpha"])),
            "--cache", "paged", "--page-size", str(cfg["page_size"]),
            "--attn-impl", cfg["attn_impl"],
            "--overlap", "on" if cfg["loop"] == "overlap" else "off"]
    if cfg["monitor"] == "proxy":
        argv += ["--monitor", "proxy", "--proxy-config", cfg["proxy"]["arch"]]
    return argv


def check_model(model_cfg, block: dict, what: str) -> None:
    """The program's registry entry must be the configuration file's, key
    by key: a key the registry does not hold is an error, and a nested
    block (such as experts) is checked against the nested entry."""
    for key, want in block.items():
        attr = MODEL_ATTRS.get(key, key)
        if not hasattr(model_cfg, attr):
            raise ValueError(f"{what}: the registry holds no {attr!r} to "
                             f"check the configuration file's {key}={want!r}"
                             f" against")
        got = getattr(model_cfg, attr)
        if isinstance(want, dict):
            check_model(got, want, f"{what}.{key}")
        elif got != want:
            raise ValueError(f"{what}: registry {attr}={got!r} but the "
                             f"configuration file says {key}={want!r}")


def build_engine(cfg: dict, mix: dict):
    """The engine ``repro.launch.serve`` builds for this cell (weights
    made on the device from the launcher's fixed seeds)."""
    from repro.launch import serve

    args = serve.parse_args(launcher_argv(cfg, mix))
    engine = serve.build_engine(args, mix["prompt_width"])
    check_model(engine.model.cfg, cfg["generator"]["model"], "generator")
    if cfg["monitor"] == "proxy":
        check_model(engine.proxy_executor.cfg, cfg["proxy"]["model"], "proxy")
    s = engine.ecfg.sampler
    want = cfg["sampler"]
    if (s.greedy, s.temperature, s.top_p) != (False, want["temperature"],
                                              want["top_p"]):
        raise ValueError(f"engine sampler {s} is not the configuration's "
                         f"{want}")
    return engine


def serve_job(engine, cfg: dict, mix: dict, job: dict, key_seed: int, *,
              max_tokens: int | None = None) -> list[dict]:
    import jax

    return engine.serve(job["prompts"], job["prompt_len"],
                        jax.random.PRNGKey(key_seed),
                        batch_size=mix["slots"], max_tokens=max_tokens,
                        answer_len=mix["answer_len"],
                        overlap=cfg["loop"] == "overlap", record_trace=True)


# ------------------------------------------------------------------ set-up
def bucket_widths(cfg: dict, mix: dict) -> list[int]:
    """Every page-list bucket width a job of this mix can reach: from the
    prompt's pages alone (the batch's first pack), up to a row that runs
    to the budget with the probe tail, the forced answer and (overlapped
    loop) one chunk of slack mapped ahead."""
    ps, granule = cfg["page_size"], 4
    prompt_pages = -(-mix["prompt_width"] // ps)
    slack = mix["chunk"] if cfg["loop"] == "overlap" else 0
    span = mix["budget"] + slack + mix["answer_len"] + 2
    hi = prompt_pages + -(-span // ps) + 1
    lo = prompt_pages
    up = lambda n: -(-n // granule) * granule  # noqa: E731
    return list(range(up(lo), up(hi) + 1, granule))


@contextlib.contextmanager
def forced_bucket(width: int):
    """Every page allocator maps at least ``width`` ranks while the block
    is open, so one short job compiles (or loads) every program of the
    serve loop at that page-list width."""
    from repro.serving.scheduler import PageAllocator

    orig = PageAllocator.bucket_width

    def bucket_width(self, granule: int = 4) -> int:
        return min(max(orig(self, granule), width), self.n_blocks)

    PageAllocator.bucket_width = bucket_width
    try:
        yield
    finally:
        PageAllocator.bucket_width = orig


def warm_up(engine, cfg: dict, mix: dict, counter=None) -> int:
    """Set-up's warm-up, from seed stream 1, never the measured seed.
    First, at every bucket width the mix can reach, one short job of
    ``slots + 1`` requests (so the batch prefill, one admission, the
    forced answer and an EAT evaluation all run at that width).  Then
    one wave of ``slots`` requests at the mix's own budget, so whatever
    the window's exits touch has run once.  With ``counter``, what that
    last job still had to compile is kept in ``counter.warm_names``.
    Returns the jobs run."""
    vocab = cfg["generator"]["model"]["vocab"]
    widths = bucket_widths(cfg, mix)
    for i, w in enumerate(widths):
        job = tr.make_job(mix, vocab, 0, i, stream=1, n=mix["slots"] + 1)
        with forced_bucket(w):
            serve_job(engine, cfg, mix, job, tr.jax_seed(0, i, stream=1),
                      max_tokens=mix["eval_every"] + 1)
    i = len(widths)
    job = tr.make_job(mix, vocab, 0, i, stream=1, n=mix["slots"])
    if counter is not None:
        counter.open = True
    serve_job(engine, cfg, mix, job, tr.jax_seed(0, i, stream=1))
    if counter is not None:
        counter.open = False
        counter.warm_names, counter.names = counter.names, []
    return i + 1


# ------------------------------------------------------------------ window
class CompileCounter:
    """Counts the executables JAX makes while open, compiled or loaded
    from the persistent cache (each records one backend-compile duration
    event through ``jax.monitoring``); a warm window reads 0.  ``names``
    keeps the functions, so a run can say what it compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.names = []
        self.warm_names = []
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    @property
    def count(self) -> int:
        return len(self.names)

    def _duration(self, name, _secs, fun_name="?", **_):
        if self.open and name == self.EVENT:
            self.names.append(str(fun_name))


class CollectorClock:
    """Pauses of Python's cyclic collector (``gc.callbacks``), so a job
    that stalls can be told apart from one the collector held up."""

    def __init__(self):
        self.pauses = []
        self._t = None
        gc.callbacks.append(self._phase)

    def _phase(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None

    def close(self):
        gc.callbacks.remove(self._phase)


def host_spans(engine):
    """Wrap the engine's calls into the program in named host spans for a
    traced job; returns a function that removes them."""
    import jax

    targets = [(engine, ("start", "force_answer"))]
    for ex in (engine.executor, engine.proxy_executor):
        if ex is not None:
            targets.append((ex, ("decode_chunk", "decode_chunk_snapshot",
                                 "prefill", "pack_paged", "admit_paged",
                                 "retract", "retract_lagged",
                                 "put_page_table", "observe_chunk")))
    undo = []
    for obj, names in targets:
        tag = "proxy " if obj is engine.proxy_executor else ""
        for name in names:
            fn = getattr(obj, name, None)
            if fn is None:
                continue

            @functools.wraps(fn)
            def wrapped(*a, _fn=fn, _span=f"{HOST_SPAN_PREFIX}{tag}{name}",
                        **k):
                with jax.profiler.TraceAnnotation(_span):
                    return _fn(*a, **k)

            setattr(obj, name, wrapped)
            undo.append((obj, name))

    def remove():
        for obj, name in undo:
            delattr(obj, name)
    return remove


def run_window(engine, cfg: dict, mix: dict, seed: int, seconds: float, *,
               trace_dir: Path | None = None, counter=None) -> dict:
    """Jobs back to back from ``--seed``; another job starts only while
    the time left is at least the mean job time so far.  With
    ``trace_dir`` the first job is traced whole.  Returns the jobs (wall
    time, host CPU time, collector pauses, results, prompts) and the
    window's wall time."""
    import jax

    vocab = cfg["generator"]["model"]["vocab"]
    jobs = []
    clock = CollectorClock()
    if counter is not None:
        counter.open = True
    t0 = time.perf_counter()
    while True:
        left = seconds - (time.perf_counter() - t0)
        if jobs and left < np.mean([j["seconds"] for j in jobs]):
            break
        i = len(jobs)
        job = tr.make_job(mix, vocab, seed, i)
        key_seed = tr.jax_seed(seed, i)
        traced = trace_dir is not None and i == 0
        if traced:
            remove = host_spans(engine)
            # no Python tracer: it would slow the serve loop's host code
            # and so inflate the idle share it is meant to explain
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        n_gc = len(clock.pauses)
        cj, tj = time.process_time(), time.perf_counter()
        with (jax.profiler.TraceAnnotation(WINDOW_SPAN) if traced
              else contextlib.nullcontext()):
            results = serve_job(engine, cfg, mix, job, key_seed)
        dt = time.perf_counter() - tj
        cpu = time.process_time() - cj
        pauses = clock.pauses[n_gc:]
        if traced:
            jax.profiler.stop_trace()
            remove()
        jobs.append({"seconds": dt, "results": results, "job": job,
                     "traced": traced, "cpu_s": cpu, "gc_s": sum(pauses),
                     "gc_max_s": max(pauses, default=0.0)})
    wall = time.perf_counter() - t0
    clock.close()
    if counter is not None:
        counter.open = False
    return {"jobs": jobs, "wall_s": wall}
