"""Engine decode throughput: per-token host loop vs device-resident chunks,
the data-parallel serve() scaling sweep, and the ring-vs-paged KV cache A/B.

The per-token path dispatches one jitted step per token and syncs the host
twice per iteration (``active.any()``, ``n_reasoning.max()``); the chunked
path runs a ``lax.while_loop`` of up to ``chunk_len`` monitored steps per
dispatch and syncs once per chunk.  Same tiny model, same sampler, same
EAT monitor — the measured delta is pure dispatch + sync overhead, i.e.
exactly what the probe-kernel work cannot recover from a host-bound loop.

``--scaling`` runs the continuous-batching ``serve()`` loop on (N x 1)
data-parallel meshes of 1/2/4/8 simulated host devices (one subprocess per
device count — the device count is fixed at process start) and emits
``BENCH_serve_scaling.json`` — throughput plus per-request latency
p50/p95/p99.  ``--overlap on`` serves through the double-buffered pipeline
(``serve(overlap=True)``): one blocking snapshot read per chunk boundary
instead of one sync per host-facing scalar.  The sweep runs on the CPU
only: every child is started with ``JAX_PLATFORMS=cpu``, so it never
contends for an accelerator, and its numbers are sharding/dispatch
overheads of XLA's CPU backend, not a device measurement.

``--cache {ring,paged,both}`` runs the mixed-exit-length serving workload
(temperature sampling — sequences exit via a naturally sampled </think> at
geometrically distributed lengths, or at the budget) under a FIXED physical
KV-slot budget.  The ring spends it as ``batch * capacity`` dense slots, so
the batch-lifetime capacity rule caps how many requests one batch may
legally serve; the paged cache spends the same slots as a shared page pool,
reclaims an exiting request's pages mid-batch, and admits the whole queue.
``both`` emits ``artifacts/BENCH_paged_cache.json`` (requests-served and
tok/s per backend — docs/serving.md §Choosing a cache backend).

``--attn`` runs the dense-gather vs page-native decode-attention A/B
(docs/serving.md §Page-native attention): per-token decode cost at a FIXED
occupancy (live mapped slots per row) across a logical-capacity sweep — the
``num_pages`` pool grows ring-equivalently with capacity while the live
tokens do not.  The gather path materializes the (B, capacity) logical view
every step, so its per-token cost scales with the sweep; the page-native
path reads only the mapped pages through the compacted page list, so its
cost stays flat at equal occupancy.  Emits
``artifacts/BENCH_paged_attn.json``.

``--monitor proxy`` runs the self-EAT vs black-box proxy-EAT serving A/B
(docs/serving.md §Black-box monitoring) on a mixed-exit greedy workload
(delta auto-calibrated to the median first-evaluation variance, so part of
the queue exits via EAT and part runs to budget).  A same-params proxy
pins tokens-saved parity with self-EAT (per-request exit steps within ±1 —
bit-equal in practice) and the generator-side probe-program count (0, the
black-box contract); the probe-FLOPs ratio of a genuinely small proxy
(``--proxy-arch``, default tiny-proxy) vs the generator quantifies the
monitoring discount.  Emits ``artifacts/BENCH_proxy_serve.json``.

Run:  PYTHONPATH=src python benchmarks/engine_throughput.py
      [--batch 8] [--budget 96] [--chunks 1 8 32] [--out artifacts/...json]
      [--scaling] [--devices-list 1 2 4 8] [--overlap on]
      [--cache both] [--requests 32] [--page-size 16]
      [--monitor proxy] [--proxy-arch tiny-proxy]
"""
import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.eat import make_probe
from repro.core.monitor import ReasoningMonitor
from repro.core.stopping import EATStopper
from repro.data.synthetic import ChainTask, Tokens
from repro.models import Model
from repro.serving.cache import CacheConfig, page_align
from repro.serving.engine import EngineConfig, ReasoningEngine
from repro.serving.sampler import SamplerConfig
from repro.utils.compile_cache import enable_compile_cache


def write_json(path: str, rec: dict) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)


def build_engine(budget: int, ctx=None, capacity=None,
                 cache: CacheConfig | None = None) -> ReasoningEngine:
    cfg = get_config("tiny")
    model = Model(cfg, attn_impl="xla") if ctx is None else \
        Model(cfg, ctx, attn_impl="xla")
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        max_reasoning_tokens=budget,
        capacity=capacity if capacity is not None else max(256, budget + 64),
        pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
        newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS,
        sampler=SamplerConfig(temperature=1.0, top_p=0.95),
        cache=cache or CacheConfig(),
    )
    # delta=0 -> the monitor runs (probe + EMA at every paragraph break)
    # but never fires, so both paths decode the full budget: equal work.
    monitor = ReasoningMonitor(
        stopper=EATStopper(alpha=0.2, delta=0.0),
        probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
        newline_id=Tokens.NEWLINE,
    )
    return ReasoningEngine(model, params, ecfg, monitor)


def measure(run, engine, batch, budget: int, reps: int) -> tuple[float, int]:
    """Median wall seconds + tokens generated for ``run(state)``."""
    times, tokens = [], 0
    for rep in range(reps + 1):        # rep 0 = compile warmup
        st = engine.start(jnp.asarray(batch["prompts"]),
                          jnp.asarray(batch["prompt_len"]),
                          jax.random.PRNGKey(100 + rep))
        jax.block_until_ready(st.cache["pos"])
        t0 = time.perf_counter()
        st = run(st)
        jax.block_until_ready(st.out_tokens)
        if rep:
            times.append(time.perf_counter() - t0)
            tokens = int(np.asarray(st.n_reasoning).sum())
    return float(np.median(times)), tokens


def run_serve_child(devices: int, batch_per_dev: int, budget: int,
                    reps: int, overlap: bool = False) -> dict:
    """One point of the DP scaling sweep, inside a process whose device
    count was fixed by XLA_FLAGS: weak scaling — global batch =
    ``batch_per_dev * devices`` slots on an (N x 1) data-parallel mesh,
    2x-oversubscribed request queue through ``serve()``.  ``overlap``
    runs the double-buffered pipeline (one host read per boundary instead
    of one per host-facing scalar); per-request latency percentiles come
    from the ``latency_s`` each result now carries."""
    from repro.launch.mesh import make_device_ctx
    from repro.serving.scheduler import SlotScheduler

    assert len(jax.devices()) == devices, jax.devices()
    B = batch_per_dev * devices
    n_req = 2 * B
    batch = ChainTask().serve_batch(np.random.default_rng(0), n_req)
    capacity = SlotScheduler.required_capacity(
        batch["prompts"].shape[1], n_req, B, budget
    )
    if overlap:
        # the overlapped loop's ring guard adds one in-flight chunk to its
        # host-mirror pointer estimate — give it that headroom
        capacity += EngineConfig.chunk_len
    engine = build_engine(budget, ctx=make_device_ctx(devices, 1),
                          capacity=capacity)

    times, tokens, lat = [], 0, []
    for rep in range(reps + 1):        # rep 0 = compile warmup
        t0 = time.perf_counter()
        results = engine.serve(batch["prompts"], batch["prompt_len"],
                               jax.random.PRNGKey(100 + rep), batch_size=B,
                               max_tokens=budget, overlap=overlap)
        if rep:
            times.append(time.perf_counter() - t0)
            tokens = int(sum(r["n_reasoning"] for r in results))
            lat += [r["latency_s"] for r in results]
    sec = float(np.median(times))
    p50, p95, p99 = (float(np.percentile(lat, q)) for q in (50, 95, 99))
    return {"devices": devices, "batch": B, "requests": n_req,
            "budget": budget, "overlap": overlap, "seconds": sec,
            "tokens": tokens, "tokens_per_s": tokens / sec,
            "latency_s": {"p50": p50, "p95": p95, "p99": p99}}


def run_cache_bench(args) -> dict:
    """Ring vs paged serve() under ONE physical KV-slot budget.

    Workload: ``--requests`` prompts through ``--batch`` slots, temperature
    sampling (mixed exit lengths: natural </think> at geometric lengths or
    the budget).  The physical budget is ``batch * C_ring`` dense slots
    where ``C_ring = S + 2*budget`` — enough ring capacity for roughly one
    recycled cohort.  The ring may only admit the queue prefix whose
    batch-lifetime fits that capacity (``required_capacity``); the paged
    backend spends the same slots as a shared pool and serves everything,
    reusing exited requests' pages mid-batch.
    """
    from repro.serving.scheduler import SlotScheduler

    task = ChainTask()
    B, budget, ps = args.batch, args.budget, args.page_size
    n_req = args.requests or 4 * B
    batch = task.serve_batch(np.random.default_rng(0), n_req)
    S = batch["prompts"].shape[1]
    C_ring = page_align(S + 2 * budget, ps)
    phys_slots = B * C_ring                           # THE memory budget

    # ring: largest queue prefix whose batch lifetime fits C_ring
    k_ring = n_req
    while k_ring > 1 and SlotScheduler.required_capacity(
            S, k_ring, B, budget) > C_ring:
        k_ring -= 1
    # paged: logical capacity covers the whole queue (int32 metadata —
    # cheap); the PHYSICAL pool is the same phys_slots budget
    C_log = page_align(SlotScheduler.required_capacity(S, n_req, B, budget),
                       ps)
    variants = {
        "ring": dict(n=k_ring, capacity=C_ring, cache=CacheConfig()),
        "paged": dict(n=n_req, capacity=C_log,
                      cache=CacheConfig(kind="paged", page_size=ps,
                                        num_pages=phys_slots // ps + 1)),
    }

    rec = {"workload": "mixed_exit_serve", "batch": B, "budget": budget,
           "requests_queued": n_req, "physical_kv_slots": phys_slots,
           "page_size": ps}
    for kind in (("ring", "paged") if args.cache == "both" else (args.cache,)):
        v = variants[kind]
        engine = build_engine(budget, capacity=v["capacity"], cache=v["cache"])
        times, tokens = [], 0
        for rep in range(args.reps + 1):              # rep 0 = warmup
            t0 = time.perf_counter()
            # ONE key for every rep: temperature sampling means the exit
            # lengths (and so the token count) depend on the key — a
            # per-rep key would divide one rep's tokens by another rep's
            # median seconds
            results = engine.serve(
                batch["prompts"][:v["n"]], batch["prompt_len"][:v["n"]],
                jax.random.PRNGKey(100), batch_size=B,
                max_tokens=budget,
            )
            if rep:
                times.append(time.perf_counter() - t0)
                tokens = int(sum(r["n_reasoning"] for r in results))
        sec = float(np.median(times))
        rec[kind] = {
            "requests_served": v["n"], "capacity": v["capacity"],
            "seconds": sec, "tokens": tokens, "tokens_per_s": tokens / sec,
        }
        print(f"{kind:>6s}: served {v['n']:3d}/{n_req} requests  "
              f"{tokens:6d} tok  {tokens / sec:8.0f} tok/s", flush=True)

    if args.cache == "both":
        rec["paged_admits_more"] = (rec["paged"]["requests_served"]
                                    > rec["ring"]["requests_served"])
        path = args.out or os.path.join(
            os.path.dirname(__file__), "..", "artifacts",
            "BENCH_paged_cache.json")
        write_json(path, rec)
        print(f"wrote {os.path.normpath(path)}")
    return rec


def run_attn_bench(args) -> dict:
    """Dense-gather vs page-native decode attention: per-token cost vs
    logical capacity at fixed occupancy.

    For each capacity in the sweep, both engines hold the SAME live state:
    ``--attn-occupancy`` mapped slots per row of a ``--batch``-row paged
    cache whose physical pool is sized to that occupancy and HELD FIXED —
    the sweep grows only the logical capacity (the batch-lifetime bound a
    longer request queue needs; int32 metadata plus, for the gather path,
    the materialized logical view).  The timed program is the DONATING
    unmonitored ``decode_chunk``
    — the actual serving hot path, pools aliased in place (the non-donating
    ``decode_step`` would copy the whole pool every call and swamp the
    attention delta) — with identical sampling/bookkeeping either way, so
    the measured delta is the attention read: gather cost ~ capacity,
    page-native cost ~ mapped pages.  ``end_think_id`` is parked on an
    unreachable id so every row decodes the full chunk.
    """
    from repro.serving.cache import alloc_paged_template
    from repro.serving.scheduler import PageAllocator

    task = ChainTask()
    B, ps = args.batch, args.page_size
    batch = task.serve_batch(np.random.default_rng(0), B)
    S = batch["prompts"].shape[1]
    # occupancy must cover the prompt + every decoded token of the timing
    # run (writing into an unmapped page would attend trash), and stays
    # FIXED across the capacity sweep
    decoded = (args.reps + 1) * args.attn_iters
    occ = page_align(max(args.attn_occupancy, S + decoded + ps), ps)
    too_small = [c for c in args.attn_capacities if page_align(c, ps) < occ]
    if too_small:
        # a capacity below the occupancy would silently clamp the mapped
        # span and wrap the ring mid-timing — the fixed-occupancy premise
        # (and so the whole A/B) would be false for those points
        raise SystemExit(
            f"--attn-capacities {too_small} are smaller than the {occ}-slot "
            f"occupancy this timing run needs (prompt {S} + "
            f"(reps+1)*iters {decoded} decoded tokens, page-aligned); "
            f"raise them or lower --reps / --attn-iters / --attn-occupancy")

    def decode_state(engine):
        """The serve()-paged setup at a pinned occupancy: prompt prefill,
        ``occ`` slots of mapped pages per row, packed into the pool."""
        ecfg = engine.ecfg
        ccfg = ecfg.cache
        C_log = page_align(ecfg.capacity, ps)
        n_blocks = C_log // ps
        # pool sized to the LIVE tokens, constant across the sweep — the
        # whole point of paging: physical footprint tracks occupancy, not
        # the logical bound
        num_pages = B * (occ // ps) + 1
        alloc = PageAllocator(num_pages, ps, n_blocks, B)
        st = engine.start(jnp.asarray(batch["prompts"]),
                          jnp.asarray(batch["prompt_len"]),
                          jax.random.PRNGKey(0), capacity=page_align(S, ps))
        for row in range(B):
            alloc.ensure(row, 0, occ - 1)
        template = alloc_paged_template(
            engine.model.cfg, B, C_log, ps, num_pages, alloc=alloc,
            native=ccfg.attn_impl != "gather")
        st = st._replace(cache=engine.executor.pack_paged(
            template, st.cache, alloc.table))
        return st, num_pages

    def time_decode(engine, st) -> float:
        iters = args.attn_iters
        budget = jnp.asarray(1 << 30, jnp.int32)
        chunk = jnp.asarray(iters, jnp.int32)
        # decode_chunk DONATES st: continue from the returned state
        st = engine.executor.decode_chunk(engine.params, st, budget, chunk,
                                          use_monitor=False)    # warmup
        jax.block_until_ready(st.out_tokens)
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            st = engine.executor.decode_chunk(engine.params, st, budget,
                                              chunk, use_monitor=False)
            jax.block_until_ready(st.out_tokens)
            times.append((time.perf_counter() - t0) / iters)
        return float(np.median(times))

    points = []
    for cap in args.attn_capacities:
        point = {"capacity": int(page_align(cap, ps))}
        for label, impl in (("gather", "gather"), ("page_native", "xla")):
            engine = build_engine(
                args.budget, capacity=cap,
                cache=CacheConfig(kind="paged", page_size=ps,
                                  attn_impl=impl))
            # no row may stop mid-chunk: park </think> on an unreachable id
            # (programs are built lazily, after this)
            engine.ecfg.end_think_id = -7
            st, num_pages = decode_state(engine)
            point["num_pages"] = num_pages
            point[label + "_s_per_tok"] = time_decode(engine, st)
        points.append(point)
        print(f"capacity={point['capacity']:5d}  pages={point['num_pages']:5d}  "
              f"gather={point['gather_s_per_tok'] * 1e3:7.2f} ms/tok  "
              f"page-native={point['page_native_s_per_tok'] * 1e3:7.2f} ms/tok",
              flush=True)

    g = [p["gather_s_per_tok"] for p in points]
    n = [p["page_native_s_per_tok"] for p in points]
    rec = {
        "workload": "decode_cost_vs_logical_capacity", "batch": B,
        "page_size": ps, "occupancy_slots": int(occ),
        "capacities": [p["capacity"] for p in points], "points": points,
        # the acceptance shape: gather grows across the sweep, page-native
        # stays flat at equal occupancy
        "gather_cost_growth": g[-1] / g[0],
        "page_native_cost_growth": n[-1] / n[0],
        "page_native_flat": n[-1] / n[0] < (g[-1] / g[0]) / 2,
    }
    path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "artifacts",
        "BENCH_paged_attn.json")
    write_json(path, rec)
    print(f"gather grows {rec['gather_cost_growth']:.2f}x over the sweep; "
          f"page-native {rec['page_native_cost_growth']:.2f}x "
          f"(flat={rec['page_native_flat']})")
    print(f"wrote {os.path.normpath(path)}")
    return rec


def run_proxy_bench(args) -> dict:
    """Self-EAT vs black-box proxy-EAT serving A/B on one mixed-exit greedy
    workload (paper Fig. 5 through the serving stack).

    A same-params proxy must save the same tokens as self-EAT (the exit
    decisions are bit-equal under greedy sampling — tests/test_proxy_serve
    pins the exact equality; the artifact reports the ±1-step parity
    check), while the generator executor builds zero probe programs.  The
    probe-FLOPs ratio of the small ``--proxy-arch`` model vs the generator
    is the black-box monitoring discount: what an EAT evaluation costs when
    a cheap local model pays for it instead of the big one.
    """
    from repro.core.eat import eval_eat
    from repro.serving.cache import alloc_cache
    from repro.serving.proxy import ProxyConfig
    from repro.serving.scheduler import SlotScheduler
    from repro.utils.jax_compat import cost_analysis_dict

    task = ChainTask()
    B, budget = args.batch, args.budget
    n_req = args.requests or 2 * B
    batch = task.serve_batch(np.random.default_rng(0), n_req)
    S = batch["prompts"].shape[1]
    # one extra budget of ring slack: the proxy-mode generator decodes to
    # the chunk boundary before a retract lands, so its ring pointer can
    # outrun the self-EAT run by up to chunk_len per exit
    capacity = SlotScheduler.required_capacity(S, n_req, B, budget) + budget

    cfg = get_config("tiny")
    model = Model(cfg, attn_impl="xla")
    params = model.init(jax.random.PRNGKey(0))
    probe = make_probe(Tokens.END_THINK, (Tokens.ANS,))

    def make(delta, proxy=None):
        ecfg = EngineConfig(
            max_reasoning_tokens=budget, capacity=capacity,
            pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
            newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS, chunk_len=8,
            sampler=SamplerConfig(greedy=True),
        )
        monitor = ReasoningMonitor(
            stopper=EATStopper(alpha=0.2, delta=delta), probe=probe,
            schedule="every_n", every_n=8, min_evals=1,
        )
        return ReasoningEngine(model, params, ecfg, monitor, proxy=proxy)

    # calibrate delta to the median of each request's LOWEST EMA variance
    # (a delta=0 dry run records the full trajectories): requests whose
    # variance dips below it exit via EAT, the rest run to budget or end
    # naturally — a genuinely mixed-exit workload, still greedy (=>
    # deterministic, parity-comparable between monitor tiers)
    cal = make(0.0).serve(batch["prompts"], batch["prompt_len"],
                          jax.random.PRNGKey(100), batch_size=B,
                          max_tokens=budget, record_trace=True)
    min_vars = [min((v for (_, e, v) in r["eat_trace"] if e >= 1),
                    default=None) for r in cal]
    delta = float(np.median([v for v in min_vars if v is not None]))

    def run(proxy):
        engine = make(delta, proxy=proxy)
        times = []
        for rep in range(args.reps + 1):              # rep 0 = warmup
            t0 = time.perf_counter()
            results = engine.serve(batch["prompts"], batch["prompt_len"],
                                   jax.random.PRNGKey(100), batch_size=B,
                                   max_tokens=budget)
            if rep:
                times.append(time.perf_counter() - t0)
        sec = float(np.median(times))
        steps = [r["n_reasoning"] for r in results]
        reasons = {}
        for r in results:
            reasons[r["exit_reason"]] = reasons.get(r["exit_reason"], 0) + 1
        return engine, {
            "seconds": sec, "tokens": int(sum(steps)),
            "tokens_per_s": sum(steps) / sec,
            "tokens_saved_vs_budget": int(n_req * budget - sum(steps)),
            "exit_steps": steps, "exit_reasons": reasons,
        }

    eng_self, rec_self = run(None)
    eng_proxy, rec_proxy = run(ProxyConfig(model=model, params=params))
    step_deltas = [abs(a - b) for a, b in zip(rec_self["exit_steps"],
                                              rec_proxy["exit_steps"])]
    gen_probe_programs = len(
        [k for k in eng_proxy.executor._programs
         if k[0] == "probe" or (k[0] == "chunk" and k[2])])

    def probe_flops(cfg_name):
        c = get_config(cfg_name)
        m = Model(c, attn_impl="xla")
        p = m.init(jax.random.PRNGKey(0))
        cache = alloc_cache(c, B, capacity)
        fn = jax.jit(lambda pp, cc, np_: eval_eat(m, pp, cc, probe, np_))
        comp = fn.lower(p, cache, jnp.zeros((B,), jnp.int32)).compile()
        return float(cost_analysis_dict(comp).get("flops", 0.0))

    f_self, f_small = probe_flops("tiny"), probe_flops(args.proxy_arch)
    rec = {
        "workload": "mixed_exit_proxy_serve", "batch": B, "budget": budget,
        "requests": n_req, "delta": delta,
        "self": rec_self, "proxy": rec_proxy,
        "exit_step_max_delta": int(max(step_deltas, default=0)),
        "tokens_saved_parity": max(step_deltas, default=0) <= 1,
        "generator_probe_programs": gen_probe_programs,
        "probe_flops": {"generator": f_self, "proxy_arch": args.proxy_arch,
                        "proxy": f_small,
                        "ratio": f_small / f_self if f_self else None},
    }
    for mode in ("self", "proxy"):
        r = rec[mode]
        print(f"{mode:>6s}: {r['tokens']:6d} tok "
              f"(saved {r['tokens_saved_vs_budget']:5d} vs budget)  "
              f"{r['tokens_per_s']:8.0f} tok/s  exits={r['exit_reasons']}",
              flush=True)
    ratio = rec["probe_flops"]["ratio"]
    print(f"exit-step max delta: {rec['exit_step_max_delta']}  "
          f"generator probe programs: {gen_probe_programs}  "
          f"probe-FLOPs ratio ({args.proxy_arch}/tiny): "
          + (f"{ratio:.3f}" if ratio is not None else "n/a"))
    path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "artifacts",
        "BENCH_proxy_serve.json")
    write_json(path, rec)
    print(f"wrote {os.path.normpath(path)}")
    return rec


def run_scaling_sweep(args) -> dict:
    """Fan the sweep out one subprocess per (device count, loop mode) and
    collect ``BENCH_serve_scaling.json``.  The simulated device count is
    fixed at jax import, hence the subprocesses.  With ``--overlap on``
    every device count runs BOTH loops — the synchronous boundary loop and
    the double-buffered pipeline — so the artifact carries the A/B
    side by side instead of a lone overlap curve with no reference."""
    def child(n, overlap_mode):
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        )
        cmd = [sys.executable, os.path.abspath(__file__), "--serve-child",
               str(n), "--batch", str(args.batch),
               "--budget", str(args.budget), "--reps", str(args.reps),
               "--overlap", overlap_mode]
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=1200)
        if r.returncode != 0:
            raise RuntimeError(f"scaling child devices={n} failed:\n"
                               f"{r.stdout}\n{r.stderr}")
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("SCALING_RESULT ")][-1]
        return json.loads(line[len("SCALING_RESULT "):])

    modes = ["off", "on"] if args.overlap == "on" else ["off"]
    points = []
    for n in args.devices_list:
        for mode in modes:
            rec = child(n, mode)
            points.append(rec)
            tag = "overlap" if rec["overlap"] else "sync   "
            print(f"devices={rec['devices']:2d}  batch={rec['batch']:3d}  "
                  f"{tag}  {rec['tokens_per_s']:8.0f} tok/s  "
                  f"p50={rec['latency_s']['p50']:6.2f}s "
                  f"p99={rec['latency_s']['p99']:6.2f}s", flush=True)
    # per-mode speedup curve: baseline = that mode's true 1-device point
    # when the sweep includes it; else its smallest device count (and the
    # key says so)
    for ov in sorted({p["overlap"] for p in points}):
        grp = [p for p in points if p["overlap"] == ov]
        base_pt = next((p for p in grp if p["devices"] == 1),
                       min(grp, key=lambda p: p["devices"]))
        key = ("speedup_vs_1dev" if base_pt["devices"] == 1
               else f"speedup_vs_{base_pt['devices']}dev")
        for p in grp:
            p[key] = p["tokens_per_s"] / base_pt["tokens_per_s"]
            tag = "overlap" if ov else "sync   "
            print(f"devices={p['devices']:2d}  {tag}  {key}={p[key]:5.2f}x",
                  flush=True)
    if len(modes) == 2:
        # overlap-vs-sync ratio at each device count — the honest A/B
        for n in args.devices_list:
            s = next(p for p in points
                     if p["devices"] == n and not p["overlap"])
            o = next(p for p in points if p["devices"] == n and p["overlap"])
            o["overlap_vs_sync"] = o["tokens_per_s"] / s["tokens_per_s"]
            print(f"devices={n:2d}  overlap_vs_sync="
                  f"{o['overlap_vs_sync']:5.2f}x", flush=True)
    out = {"sweep": "serve_dp_weak_scaling", "batch_per_device": args.batch,
           "budget": args.budget, "overlap": args.overlap == "on",
           "points": points}
    path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "artifacts",
        "BENCH_serve_scaling.json")
    write_json(path, out)
    print(f"wrote {os.path.normpath(path)}")
    return out


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--budget", type=int, default=96)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--chunks", type=int, nargs="+", default=[1, 8, 32])
    ap.add_argument("--out", default=None)
    ap.add_argument("--scaling", action="store_true",
                    help="run the data-parallel serve() scaling sweep over "
                         "--devices-list simulated host devices")
    ap.add_argument("--devices-list", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--overlap", choices=["off", "on"], default="off",
                    help="--scaling: serve through the double-buffered "
                         "pipeline (serve(overlap=True)) instead of the "
                         "synchronous chunk-boundary loop")
    ap.add_argument("--cache", choices=["ring", "paged", "both"], default=None,
                    help="run the ring-vs-paged KV cache serve() A/B on the "
                         "mixed-exit workload ('both' writes "
                         "artifacts/BENCH_paged_cache.json)")
    ap.add_argument("--requests", type=int, default=0,
                    help="--cache workload queue length (0 = 4 * --batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--cache paged backend page size (logical slots)")
    ap.add_argument("--attn", action="store_true",
                    help="run the dense-gather vs page-native decode-"
                         "attention A/B across a logical-capacity sweep "
                         "(writes artifacts/BENCH_paged_attn.json)")
    ap.add_argument("--attn-capacities", type=int, nargs="+",
                    default=[256, 512, 1024, 2048, 4096],
                    help="--attn: logical capacities to sweep")
    ap.add_argument("--attn-occupancy", type=int, default=64,
                    help="--attn: live mapped slots per row (held fixed "
                         "across the sweep)")
    ap.add_argument("--attn-iters", type=int, default=16,
                    help="--attn: decode steps per timing sample")
    ap.add_argument("--monitor", choices=["proxy"], default=None,
                    help="run the self-EAT vs black-box proxy-EAT serve() "
                         "A/B (writes artifacts/BENCH_proxy_serve.json)")
    ap.add_argument("--proxy-arch", default="tiny-proxy",
                    help="--monitor proxy: small-proxy architecture for the "
                         "probe-FLOPs ratio")
    ap.add_argument("--serve-child", type=int, default=0,
                    help=argparse.SUPPRESS)   # internal: one sweep point
    args = ap.parse_args()

    if args.reps < 1:
        # every path medians over the timed reps: zero reps would write
        # NaN seconds/tok/s into the artifact without erroring
        ap.error("--reps must be >= 1 (rep 0 is compile warmup)")
    modes = [m for m, on in (("--monitor proxy", args.monitor),
                             ("--cache", args.cache),
                             ("--scaling", args.scaling),
                             ("--attn", args.attn)) if on]
    if len(modes) > 1:
        # each mode is its own A/B with its own artifact — running one
        # silently while another flag is set hides the un-run benchmark
        ap.error(f"{' and '.join(modes)} are standalone A/Bs; run them "
                 f"separately")
    if args.overlap == "on" and not (args.scaling or args.serve_child):
        ap.error("--overlap applies to the --scaling serve sweep")

    if args.serve_child:
        rec = run_serve_child(args.serve_child, args.batch, args.budget,
                              args.reps, overlap=args.overlap == "on")
        print("SCALING_RESULT " + json.dumps(rec))
        return rec
    if args.scaling:
        return run_scaling_sweep(args)
    if args.cache:
        return run_cache_bench(args)
    if args.attn:
        return run_attn_bench(args)
    if args.monitor == "proxy":
        return run_proxy_bench(args)

    engine = build_engine(args.budget)
    batch = ChainTask().serve_batch(np.random.default_rng(0), args.batch)

    t_host, tok = measure(
        lambda st: engine._reason_per_token(st, max_tokens=args.budget),
        engine, batch, args.budget, args.reps,
    )
    base_tps = tok / t_host
    print(f"{'per-token host loop':>22s}: {t_host * 1e3:8.1f} ms  "
          f"{base_tps:8.0f} tok/s")

    rec = {"batch": args.batch, "budget": args.budget,
           "per_token": {"seconds": t_host, "tokens_per_s": base_tps},
           "chunked": {}}
    for chunk in args.chunks:
        t, tok = measure(
            lambda st: engine.reason(st, max_tokens=args.budget,
                                     chunk_len=chunk),
            engine, batch, args.budget, args.reps,
        )
        tps = tok / t
        rec["chunked"][chunk] = {"seconds": t, "tokens_per_s": tps,
                                 "speedup": tps / base_tps}
        print(f"{'chunked (len=%d)' % chunk:>22s}: {t * 1e3:8.1f} ms  "
              f"{tps:8.0f} tok/s   {tps / base_tps:5.2f}x")

    if args.out:
        write_json(args.out, rec)
    return rec


if __name__ == "__main__":
    main()
