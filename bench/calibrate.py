"""Chip-side calibration for a cell: EAT trajectories for choosing delta,
job times for sizing the mix, a small device-trace fixture, and the
program's and the control's readings of the numbers ``correct`` compares.

  python bench/calibrate.py --workload <cell> --out cal.json \
      [--delta D] [--seeds 1 2 3] [--control] [--fixture PATH]

``--delta`` overrides the configuration's (0 runs every request to the
budget, which records every evaluation's variance).  For each seed the
first job of that seed's window is served and its requests' trajectories
and times are written to ``--out``; with ``--control`` the reference and
the float8 control are then read on each of those jobs, once the served
model is freed, and each side is judged by the run's own rule
(``correct.verdict``) against ``bench/limits/<cell>.json``.  Not run by
the benchmark itself.
"""
from __future__ import annotations

import argparse
import gc
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import correct as cmp  # noqa: E402
from bench import harness  # noqa: E402
from bench import traffic as tr  # noqa: E402
from bench.run import load_cell, unpadded  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--delta", type=float, default=None)
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    root = Path.cwd()
    c = load_cell(root, args.workload)
    cfg, mix = c["cfg"], c["mix"]
    if args.delta is not None:
        cfg = {**cfg, "delta": args.delta}
    sys.path.insert(0, str(root / "src"))
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    t0 = time.perf_counter()
    engine = harness.build_engine(cfg, mix)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_warm = harness.warm_up(engine, cfg, mix)
    t_warm = time.perf_counter() - t0
    print(f"build {t_build:.3f} s; warm-up {n_warm} jobs {t_warm:.3f} s")
    out = {"workload": args.workload, "delta": cfg["delta"],
           "build_s": t_build, "warm_s": t_warm, "seeds": {}}

    if args.fixture:
        from bench import devtrace

        vocab = cfg["generator"]["model"]["vocab"]
        job = tr.make_job(mix, vocab, 0, 0, stream=1, n=mix["slots"])
        tdir = root / ".bench_run" / "fixture"
        shutil.rmtree(tdir, ignore_errors=True)
        remove = harness.host_spans(engine)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            res = harness.serve_job(engine, cfg, mix, job, 7,
                                    max_tokens=mix["eval_every"] + 2)
        jax.profiler.stop_trace()
        remove()
        pb = sorted(tdir.rglob("*.xplane.pb"))[-1]
        Path(args.fixture).parent.mkdir(parents=True, exist_ok=True)
        with open(pb, "rb") as f, gzip.open(args.fixture, "wb") as g:
            g.write(f.read())
        red = devtrace.reduce_trace(devtrace.load(str(pb)))
        out["fixture"] = {"reduction": red,
                          "n_reasoning": [r["n_reasoning"] for r in res],
                          "prompt_len": job["prompt_len"].tolist()}
        print("fixture reduction", json.dumps(red)[:3000])
        shutil.rmtree(tdir, ignore_errors=True)

    served = []
    for seed in args.seeds:
        vocab = cfg["generator"]["model"]["vocab"]
        job = tr.make_job(mix, vocab, seed, 0)
        t0 = time.perf_counter()
        res = harness.serve_job(engine, cfg, mix, job, tr.jax_seed(seed, 0))
        dt = time.perf_counter() - t0
        out["seeds"][seed] = {
            "job_s": dt,
            "requests": [{"P": int(job["prompt_len"][i]),
                          "n": r["n_reasoning"], "exit": r["exit_reason"],
                          "latency_s": r["latency_s"],
                          "trace": r["eat_trace"]}
                         for i, r in enumerate(res)]}
        print(f"seed {seed}: job {dt:.3f} s, n "
              f"{[r['n_reasoning'] for r in res]}")
        served.append((seed, job, res))
    out["memory_peak_bytes"] = int(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    print(f"memory peak {out['memory_peak_bytes']} bytes")
    if args.control:
        # the reference runs once the served model is freed, as in a run
        del engine
        gc.collect()
        from bench import reference as ref

        weights = {"generator": ref.init_weights(
            cfg["generator"]["model"], cfg["generator"]["weights_seed"])}
        if cfg["monitor"] == "proxy":
            weights["proxy"] = ref.init_weights(
                cfg["proxy"]["model"], cfg["proxy"]["weights_seed"])
        for seed, job, res in served:
            finished = [(unpadded(job, i), r) for i, r in enumerate(res)]
            checked = [finished[i] for i in cmp.sample(res, seed)]
            t0 = time.perf_counter()
            nums = cmp.compare(cfg, mix, checked, weights,
                               margin=c["limits"]["eat_var_rel"],
                               control=True)
            out["seeds"][seed]["readings"] = nums
            ok = {side: cmp.verdict(n, c["limits"])[0]
                  for side, n in nums.items()}
            print(f"seed {seed}: readings {json.dumps(nums)}; correct "
                  f"under bench/limits: {ok} "
                  f"({time.perf_counter() - t0:.3f} s)")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
