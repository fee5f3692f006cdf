"""Run one cell of ``BENCHMARK.json`` once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its limits for ``correct`` are in
``bench/limits/<cell>.json``.  Set-up (``setup_s``) runs from process
start to the first request of the window: the compile cache, the engine
with its weights, and the warm-up jobs.  The window runs jobs back to back
for ``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the first job of the window is
traced whole and the result carries the per-layer metrics read from it.
Then the served model is freed and a sample of the finished requests is
checked against the plain reference (``bench/correct.py``).

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  The last line of standard output is the
JSON result; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import correct as cmp  # noqa: E402
from bench import harness  # noqa: E402
from bench import stats  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str, bench: Path = BENCH) -> dict:
    """The cell, its configuration, mix, limits and metric entries — all
    found by the names in ``BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "cfg": load_json(bench / "configs" / f"{cell['config']}.json"),
        "mix": load_json(bench / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(bench / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def metric_reader(name: str, bench: Path = BENCH):
    """``read(rec)`` of ``bench/metrics/<name>.py``, or None."""
    path = bench / "metrics" / f"{name}.py"
    if not path.exists():
        return None
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list[dict], rec: dict, bench: Path = BENCH) -> dict:
    """Each metric from its reader module, else from ``rec["values"]``; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in entries:
        reader = metric_reader(m["name"], bench)
        value = reader(rec) if reader else rec["values"].get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def unpadded(job: dict, i: int):
    L = int(job["prompt_len"][i])
    return job["prompts"][i, job["prompts"].shape[1] - L:]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    result = run(args, Path.cwd())
    if result is None:
        return 2
    print(json.dumps(result))
    return 0


def run(args, root: Path, bench: Path = BENCH, *,
        require_tpu: bool = True) -> dict | None:
    """One run of a cell; the result object, or None when the chip the
    cell needs is not there.  ``require_tpu=False`` lets a test drive the
    rest of a run on the CPU at a tiny size."""
    c = load_cell(root, args.workload, bench)
    cfg, mix, limits, cell = c["cfg"], c["mix"], c["limits"], c["cell"]

    sys.path.insert(0, str(root / "src"))
    import jax

    cache_dir = None
    if require_tpu:
        # every program into the checkout's persistent cache, however
        # quick its compile, so a run after the first compiles nothing
        from repro.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return None
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    counter = harness.CompileCounter()
    engine = harness.build_engine(cfg, mix)
    n_warm = harness.warm_up(engine, cfg, mix, counter)
    # set-up's heap out of the collector's reach, so no collection in the
    # window walks it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s:.3f} s ({n_warm} warm-up jobs; the last, at "
          f"the mix's budget, compiled {counter.warm_names})")

    trace_dir = None
    if args.trace:
        trace_dir = root / ".bench_run" / f"trace-{args.workload}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = harness.run_window(engine, cfg, mix, args.seed, args.seconds,
                             trace_dir=trace_dir, counter=counter)
    jobs = win["jobs"]
    used = devices[:cell["chips"]]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    e2e = stats.window_rates(jobs)
    print(f"window: {len(jobs)} jobs, {e2e['requests']} requests, "
          f"{e2e['tokens']} tokens in {e2e['seconds']:.3f} s of jobs; "
          f"wall {win['wall_s']:.3f} s, overran --seconds by "
          f"{win['wall_s'] - args.seconds:.3f} s; "
          f"compiles in window {counter.count} {counter.names}; job "
          f"seconds {[round(j['seconds'], 3) for j in jobs]}, host CPU "
          f"seconds {[round(j['cpu_s'], 3) for j in jobs]}, collector "
          f"seconds {[round(j['gc_s'], 4) for j in jobs]} (longest pause "
          f"{max(j['gc_max_s'] for j in jobs):.4f} s)")
    results = [r for j in jobs for r in j["results"]]
    finished = [(unpadded(j["job"], i), r) for j in jobs
                for i, r in enumerate(j["results"])]
    failed = sum(r["status"] not in ("exited", "exhausted") for r in results)
    exits = {}
    for r in results:
        exits[r["exit_reason"]] = exits.get(r["exit_reason"], 0) + 1
    print(f"exits: {exits}; n_reasoning "
          f"{sorted({r['n_reasoning'] for r in results})}")

    rec = {"cfg": cfg, "mix": mix, "peaks": peaks, "jobs": jobs,
           "compiles": counter.count,
           "values": {**e2e, "setup_s": setup_s}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {}
    if args.trace:
        from bench import devtrace

        t_red = time.perf_counter()
        pb = sorted(trace_dir.rglob("*.xplane.pb"))
        rec["trace"] = devtrace.reduce_trace(devtrace.load(str(pb[-1])))
        print(f"trace: {pb[-1].stat().st_size} bytes reduced in "
              f"{time.perf_counter() - t_red:.3f} s")
        rec["traced"] = next(j for j in jobs if j["traced"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        metrics = read_metrics(c["per_layer"], rec, bench)
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    else:
        metrics = read_metrics(c["end_to_end"], rec, bench)

    # the reference runs once the served model's state is freed
    del engine
    gc.collect()
    from bench import reference as ref

    t_ref = time.perf_counter()
    weights = {"generator": ref.init_weights(cfg["generator"]["model"],
                                             cfg["generator"]["weights_seed"])}
    if cfg["monitor"] == "proxy":
        weights["proxy"] = ref.init_weights(cfg["proxy"]["model"],
                                            cfg["proxy"]["weights_seed"])
    checked = [finished[i] for i in cmp.sample(results, args.seed)]
    nums = cmp.compare(cfg, mix, checked, weights,
                       margin=limits["eat_var_rel"])["program"]
    print(f"reference check: {len(checked)} requests, "
          f"{sum(len(r['reasoning_tokens']) for _, r in checked)} served "
          f"reasoning tokens, {time.perf_counter() - t_ref:.3f} s; "
          f"{nums['ambiguous']} ambiguous exits")
    correct, compared = cmp.verdict(nums, limits, failed)
    for k, v in compared.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)

    return {"correct": correct, "attempted": len(results), "failed": failed,
            "metrics": metrics, "device": device, **out,
            "compared": compared}


if __name__ == "__main__":
    sys.exit(main())
