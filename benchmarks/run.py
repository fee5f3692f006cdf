# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper artifact (DESIGN.md §9):

  fig1_trajectories   Pass@1 / EAT / #UA trajectories (overthinking evidence)
  fig2_variance_traces V-hat thresholding + unsolvable-question error analysis
  fig3_tradeoff       EAT vs token-budget accuracy-token curves (+AUC, saving)
  fig4_confidence     EAT vs rollout confidence (Yang et al. Eq. 16)
  fig6_ua_overhead    #UA@K sensitivity + true-cost accounting
  fig5_blackbox       proxy monitoring overlap headroom
  fig21_eat_overhead  EAT probe cost vs decode/rollout at growing context
  ablation_alpha      EMA timescale sweep (App. I.3)
  ablation_frequency  evaluation-schedule sweep (App. G)
  kernels_micro       fused entropy kernel vs naive
  roofline            dry-run roofline terms (reads artifacts/dryrun)

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig3,roofline]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODULES = [
    "fig1_trajectories",
    "fig2_variance_traces",
    "fig3_tradeoff",
    "fig4_confidence",
    "fig6_ua_overhead",
    "fig5_blackbox",
    "fig21_eat_overhead",
    "ablation_alpha",
    "ablation_frequency",
    "beyond_giveup",
    "kernels_micro",
    "roofline",
]

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module list")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES

    os.makedirs(ART, exist_ok=True)
    rows: list[tuple[str, float, float]] = []
    results: dict = {}
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            results[name] = mod.run(rows)
            status = "ok"
        except Exception as e:  # noqa: BLE001
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            traceback.print_exc()
            status = "ERROR"
        print(f"# {name}: {status} ({time.time()-t0:.1f}s)", file=sys.stderr)

    with open(os.path.join(ART, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=2, default=str)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived:.6g}")


if __name__ == "__main__":
    main()
