"""Roofline analysis over the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) record, derive the three roofline terms on
TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute_s    = flops_per_device / PEAK_FLOPS
  memory_s     = bytes_per_device / HBM_BW
  collective_s = collective_bytes_per_device / ICI_BW

All quantities are per-device (the compiled module is the per-partition
program; dividing global totals by chip count is equivalent).  The dominant
term is the bottleneck; MODEL_FLOPS = 6*N*D (dense; N_active for MoE) gives
the useful-compute ratio that catches remat/dispatch waste.

Writes artifacts/roofline.csv and the markdown table EXPERIMENTS.md embeds.
"""
from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (per-chip collective budget)

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def tokens_processed(shape_kind: str, seq_len: int, global_batch: int,
                     probe_len: int = 2) -> int:
    if shape_kind == "train":
        return seq_len * global_batch
    if shape_kind == "prefill":
        return seq_len * global_batch
    # decode serve_step: 1 decode token + probe positions per sequence
    return global_batch * (1 + probe_len)


def model_flops(rec: dict, shapes: dict) -> float:
    """6*N*D per step (3x forward-backward for train; 2*N*D forward-only
    for serving steps)."""
    sh = shapes[rec["shape"]]
    n_active = rec["param_count_active"]
    toks = tokens_processed(rec["kind"], sh.seq_len, sh.global_batch)
    mult = 6.0 if rec["kind"] == "train" else 2.0
    return mult * n_active * toks


def analyze(rec: dict, shapes: dict, chips: int) -> dict:
    compute_s = rec["flops_per_device"] / PEAK_FLOPS
    memory_s = rec["bytes_accessed_per_device"] / HBM_BW
    coll_s = rec["collectives"]["total"] / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec, shapes)
    hlo_global = rec["flops_per_device"] * chips
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "kind": rec["kind"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "bound_s": terms[dominant],
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "window": rec.get("window", 0),
    }


def load_records(out_dir: str | None = None) -> list[dict]:
    out_dir = out_dir or os.path.join(ART, "dryrun")
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def run(out_rows: list) -> dict:
    from repro.configs.base import INPUT_SHAPES

    recs = load_records()
    ok = [r for r in recs if r.get("status") == "ok"]
    rows = []
    for r in ok:
        chips = 512 if r["mesh"] == "pod2x16x16" else 256
        rows.append(analyze(r, INPUT_SHAPES, chips))

    path = os.path.join(ART, "roofline.csv")
    with open(path, "w") as f:
        f.write("arch,shape,mesh,kind,compute_s,memory_s,collective_s,"
                "dominant,bound_s,useful_ratio,window\n")
        for r in rows:
            f.write(f"{r['arch']},{r['shape']},{r['mesh']},{r['kind']},"
                    f"{r['compute_s']:.4e},{r['memory_s']:.4e},"
                    f"{r['collective_s']:.4e},{r['dominant']},{r['bound_s']:.4e},"
                    f"{r['useful_ratio']:.3f},{r['window']}\n")

    n_skip = sum(1 for r in recs if r.get("status") == "skipped")
    n_err = sum(1 for r in recs if r.get("status") == "error")
    summary = {
        "n_ok": len(ok), "n_skipped": n_skip, "n_error": n_err,
        "csv": path,
        "dominant_counts": {
            k: sum(1 for r in rows if r["dominant"] == k)
            for k in ("compute", "memory", "collective")
        },
    }
    out_rows.append(("roofline_pairs_ok", 0.0, len(ok)))
    out_rows.append(("roofline_pairs_error", 0.0, n_err))
    for r in rows:
        out_rows.append((
            f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            r["bound_s"] * 1e6,
            r["useful_ratio"],
        ))
    return summary


def markdown_table(mesh: str = "pod16x16") -> str:
    from repro.configs.base import INPUT_SHAPES

    recs = [r for r in load_records() if r.get("status") == "ok" and r["mesh"] == mesh]
    rows = [analyze(r, INPUT_SHAPES, 256 if mesh == "pod16x16" else 512) for r in recs]
    lines = [
        "| arch | shape | compute (ms) | memory (ms) | collective (ms) | bottleneck | useful FLOP ratio |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']*1e3:.2f} | "
            f"{r['memory_s']*1e3:.2f} | {r['collective_s']*1e3:.2f} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} |"
        )
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows: list = []
    print(json.dumps(run(rows), indent=2))
    print(markdown_table())
