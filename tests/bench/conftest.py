"""Shared set-up for the benchmark's own tests: the repository root on
``sys.path`` (for ``import bench``) and a tiny cell laid out as the
harness finds a real one, for CPU runs of the whole harness."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 64, "qk_norm": True,
              "attn_bias": False, "tie_embeddings": False,
              "rope_theta": 10000.0, "norm_eps": 1e-06, "dtype": "float32"}
TINY_PROXY = {**TINY_MODEL, "n_layers": 1, "d_model": 32, "n_heads": 2,
              "n_kv_heads": 1, "d_ff": 64}

TINY_MIX = {"prompt_len": {"min": 4, "max": 16, "median": 8, "sigma": 0.5},
            "prompt_width": 16, "special_ids_below": 19, "budget": 24,
            "eval_every": 4, "chunk": 4, "slots": 2,
            "requests_per_job": 3, "answer_len": 4}


def tiny_cfg(monitor: str = "self", delta: float = 1e9) -> dict:
    cfg = {"source": "test", "reduced": [], "assumed": {},
           "reference": "qwen_dense",
           "generator": {"arch": "tiny", "weights_seed": 0,
                         "model": TINY_MODEL},
           "proxy": None, "monitor": monitor,
           "loop": "overlap" if monitor == "proxy" else "sync",
           "page_size": 8, "attn_impl": "auto", "alpha": 0.2,
           "delta": delta, "min_evals": 2, "probe_ids": [1, 6],
           "end_think_id": 1,
           "sampler": {"temperature": 0.6, "top_p": 0.95}}
    if monitor == "proxy":
        cfg["proxy"] = {"arch": "tiny-proxy", "weights_seed": 1,
                        "model": TINY_PROXY}
    return cfg


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_tiny_bench(tmp_path: Path):
    """(root, bench dir) holding a BENCHMARK.json with two tiny cells,
    their files, and the real per-layer metric readers and references."""
    bench = tmp_path / "bench"
    for sub in ("metrics", "references"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = []
    spec["workloads"] = []
    for monitor in ("self", "proxy"):
        name = f"tiny.{monitor}"
        write_json(bench / "configs" / f"{name}.json", tiny_cfg(monitor))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        cell = f"{name}.mix"
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": "tiny-mix", "chips": 1,
                                  "why": "test"})
        write_json(bench / "limits" / f"{cell}.json",
                   {"answer_gap": 1e-3, "eat_var_rel": 1e-3,
                    "exit_mismatch": 0})
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    write_json(bench / "traffic" / "tiny-mix.json", TINY_MIX)
    write_json(tmp_path / "BENCHMARK.json", spec)
    return tmp_path, bench


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)
