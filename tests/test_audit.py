"""repro-audit test suite (tools/audit + src/repro/analysis).

Two directions per pass: the seeded fixture violation under
``tests/fixtures/audit/`` IS caught (the analyzer can see), and the real
tree is clean (the contracts hold — these are the assertions CI's audit
job re-runs via ``python -m tools.audit.run --fail-on-violation``).
The lowered pass additionally gets unit fixtures for each artifact scan:
a debug-callback jaxpr, a float-widening cast, and donation mismatches in
both directions.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import PASS_NAMES, run_passes
from repro.analysis import docs_links, keys, layering, lowered, pallas_lint

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "audit"


def _rules(vs):
    return {v.rule for v in vs}


# ------------------------------------------------------------ pass 1: layering
def test_layering_catches_fixture_tree():
    r = layering.run(FIXTURES / "layer_tree")
    assert _rules(r.violations) == {
        "pure-host", "executor-only-jit", "kernels-are-leaves",
        "dispatch-only", "stays-deleted",
    }
    # the jit owner's own jit sites are not flagged
    assert not any("executor" in v.where for v in r.violations
                   if v.rule == "executor-only-jit")
    # the overlap pipeline fixture blocks twice (direct + aliased)
    assert len([v for v in r.violations if v.rule == "dispatch-only"]) == 2


def test_layering_clean_on_real_tree():
    r = layering.run(REPO / "src")
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["modules"] > 50


def test_layering_pins_serve_step_deleted():
    """The satellite: launch/serve_step.py stays gone, and the pass is what
    enforces it."""
    assert not (REPO / "src/repro/launch/serve_step.py").exists()
    assert "repro/launch/serve_step.py" in layering.DEFAULT_RULES[
        "banned_paths"]


# ---------------------------------------------------------------- pass 3: keys
def test_keys_catches_unkeyed_knob():
    r = keys.run(FIXTURES / "keys_bad.py")
    assert r.stats["builders"] == 3
    assert len(r.violations) == 1
    v = r.violations[0]
    assert v.rule == "key-param" and "use_monitor" in v.detail
    assert "bad_chunk_program" in v.where
    # the correctly keyed builder and the KEY_EXEMPT-waived one are clean
    assert r.stats["exempt"] == ["waived"]


def test_keys_clean_on_real_executor():
    r = keys.run(REPO / "src/repro/serving/executor.py")
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["builders"] >= 10
    assert r.stats["exempt"] == ["prefill"]


# -------------------------------------------------------------- pass 4: pallas
def test_pallas_catches_fixture_kernel():
    r = pallas_lint.run([FIXTURES / "kernels" / "bad_kernel.py"])
    assert _rules(r.violations) == {"index-map-closure", "where-mask"}
    closure = [v for v in r.violations if v.rule == "index-map-closure"]
    assert len(closure) == 1 and "idx" in closure[0].detail
    # the clean kernel in the same file contributes no violations
    assert len(r.violations) == 2


def test_pallas_clean_on_real_kernels():
    paths = sorted((REPO / "src/repro/kernels").glob("*/kernel.py"))
    assert len(paths) == 5
    r = pallas_lint.run(paths)
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["index_maps"] > 20 and r.stats["wheres"] > 10


# ---------------------------------------------------------------- pass 5: docs
def test_docs_catches_broken_link():
    r = docs_links.run(FIXTURES / "docs_tree")
    assert len(r.violations) == 1
    assert r.violations[0].rule == "broken-link"
    assert "missing/nowhere.md" in r.violations[0].detail
    assert r.stats["links"] == 4          # good + anchor + external + broken


def test_docs_clean_on_real_tree():
    r = docs_links.run(REPO)
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["files"] >= 4


def test_docs_shim_cli_contract():
    """tools/check_docs_links.py keeps its exit-code + summary contract."""
    cp = subprocess.run(
        [sys.executable, str(REPO / "tools/check_docs_links.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert cp.returncode == 0, cp.stderr
    assert "0 broken" in cp.stdout


# ------------------------------------------------------------- pass 2: lowered
def test_scan_jaxpr_flags_callback_through_cond():
    def noisy(x):
        def tap(v):
            jax.debug.print("v={v}", v=v)
            return v

        return jax.lax.cond(x.sum() > 0, tap, lambda v: v * 2, x)

    jaxpr = jax.jit(noisy).trace(jnp.ones(3)).jaxpr
    vs = lowered.scan_jaxpr(jaxpr, "unit")
    assert {v.rule for v in vs} == {"sync-point"}
    assert any("callback" in v.detail for v in vs)


def test_scan_jaxpr_flags_float_widening():
    def widen(x):
        y = x.astype(jnp.float32)
        return y @ y.T

    jaxpr = jax.jit(widen).trace(
        jax.ShapeDtypeStruct((8, 8), jnp.float16)).jaxpr
    vs = lowered.scan_jaxpr(jaxpr, "unit")
    assert {v.rule for v in vs} == {"float-widening"}
    # scalar/1-D casts are tolerated (epsilons, counters)
    clean = jax.jit(lambda s: s.astype(jnp.float32) + 1).trace(
        jax.ShapeDtypeStruct((), jnp.float16)).jaxpr
    assert lowered.scan_jaxpr(clean, "unit") == []


def test_scan_hlo_text_flags_callback_custom_call():
    text = 'custom-call target="xla_ffi_python_cpu_callback"'
    assert _rules(lowered.scan_hlo_text(text, "unit")) == {"sync-point"}
    assert lowered.scan_hlo_text("add f32[2] %a, %b", "unit") == []


def test_donation_check_both_directions():
    c = jnp.zeros((64, 64))
    x = jnp.ones((64,))

    donating = jax.jit(lambda c, x: (c.at[0].set(x), x.sum()),
                       donate_argnums=0).lower(c, x).compile()
    assert lowered.check_donation(donating, "chunk", True, "unit") == []
    flagged = lowered.check_donation(donating, "probe", False, "unit")
    assert flagged and flagged[0].rule == "donation"

    functional = jax.jit(lambda c, x: (c.at[0].set(x), x.sum())
                         ).lower(c, x).compile()
    assert lowered.check_donation(functional, "probe", False, "unit") == []
    flagged = lowered.check_donation(functional, "chunk", True, "unit")
    assert flagged and flagged[0].rule == "donation"


def test_lowered_quick_matrix_clean():
    """Two-cell smoke of the real program matrix: a self cell and a proxy
    cell trace, lower, and donation-check clean (the full 12-cell matrix
    runs in CI's audit job)."""
    r = lowered.run(quick=True)
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["distinct_keys"] >= 10
    assert r.stats["donation_checked"] >= 5
    assert {"chunk", "shadow", "serve_step"} <= set(r.stats["families"])


def test_lowered_program_names_are_distinct_per_family():
    """Each program family lowers to a module of its own name
    (``jit_<name>``), never ``jit_fn``, over the {self, proxy} x {ring,
    paged} matrix — so a device trace can tell the programs apart."""
    cells = [(t, k, "gather") for t in lowered.TIERS for k in lowered.KINDS]
    r = lowered.run(cells=cells)
    assert r.ok, "\n".join(str(v) for v in r.violations)
    assert r.stats["modules"] == {
        "admit": ["jit_admit"],
        "admit_paged": ["jit_admit_paged"],
        "chunk": ["jit_decode_chunk"],
        "chunk_snapshot": ["jit_decode_chunk_snapshot"],
        "decode": ["jit_decode_step"],
        "pack": ["jit_pack_paged"],
        "prefill": ["jit_prefill"],
        "probe": ["jit_probe"],
        "retract": ["jit_retract"],
        "retract_lagged": ["jit_retract_lagged"],
        "rollout": ["jit_rollout"],
        "serve_step": ["jit_serve_step"],
        "shadow": ["jit_observe_chunk"],
        "stream_consume": ["jit_stream_consume"],
        "stream_prefill": ["jit_stream_prefill"],
        "stream_probe": ["jit_stream_probe"],
    }
    names = [n for ms in r.stats["modules"].values() for n in ms]
    assert len(names) == len(set(names))


# ------------------------------------------------------------------ the runner
def test_runner_cli_static_passes(tmp_path):
    out = tmp_path / "report.json"
    cp = subprocess.run(
        [sys.executable, "-m", "tools.audit.run",
         "--passes", "layering,keys,pallas,docs",
         "--fail-on-violation", "--json", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert cp.returncode == 0, cp.stdout + cp.stderr
    report = json.loads(out.read_text())
    assert report["violations"] == 0
    assert [p["name"] for p in report["passes"]] == [
        "layering", "keys", "pallas", "docs"]
    assert all(p["ok"] for p in report["passes"])


def test_run_passes_rejects_unknown_pass():
    with pytest.raises(ValueError, match="unknown pass"):
        run_passes(["nope"], REPO)
    assert set(PASS_NAMES) == {"layering", "keys", "pallas", "docs",
                               "lowered"}
