"""CPU rehearsal of ``chip_smoke.py``: its phase functions at tiny widths,
with the Pallas kernels in interpret mode asked for explicitly, holding the
same checks the chip run holds.  Only ``main()`` insists on a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


TINY = dict(budget=32, chunk=8, eval_every=4, attn_impl="pallas",
            interpret=True)


def test_kernel_phase_interpret(smoke):
    errs = smoke.phase_kernels(("tiny", "tiny-proxy"), interpret=True,
                               batch=4, ranks=6)
    assert len(errs) == 6                 # 2 archs x (probe + 2 attention)
    assert max(errs.values()) < 1e-5      # f32 tiny configs


def test_self_eat_phase(smoke):
    out = smoke.phase_self_eat("tiny", requests=6, slots=3, **TINY)
    assert len(out["n_reasoning"]) == 6
    assert min(out["n_reasoning"]) < TINY["budget"]
    assert out["evals"] > 0


def test_proxy_phase(smoke):
    out = smoke.phase_proxy("tiny", "tiny-proxy", requests=4, slots=2,
                            **TINY)
    assert len(out["n_reasoning"]) == 4
    assert min(out["n_reasoning"]) < TINY["budget"]


def test_checks_reject_a_bad_run(smoke):
    """The serve checks fail on what they are there to catch."""
    ok = {"request": 0, "status": "exited", "n_reasoning": 5,
          "exit_reason": "eat", "eat_trace": [(4, 1, 0.5)]}
    smoke.check_served([ok], 1, 32)
    for bad in ({"status": "decoding"}, {"n_reasoning": 33},
                {"eat_trace": [(4, 1, float("nan"))]},
                {"eat_trace": [(4, 0, float("inf"))]}):
        with pytest.raises(RuntimeError, match="check failed"):
            smoke.check_served([{**ok, **bad}], 1, 32)
    with pytest.raises(RuntimeError, match="below budget"):
        smoke.check_served([{**ok, "n_reasoning": 32,
                             "status": "exhausted"}], 1, 32)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_mesh_phase_on_four_host_devices():
    """The four-chip phase at tiny width on four simulated host devices
    (the device count is fixed at jax start, hence the subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import chip_smoke as s; "
            "r = s.phase_mesh('tiny', requests=6, slots=4, budget=32, "
            "chunk=8, eval_every=4); "
            "assert r['d_logit'] < 1e-5 and r['d_eat'] < 1e-5, r")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-4000:]
    assert "parameters: " in r.stdout and "KV page pools: " in r.stdout
    assert "token streams identical" in r.stdout
