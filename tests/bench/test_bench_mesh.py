"""A cell's mesh and reference come from its configuration file.

The harness on four simulated host devices (the device count is fixed at
jax start, hence one subprocess, which runs this file's ``__main__``): a
tiny cell added as files alone, served on a 1x4 mesh and checked by a
reference module of its own, placed over the same four devices.  Then,
in this process, what a configuration may not say: chips its mesh does
not span, a reference that is not there, a model key the registry does
not hold.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from argparse import Namespace
from pathlib import Path

import pytest

from bench import harness
from bench import run as bench_run

HERE = Path(__file__).resolve()
ROOT = HERE.parents[2]
MESH_CELL = "tiny.own.mix"


def add_mesh_cell(root: Path, bench: Path) -> None:
    """A cell on a 1x4 mesh whose configuration names a reference module
    of its own, written as new files beside the tiny bench's."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "tiny.self.json").read_text())
    cfg.update(mesh="1x4", reference="own_ref")
    (bench / "configs" / "tiny.own.json").write_text(json.dumps(cfg))
    # the plain reference, noting each forward it runs
    (bench / "references" / "own_ref.py").write_text(
        (ROOT / "bench" / "references" / "qwen_dense.py").read_text()
        + "\n\n_plain_forward = forward\n\n\n"
        "def forward(*a, **k):\n"
        "    with open(__file__ + '.calls', 'a') as f:\n"
        "        f.write('forward\\n')\n"
        "    return _plain_forward(*a, **k)\n")
    (bench / "limits" / f"{MESH_CELL}.json").write_text(
        (bench / "limits" / "tiny.self.mix.json").read_text())
    spec["configs"].append({"name": "tiny.own", "source": "test",
                            "file": "bench/configs/tiny.own.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": MESH_CELL, "config": "tiny.own",
                              "traffic": "tiny-mix", "chips": 4,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


# ------------------------------------------------- four host devices
@pytest.fixture(scope="module")
def on_four_devices(tmp_path_factory):
    """The readings of ``four_device_run`` in a subprocess."""
    out = tmp_path_factory.mktemp("mesh") / "readings.json"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, str(HERE), str(out)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    return json.loads(out.read_text())


def test_a_cell_on_a_1x4_mesh_is_served_over_four_devices(on_four_devices):
    got = on_four_devices
    assert got["devices"] == 4
    assert got["argv_mesh"] == "1x4"
    assert got["params_devices"] == [4] and got["pools_devices"] == [4]
    assert got["pools_split"]            # the pools are sharded, not copied
    assert got["correct"], got["compared"]


def test_a_1x4_cell_with_kv_writes_dropped_is_not_correct(on_four_devices):
    assert not on_four_devices["broken_correct"], \
        on_four_devices["broken_compared"]


def test_a_1x4_cell_with_the_exchange_between_chips_left_out_is_not_correct(
        on_four_devices):
    assert not on_four_devices["no_exchange_correct"], \
        on_four_devices["no_exchange_compared"]


def test_a_cell_brings_its_own_reference_as_a_file(on_four_devices):
    assert on_four_devices["own_reference_forwards"] > 0


def test_reference_over_four_devices_draws_the_served_params(
        on_four_devices):
    got = on_four_devices
    assert got["ref_devices"] == [4] and got["ref_split"]
    assert got["ref_equals_one_device"] and got["ref_equals_served"]


def test_reference_over_four_devices_agrees_with_one_device(
        on_four_devices):
    assert on_four_devices["logits_max_abs_diff"] <= 1e-5


def _devices_of(tree) -> list[int]:
    import jax

    return sorted({len(a.sharding.device_set)
                   for a in jax.tree_util.tree_leaves(tree)})


def _split(tree) -> bool:
    import jax

    return any(a.addressable_shards[0].data.shape != a.shape
               for a in jax.tree_util.tree_leaves(tree))


def mlp_without_exchange(p, x, cfg):
    """The feed-forward block with the exchange between chips left out:
    each chip carries its own slice of d_ff's partial sum on as if it were
    the whole, where the served block sums the four."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_device_ctx
    from repro.utils.jax_compat import shard_map

    def local(x, w_up, w_gate, w_down):
        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

    col, row = P(None, "model"), P("model", None)
    return shard_map(local, make_device_ctx(1, 4).mesh,
                     in_specs=(P(), col, col, row), out_specs=P(),
                     check_vma=False)(x, p["w_up"], p["w_gate"], p["w_down"])


def four_device_run(out: Path) -> None:
    """Serve the mesh cell through ``bench/run.py``'s ``run`` on four host
    devices, correct, with KV writes dropped and with the exchange between
    chips left out, then place its reference
    over the four devices; write what was seen to ``out``."""
    import jax
    import numpy as np

    sys.path.insert(0, str(HERE.parent))
    from conftest import TINY_MODEL, make_tiny_bench

    import repro.models.transformer as tf

    root, bench = make_tiny_bench(out.parent / "tree")
    add_mesh_cell(root, bench)
    seen = {"devices": len(jax.devices()), "argv_mesh": None}
    build = harness.build_engine

    def spy_build(cfg, mix):
        argv = harness.launcher_argv(cfg, mix)
        seen["argv_mesh"] = argv[argv.index("--mesh") + 1]
        engine = build(cfg, mix)
        seen["params_devices"] = _devices_of(engine.params)
        seen["params"] = jax.tree_util.tree_map(np.asarray, engine.params)
        chunk = engine.executor.decode_chunk

        def spy_chunk(params, state, *a, **k):
            res = chunk(params, state, *a, **k)
            if "pools_devices" not in seen:
                pools = res.cache["layers"]
                seen["pools_devices"] = _devices_of(pools)
                seen["pools_split"] = _split(pools)
            return res

        engine.executor.decode_chunk = spy_chunk
        return engine

    harness.build_engine = spy_build
    args = Namespace(workload=MESH_CELL, seed=2**33 + 5, seconds=0.5,
                     trace=0)
    res = bench_run.run(args, root, bench, require_tpu=False)
    seen["correct"], seen["compared"] = res["correct"], res["compared"]
    calls = bench / "references" / "own_ref.py.calls"
    seen["own_reference_forwards"] = len(calls.read_text().split())

    scatter = tf.scatter_pages
    tf.scatter_pages = lambda pool, *a, **k: pool
    res = bench_run.run(args, root, bench, require_tpu=False)
    tf.scatter_pages = scatter
    seen["broken_correct"] = res["correct"]
    seen["broken_compared"] = res["compared"]

    mlp = tf.mlp_apply
    tf.mlp_apply = mlp_without_exchange
    res = bench_run.run(args, root, bench, require_tpu=False)
    tf.mlp_apply = mlp
    seen["no_exchange_correct"] = res["correct"]
    seen["no_exchange_compared"] = res["compared"]

    ref = bench_run.load_cell(root, MESH_CELL, bench)["reference"]
    one = ref.init_weights(TINY_MODEL, 0)
    four = ref.init_weights(TINY_MODEL, 0, jax.devices()[:4])
    seen["ref_devices"] = _devices_of(four)
    seen["ref_split"] = _split(four)
    seen["ref_equals_one_device"] = all(
        np.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(one),
                                             jax.tree_util.tree_leaves(four)))
    served = seen.pop("params")
    layers = served["stack"]["layers"]
    pairs = [(four["embedding"], served["embed"]["embedding"]),
             (four["lm_head"], served["embed"]["lm_head"]),
             (four["layers"]["wq"], layers["attn"]["wq"]),
             (four["layers"]["wo"], layers["attn"]["wo"]),
             (four["layers"]["w_gate"], layers["ffn"]["w_gate"]),
             (four["layers"]["w_down"], layers["ffn"]["w_down"])]
    seen["ref_equals_served"] = all(np.array_equal(a, b) for a, b in pairs)
    toks = np.asarray([[21, 33, 40, 25, 63, 19, 50, 22]] * 2, np.int32)
    pos = np.tile(np.arange(8, dtype=np.int32), (2, 1))
    mask = np.tile(np.tril(np.ones((8, 8), bool)), (2, 1, 1))
    a = ref.forward(TINY_MODEL, one, toks, pos, mask, pos)
    b = ref.forward(TINY_MODEL, four, toks, pos, mask, pos)
    seen["logits_max_abs_diff"] = float(np.max(np.abs(a - b)))
    out.write_text(json.dumps(seen))


# ------------------------------------------------- what a file may not say
def _edit_cfg(bench: Path, name: str, **change) -> None:
    path = bench / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    for k, v in change.items():
        if v is None:
            cfg.pop(k, None)
        else:
            cfg[k] = v
    path.write_text(json.dumps(cfg))


@pytest.mark.parametrize("mesh,chips", [("1x4", 1), ("1x1", 4), ("2x2", 1)])
def test_a_cell_whose_chips_differ_from_its_mesh_is_refused(
        tiny_bench, capsys, mesh, chips):
    root, bench = tiny_bench
    _edit_cfg(bench, "tiny.self", mesh=mesh)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"][0]["chips"] = chips
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    args = Namespace(workload="tiny.self.mix", seed=1, seconds=0.5, trace=0)
    with pytest.raises(SystemExit, match="mesh"):
        bench_run.run(args, root, bench, require_tpu=False)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("reference", ["no_such_reference", None])
def test_a_configuration_naming_a_missing_reference_fails(
        tiny_bench, capsys, reference):
    root, bench = tiny_bench
    _edit_cfg(bench, "tiny.self", reference=reference)
    args = Namespace(workload="tiny.self.mix", seed=1, seconds=0.5, trace=0)
    with pytest.raises(SystemExit, match="reference"):
        bench_run.run(args, root, bench, require_tpu=False)
    assert capsys.readouterr().out == ""


def test_the_existing_cells_build_the_launcher_argv_they_built():
    """Both one-chip cells pass ``--mesh 1x1`` as before the mesh was a
    configuration key, and every other argument as it was."""
    want = {
        "qwen3-1.7b.self-eat.reasoning": [
            "--arch", "qwen3-1.7b", "--mesh", "1x1", "--requests", "48",
            "--batch", "48", "--budget", "256", "--chunk", "32",
            "--eval-every", "32", "--delta", "48.6", "--alpha", "0.2",
            "--cache", "paged", "--page-size", "16", "--attn-impl", "auto",
            "--overlap", "off"],
        "qwen3-1.7b.proxy-1.5b.reasoning": [
            "--arch", "qwen3-1.7b", "--mesh", "1x1", "--requests", "32",
            "--batch", "32", "--budget", "256", "--chunk", "32",
            "--eval-every", "32", "--delta", "48.6", "--alpha", "0.2",
            "--cache", "paged", "--page-size", "16", "--attn-impl", "auto",
            "--overlap", "on", "--monitor", "proxy", "--proxy-config",
            "eat-proxy-1.5b"]}
    for cell, argv in want.items():
        c = bench_run.load_cell(ROOT, cell)
        assert "mesh" not in c["cfg"]
        assert harness.launcher_argv(c["cfg"], c["mix"]) == argv
    c = bench_run.load_cell(ROOT, "eat-paper-8b.self-eat.reasoning")
    argv = harness.launcher_argv(c["cfg"], c["mix"])
    assert argv[:4] == ["--arch", "eat-paper-8b", "--mesh", "1x4"]


def test_check_model_checks_every_key_of_the_block():
    from repro.configs.base import get_config

    for cell in json.loads((ROOT / "BENCHMARK.json").read_text())[
            "workloads"]:
        c = bench_run.load_cell(ROOT, cell["name"])
        for side in ("generator", "proxy"):
            if c["cfg"].get(side):
                block = c["cfg"][side]
                harness.check_model(get_config(block["arch"]),
                                    block["model"], side)
    model = json.loads((ROOT / "bench" / "configs" / "eat-paper-8b.self-eat"
                        ".json").read_text())["generator"]["model"]
    reg = get_config("eat-paper-8b")
    with pytest.raises(ValueError, match="no 'kv_lora_rank'"):
        harness.check_model(reg, {**model, "kv_lora_rank": 512}, "gen")
    with pytest.raises(ValueError, match="d_ff"):
        harness.check_model(reg, {**model, "d_ff": 6144}, "gen")
    # a nested block is checked against the nested entry
    moe = get_config("tiny-moe")
    harness.check_model(moe, {"moe": {"top_k": moe.moe.top_k}}, "gen")
    with pytest.raises(ValueError, match="top_k"):
        harness.check_model(moe, {"moe": {"top_k": moe.moe.top_k + 1}},
                            "gen")
    with pytest.raises(ValueError, match="holds no"):
        harness.check_model(reg, {"moe": {"top_k": 2}}, "gen")


if __name__ == "__main__":
    four_device_run(Path(sys.argv[1]))
