"""Unit tests of the benchmark's yardstick, on the CPU: the traffic
generator, discovery of cells by name, the peaks table, the percentile and
rate arithmetic, the operation and byte counts, the trace reduction, and
the plain reference against the served model."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, stats, traffic
from bench import run as bench_run
from bench.devtrace import WINDOW_SPAN, reduce_trace
from bench.peaks import peaks_for

REASONING = json.loads((bench_run.BENCH / "traffic" / "reasoning.json")
                       .read_text())


# ------------------------------------------------------------------ traffic
def test_traffic_is_deterministic_for_a_seed():
    big = 2**31 + 12345
    a = traffic.make_job(REASONING, 151936, big, 3)
    b = traffic.make_job(REASONING, 151936, big, 3)
    np.testing.assert_array_equal(a["prompts"], b["prompts"])
    np.testing.assert_array_equal(a["prompt_len"], b["prompt_len"])
    assert traffic.jax_seed(big, 3) == traffic.jax_seed(big, 3) < 2**31


def test_every_seed_gets_the_same_lengths_in_another_order():
    a = traffic.make_job(REASONING, 151936, 1, 0)
    b = traffic.make_job(REASONING, 151936, 2, 0)
    assert sorted(a["prompt_len"]) == sorted(b["prompt_len"])
    assert list(a["prompt_len"]) != list(b["prompt_len"])
    lens = a["prompt_len"]
    p = REASONING["prompt_len"]
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert int(np.median(lens)) in range(p["median"] - 8, p["median"] + 8)


def test_prompts_are_left_padded_and_skip_the_special_ids():
    job = traffic.make_job(REASONING, 151936, 7, 1)
    W = REASONING["prompt_width"]
    for row, L in zip(job["prompts"], job["prompt_len"]):
        assert (row[:W - L] == traffic.PAD_ID).all()
        assert (row[W - L:] >= REASONING["special_ids_below"]).all()
        assert (row[W - L:] < 151936).all()


# ---------------------------------------------------------------- discovery
def test_files_added_by_name_are_found(tiny_bench):
    root, bench = tiny_bench
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (bench / "traffic" / "added-mix.json").write_text(
        json.dumps({**json.loads((bench / "traffic" / "tiny-mix.json")
                                 .read_text()), "slots": 3}))
    (bench / "configs" / "added.json").write_text(
        (bench / "configs" / "tiny.self.json").read_text())
    (bench / "limits" / "added.cell.json").write_text(
        json.dumps({"answer_gap": 1, "eat_var_rel": 1, "exit_mismatch": 0}))
    (bench / "metrics" / "added_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    spec["workloads"].append({"name": "added.cell", "config": "added",
                              "traffic": "added-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "added_metric", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "executor", "moves": "tokens_per_s",
                              "workloads": ["added.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = bench_run.load_cell(root, "added.cell", bench)
    assert c["mix"]["slots"] == 3
    assert c["cfg"]["generator"]["arch"] == "tiny"
    assert "added_metric" in [m["name"] for m in c["per_layer"]]
    got = bench_run.read_metrics(c["per_layer"], {"values": {}}, bench)
    assert got["added_metric"] == {"value": 42.0, "unit": "count"}
    # a metric listed for other cells only is not this cell's
    other = bench_run.load_cell(root, "tiny.self.mix", bench)
    assert "added_metric" not in [m["name"] for m in other["per_layer"]]


def test_unknown_workload_is_refused(tiny_bench):
    root, bench = tiny_bench
    with pytest.raises(SystemExit):
        bench_run.load_cell(root, "no.such.cell", bench)


# -------------------------------------------------------------------- peaks
def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks_for("cpu")


# -------------------------------------------------------------------- stats
def test_percentile_interpolates_between_ranks():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 95) == pytest.approx(19.5)
    assert stats.percentile(list(range(1, 21)), 95) == pytest.approx(19.05)
    assert stats.percentile([7], 95) == 7


def test_rates_are_over_all_jobs_wall_time():
    r = lambda n, lat: {"n_reasoning": n, "answer_tokens": [0] * 4,  # noqa: E731
                        "latency_s": lat}
    jobs = [{"seconds": 2.0, "results": [r(10, 1.0), r(20, 2.0)]},
            {"seconds": 3.0, "results": [r(30, 3.0)]}]
    out = stats.window_rates(jobs)
    assert out["tokens_per_s"] == pytest.approx((10 + 20 + 30 + 12) / 5.0)
    assert out["requests_per_s"] == pytest.approx(3 / 5.0)
    assert out["latency_p95_s"] == pytest.approx(2.9)


# -------------------------------------------------------------------- flops
QWEN3 = json.loads((bench_run.BENCH / "configs" / "qwen3-1.7b.self-eat.json")
                   .read_text())["generator"]["model"]


def test_model_flops_match_a_hand_count():
    # Qwen3-1.7B: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # MLP 3 x 2048x6144, over 28 layers, plus a 2048x151936 unembedding
    per_layer = 2 * (2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144)
    want = 28 * per_layer + 2 * 2048 * 151936
    assert flops.matmul_flops_per_token(QWEN3) == want
    # attention over 100 cached tokens: 16 heads x 128 dims, QK and PV
    assert flops.token_flops(QWEN3, 100) == want + 28 * 4 * 16 * 128 * 100


def test_attention_bytes_and_calls_match_a_hand_count():
    # 8 kv heads x 128 dims x (K, V) x bf16 per cached token per layer,
    # plus 16 heads x 128 dims of query and output
    assert flops.attention_bytes(QWEN3, 1, 10) == 28 * (
        2 * 10 * 8 * 128 + 2 * 16 * 128) * 2
    calls = list(flops.request_calls(P=50, n=70, every=32, answer_len=4))
    kinds = [c[0] for c in calls]
    assert kinds.count("decode") == 69 and kinds.count("answer") == 4
    assert [c for c in calls if c[0] == "probe"] == [
        ("probe", 2, 50 + 32 + 2), ("probe", 2, 50 + 64 + 2)]
    assert calls[0] == ("decode", 1, 51) and calls[-1] == ("answer", 1, 123)


def test_entropy_probe_work_and_roofline_share():
    # W is read over the vocabulary padded to 256: 152064 columns
    fl, by = flops.entropy_probe_work(QWEN3, 16)
    assert by == 2048 * 152064 * 2 + 16 * 2048 * 2
    assert fl == 2 * 16 * 2048 * 152064
    peaks = peaks_for("TPU v5 lite")
    share, bound = flops.roofline_share(fl, by, by / 819e9 * 2, peaks)
    assert bound == "memory" and share == pytest.approx(0.5)
    share, bound = flops.roofline_share(197e12, 1.0, 2.0, peaks)
    assert bound == "compute" and share == pytest.approx(0.5)


# -------------------------------------------------------------------- trace
def _ev(name, start, dur, stats=()):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                           stats=list(stats))


def _pd(host, device, chips=1):
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("main", host)])] + [
        SimpleNamespace(name=f"/device:TPU:{i}",
                        lines=[line("XLA Ops", device),
                               line("XLA Modules", [_ev("mod", 0, 10**9)])])
        for i in range(chips)])


def test_trace_reduction_on_a_hand_made_trace():
    host = [_ev(WINDOW_SPAN, 1_000_000, 1_000_000),
            _ev("bench:admit_paged", 1_300_000, 200_000)]
    device = [_ev("%fusion.1 = f32[2] fusion(...)", 1_000_000, 100_000),
              _ev("%paged_attention.3 = bf16[16,8,8,128] custom-call(...)",
                  1_100_000, 150_000),
              # reads the kernel's output: not a kernel event
              _ev("%slice.4 = bf16[2] slice(%paged_attention.3)",
                  1_100_000, 10_000),
              _ev("%entropy_probe.1 = f32[16,128] custom-call(...)",
                  1_200_000, 50_000),
              _ev("%while.2 = (s32[]) while(...)", 1_600_000, 300_000),
              _ev("%fusion.9 = f32[2] fusion(...)", 1_650_000, 100_000)]
    red = reduce_trace(_pd(host, device))
    assert red["window_s"] == pytest.approx(1e-3)
    # busy: [1.0, 1.25] ms (the probe overlaps the attention) and [1.6, 1.9]
    assert red["busy_s"] == pytest.approx((250_000 + 300_000) * 1e-9)
    assert red["kernel_s"]["paged_attention"] == pytest.approx(150e-6)
    assert red["kernel_s"]["entropy_probe"] == pytest.approx(50e-6)
    assert red["kernel_calls"] == {"paged_attention": 1, "entropy_probe": 1}
    gaps = dict(red["idle_gaps"])
    assert gaps["admit_paged"] == pytest.approx(350e-6)   # 1.25-1.6 ms
    assert gaps["serve loop (host)"] == pytest.approx(100e-6)  # 1.9-2.0 ms
    ops = dict(red["device_ops"])
    assert ops["fusion"] == pytest.approx(200e-6)       # leaves only
    assert "while" not in ops                           # encloses fusion.9
    assert red["collective_s"] == 0


#: one job's window of 1 ms: 0.6 ms of device work on each chip, of which
#: 0.15 ms is exchanges between chips
SHARDED_OPS = [
    _ev("%fusion.1 = bf16[48,4096] fusion(...)", 1_000_000, 200_000),
    _ev("%all-reduce.7 = bf16[48,4096] all-reduce(...)", 1_200_000, 100_000),
    _ev("%fusion.2 = bf16[48,4096] fusion(...)", 1_300_000, 250_000),
    _ev("%all-gather-start.3 = bf16[48,256] all-gather-start(...)",
        1_550_000, 30_000),
    _ev("%all-gather-done.3 = bf16[48,1024] all-gather-done(...)",
        1_580_000, 20_000),
    # outside the window: not counted
    _ev("%all-reduce.8 = bf16[48,4096] all-reduce(...)", 2_100_000, 50_000)]


def _sharded_rec(chips: int) -> dict:
    c = bench_run.load_cell(bench_run.BENCH.parent,
                            "qwen3-1.7b.self-eat.reasoning")
    red = reduce_trace(_pd([_ev(WINDOW_SPAN, 1_000_000, 1_000_000)],
                           SHARDED_OPS, chips))
    return {"cfg": c["cfg"], "mix": c["mix"], "trace": red,
            "peaks": peaks_for("TPU v5 lite"),
            "traced": {"job": {"prompt_len": np.asarray([64, 100])},
                       "results": [{"n_reasoning": 129}] * 2}}


def test_a_share_of_the_peak_is_taken_over_every_chip():
    """The same work in the same window on four chips reads a quarter of
    the share it reads on one."""
    one, four = _sharded_rec(1), _sharded_rec(4)
    assert four["trace"]["n_devices"] == 4
    for key in ("busy_s", "collective_s"):
        assert four["trace"][key] == pytest.approx(one["trace"][key]), key
    for key in ("device_ops", "idle_gaps"):       # means over chips too
        assert dict(four["trace"][key]) == pytest.approx(
            dict(one["trace"][key])), key
    mfu = bench_run.metric_reader("decode_mfu")
    assert mfu(four) == pytest.approx(mfu(one) / 4)
    # by hand: two requests' model FLOPs over the window and four peaks
    m, a = one["cfg"]["generator"]["model"], one["mix"]["answer_len"]
    want = sum(flops.emitted_token_flops(m, P, 129, a) for P in (64, 100))
    assert mfu(four) == pytest.approx(100 * want / (1e-3 * 4 * 197e12))
    fl, by = 1e12, 1e9
    s1, _ = flops.roofline_share(fl, by, 1e-3, one["peaks"])
    s4, _ = flops.roofline_share(fl, by, 1e-3, one["peaks"], chips=4)
    assert s4 == pytest.approx(s1 / 4)


@pytest.mark.parametrize("chips", [1, 4])
def test_collective_share_reads_the_exchanges_between_chips(chips):
    rec = _sharded_rec(chips)
    red = rec["trace"]
    assert red["collective_s"] == pytest.approx(150e-6)     # per chip
    assert red["busy_s"] == pytest.approx(600e-6)
    got = bench_run.metric_reader("collective_share")(rec)
    if chips == 1:
        assert got is None              # one chip exchanges nothing
    else:
        assert got == pytest.approx(25.0)


# ---------------------------------------------------------------- reference
def _program_tiny():
    import jax

    from repro.configs.base import get_config
    from repro.models import Model

    # jitted, as the launcher's ``init_params`` makes them on a mesh
    model = Model(get_config("tiny"))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _tiny_model(tiny_bench) -> dict:
    _, bench = tiny_bench
    cfg = json.loads((bench / "configs" / "tiny.self.json").read_text())
    return cfg["generator"]["model"]


def _reference(tiny_bench):
    root, bench = tiny_bench
    return bench_run.load_cell(root, "tiny.self.mix", bench)["reference"]


def test_reference_draws_the_served_weights_from_the_seed(tiny_bench):
    ref = _reference(tiny_bench)
    tiny = _tiny_model(tiny_bench)
    _, params = _program_tiny()
    w = ref.init_weights(tiny, 0)
    np.testing.assert_array_equal(w["embedding"],
                                  params["embed"]["embedding"])
    np.testing.assert_array_equal(w["lm_head"], params["embed"]["lm_head"])
    layers = params["stack"]["layers"]
    for name, got in (("wq", layers["attn"]["wq"]),
                      ("wo", layers["attn"]["wo"]),
                      ("w_gate", layers["ffn"]["w_gate"]),
                      ("w_down", layers["ffn"]["w_down"])):
        np.testing.assert_array_equal(w["layers"][name], got)


def test_reference_agrees_with_the_served_prefill(tiny_bench):
    import jax.numpy as jnp

    from repro.serving.cache import alloc_cache

    ref = _reference(tiny_bench)
    tiny = _tiny_model(tiny_bench)
    model, params = _program_tiny()
    toks = np.asarray([[21, 33, 40, 25, 63, 19, 50, 22]], np.int32)
    pos = np.arange(8, dtype=np.int32)[None]
    cache = alloc_cache(model.cfg, 1, 16)
    hidden, _ = model.prefill(params, jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray(pos), cache)
    served = np.asarray(model.logits(params, hidden))[0, :, :64]
    mask = np.tril(np.ones((8, 8), bool))[None]
    want = ref.forward(tiny, ref.init_weights(tiny, 0), toks,
                       pos, mask, pos)[0]
    np.testing.assert_allclose(served, want, atol=2e-4, rtol=0)


def test_float8_control_reads_far_above_the_program(tiny_bench):
    """The control (the reference with float8 weights in the program's
    place) must read well above what the served path reads, on the same
    requests: the comparison can tell a lower precision apart."""
    from bench import correct, harness

    root, bench = tiny_bench
    c = bench_run.load_cell(root, "tiny.self.mix", bench)
    cfg, mix, ref = c["cfg"], c["mix"], c["reference"]
    engine = harness.build_engine(cfg, mix)
    job = traffic.make_job(mix, 64, 99, 0)
    res = harness.serve_job(engine, cfg, mix, job, 5)
    finished = [(bench_run.unpadded(job, i), r) for i, r in enumerate(res)]
    w = correct.reference_weights(ref, cfg, None)
    nums = correct.compare(cfg, mix, finished, ref, w, margin=1e-3,
                           control=True)
    prog, ctrl = nums["program"], nums["control"]
    assert prog["answer_gap"] <= 1e-3 and prog["eat_var_rel"] <= 1e-3
    assert ctrl["eat_var_rel"] > 10 * max(prog["eat_var_rel"], 1e-6)
    # judged by the run's own rule against the cell's limits file
    assert correct.verdict(prog, c["limits"])[0]
    assert not correct.verdict(ctrl, c["limits"])[0]


# ------------------------------------------- limits against chip readings
CELLS = [w["name"] for w in json.loads(
    (bench_run.BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_each_limit_passes_the_program_and_fails_the_control(cell):
    """The readings ``bench/calibrate.py --control`` took on the chip at
    the cell's own size, judged by the run's own rule against the cell's
    limits file: every seed of the program is correct and every seed of
    the float8 control is not, so a loosened limit is caught here."""
    from bench import correct

    c = bench_run.load_cell(bench_run.BENCH.parent, cell)
    cal = json.loads((bench_run.BENCH / "calibration" / f"{cell}.json")
                     .read_text())
    assert len(cal["seeds"]) >= 3
    for seed, r in cal["seeds"].items():
        assert correct.verdict(r["program"], c["limits"])[0], seed
        assert not correct.verdict(r["control"], c["limits"])[0], seed


# ------------------------------------------------- recorded chip trace
#: One job of cell qwen3-1.7b.self-eat.reasoning recorded on a TPU v5 lite
#: (bench/calibrate.py --fixture): 16 requests, prompts below, each run to
#: 34 reasoning tokens with one EAT evaluation (at 32) and a 4-token
#: forced answer.
FIXTURE_PROMPTS = [85, 32, 78, 62, 48, 33, 128, 72, 43, 124, 57, 106, 67, 94,
                   39, 52]


def test_reduction_of_a_recorded_chip_trace():
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    from bench.peaks import peaks_for
    from repro.serving.telemetry import ServeResults, Telemetry

    path = Path(__file__).parent / "fixtures" / "self_eat_job.xplane.pb.gz"
    red = reduce_trace(ProfileData.from_serialized_xspace(
        gzip.open(path).read()))
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(1.015913372)
    assert 0.9 * red["window_s"] < red["busy_s"] < red["window_s"]
    # 28 layers x (33 decode steps + 1 probe + the rollout's 5 steps)
    assert red["kernel_calls"] == {"paged_attention": 28 * 39,
                                   "entropy_probe": 1}
    assert 0 < red["kernel_s"]["paged_attention"] < red["busy_s"]
    c = bench_run.load_cell(bench_run.BENCH.parent,
                            "qwen3-1.7b.self-eat.reasoning")
    # the same job's telemetry, served again (test_telemetry_metrics)
    tel = Telemetry.load(json.loads(
        (path.parent / "self_eat_job.telemetry.json").read_text()))
    rec = {"cfg": c["cfg"], "mix": {**c["mix"], "budget": 34},
           "peaks": peaks_for("TPU v5 lite"), "trace": red, "compiles": 0,
           "values": {},
           "traced": {"job": {"prompt_len": np.asarray(FIXTURE_PROMPTS)},
                      "results": ServeResults([{"n_reasoning": 34}] * 16,
                                              tel)}}
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(c["per_layer"], rec).items()}
    assert set(got) == {m["name"] for m in c["per_layer"]}
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    for name in ("decode_mfu", "paged_attention_roofline",
                 "entropy_probe_roofline"):
        assert 0 < got[name] <= 100, name
    assert got["tokens_saved_share"] == 0
    assert got["compiles_in_window"] == 0
