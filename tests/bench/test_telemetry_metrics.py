"""The per-layer metrics read from the program's own telemetry
(``ServeResults.telemetry``): each reader gives its definition's value on
a hand-built recorder, and nothing where the results carry none (as a
program without the recorder hands back a plain list)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import run as bench_run
from repro.serving.telemetry import ServeResults, Telemetry

S = 1_000_000_000       # one second in the recorder's nanoseconds

#: one 10 s job: 2 s and 0.5 s blocked on reads (one of them inside a
#: harvest), 48 slots over 128 batch-wide steps, 40 rows of 128 steps and
#: 8 of 100, a generator pool of 1920 data pages peaking at 864
TEL = {
    "spans": [["serve", 0, 10 * S, -1, {"loop": "sync"}],
              ["serve.wait", 1 * S, 3 * S, 0, {"what": "active"}],
              ["serve.harvest", 4 * S, 5 * S, 0, {}],
              ["serve.wait", 4 * S, 4 * S + S // 2, 2, {"what": "harvest"}]],
    "counters": {"slots": 48, "decode_steps": 128,
                 "row_steps": 40 * 128 + 8 * 100, "chunks": 5,
                 "gen.pool_pages": 1920, "gen.peak_pages": 864,
                 "proxy.pool_pages": 1000, "proxy.peak_pages": 1000},
}
WANT = {"host_busy_share": 100 * (10 - 2.5) / 10,
        "kv_pages_used_share": 100 * 864 / 1920,
        "slot_occupancy": 100 * (40 * 128 + 8 * 100) / (128 * 48)}


def rec_of(results) -> dict:
    return {"traced": {"results": results}, "mix": {"budget": 256}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_its_definition(name):
    read = bench_run.metric_reader(name)
    results = ServeResults([{"n_reasoning": 129}], Telemetry.load(TEL))
    assert read(rec_of(results)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_without_telemetry(name):
    read = bench_run.metric_reader(name)
    assert read(rec_of([{"n_reasoning": 129}])) is None
    assert read({"mix": {"budget": 256}}) is None
    # a recorder with nothing of its kind in it (a ring cache has no pool)
    empty = ServeResults([], Telemetry.load({"spans": [], "counters": {}}))
    assert read(rec_of(empty)) is None


#: The telemetry (``Telemetry.dump()``) of the 16-request job of
#: ``self_eat_job.xplane.pb.gz`` (see ``test_bench_units``), served again
#: on a TPU v5 lite in 16 slots with the profiler off: 2 chunks, 33
#: batch-wide steps, every row to 34 reasoning tokens, a 640-page pool.
CHIP_TELEMETRY = Path(__file__).parent / "fixtures" / \
    "self_eat_job.telemetry.json"


def test_readers_on_a_recorded_chip_telemetry():
    tel = Telemetry.load(json.loads(CHIP_TELEMETRY.read_text()))
    s = tel.summary()
    assert s["spans"]["serve"]["count"] == 1
    c = s["counters"]
    assert c["row_steps"] == 16 * (34 - 1)
    entries = [m for m in json.loads(
        (bench_run.BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] in WANT]
    assert len(entries) == len(WANT)
    got = {k: v["value"] for k, v in bench_run.read_metrics(
        entries, rec_of(ServeResults([{"n_reasoning": 34}] * 16, tel)))
        .items()}
    assert set(got) == set(WANT)
    for name, value in got.items():
        assert 0 < value <= 100, name
    wait = s["spans"]["serve.wait"]["total_s"]
    assert got["host_busy_share"] == pytest.approx(
        100 * (1 - wait / s["spans"]["serve"]["total_s"]))
    assert got["kv_pages_used_share"] == pytest.approx(
        100 * c["gen.peak_pages"] / c["gen.pool_pages"])
    assert got["slot_occupancy"] == pytest.approx(
        100 * 16 * 33 / (c["decode_steps"] * c["slots"]))
