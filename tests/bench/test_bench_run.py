"""The whole harness on the CPU at a tiny size: a run's result line, the
comparison against the plain reference, and runs with the timed path
broken underneath, which must come out not correct."""
from __future__ import annotations

import json
from argparse import Namespace

import jax.numpy as jnp
import pytest

from bench import run as bench_run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, bench, cell, *, trace=0, seed=1234567891011):
    args = Namespace(workload=cell, seed=seed, seconds=0.5, trace=trace)
    return bench_run.run(args, root, bench, require_tpu=False)


@pytest.mark.parametrize("cell", ["tiny.self.mix", "tiny.proxy.mix"])
def test_run_is_correct_and_carries_the_contract_keys(tiny_bench, cell):
    root, bench = tiny_bench
    res = _run(root, bench, cell)
    assert res["correct"], res["compared"]
    assert set(res) - {"compared"} == CONTRACT_KEYS
    assert list(res)[-1] == "compared"
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"tokens_per_s", "requests_per_s",
                                   "latency_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] >= 1
    json.dumps(res)


def test_run_without_a_tpu_prints_nothing(tiny_bench, capsys):
    root, bench = tiny_bench
    args = Namespace(workload="tiny.self.mix", seed=1, seconds=0.5, trace=0)
    assert bench_run.run(args, root, bench) is None
    assert capsys.readouterr().out == ""


def test_main_exits_nonzero_without_a_tpu(monkeypatch, capsys):
    monkeypatch.chdir(bench_run.BENCH.parent)
    assert bench_run.main(["--workload", "qwen3-1.7b.self-eat.reasoning",
                           "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def _alter_answers(monkeypatch):
    from repro.serving.executor import Executor

    orig = Executor.rollout

    def rollout(self, *a, **k):
        toks, lps = orig(self, *a, **k)
        return (toks + 1) % self.cfg.vocab, lps

    monkeypatch.setattr(Executor, "rollout", rollout)


def _alter_eat(monkeypatch):
    import repro.serving.executor as ex

    orig = ex.eval_eat
    monkeypatch.setattr(ex, "eval_eat",
                        lambda *a, **k: orig(*a, **k) * jnp.float32(1.01))


def _drop_cache_writes(monkeypatch):
    """Every step returns the KV pages it was handed, unchanged."""
    import repro.models.transformer as tf

    monkeypatch.setattr(tf, "scatter_pages", lambda pool, *a, **k: pool)


@pytest.mark.parametrize("cell", ["tiny.self.mix", "tiny.proxy.mix"])
@pytest.mark.parametrize("fault", [_alter_answers, _alter_eat,
                                   _drop_cache_writes])
def test_a_broken_timed_path_is_not_correct(tiny_bench, monkeypatch, cell,
                                            fault):
    root, bench = tiny_bench
    fault(monkeypatch)
    res = _run(root, bench, cell)
    assert not res["correct"], res["compared"]
