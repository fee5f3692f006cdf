"""Scenario suite for the proxy-EAT executor tier (``monitor="proxy"``,
paper §4.2 / Fig. 5 through the serving stack):

* bit-exactness — when the proxy IS the generator (same params), proxy-mode
  ``serve()`` reproduces self-EAT serving exactly: token streams, exit
  steps, exit reasons, forced answers, and EAT traces (exact float
  equality), through BOTH cache backends — the proxy-tier analogue of the
  paged==ring invariant in ``tests/test_paged_cache.py``;
* small proxy / large generator — a 1-layer tiny-proxy still exits every
  overthinking request before the budget (the paper's headline: a cheap
  local model stops a big black box);
* black-box contract — in proxy mode the generator executor never builds a
  probe program or a monitored chunk (program-key audit: no generator
  logits feed the exit decision); the shadow programs live in the
  ``ProxyExecutor``;
* proxy page pool — proxy-driven exits free slot AND pages that back
  same-batch admissions (the PR 3 reuse scenario with the exit decision
  originating from the proxy), including a deliberately undersized proxy
  pool gating admission independently of the generator pool;
* ``ProxyMonitor.observe_chunk`` offset regression — the standalone monitor
  must probe at the generator's stream offset, not its own chunk counter;
* CLI smoke — ``python -m repro.launch.serve --monitor proxy --requests 4``
  stays runnable (the tier-1 guard for the launcher path).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.eat import make_probe
from repro.core.monitor import ReasoningMonitor
from repro.core.stopping import EATStopper
from repro.data.synthetic import ChainTask, Tokens
from repro.models import Model
from repro.serving.cache import CacheConfig
from repro.serving.engine import EngineConfig, ReasoningEngine
from repro.serving.proxy import ProxyConfig, ProxyMonitor
from repro.serving.sampler import SamplerConfig


@pytest.fixture(scope="module")
def gen_model():
    model = Model(get_config("tiny"), attn_impl="xla")
    return model, model.init(jax.random.PRNGKey(11))


@pytest.fixture(scope="module")
def small_proxy():
    model = Model(get_config("tiny-proxy"), attn_impl="xla")
    return model, model.init(jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def serve_batch():
    return ChainTask().serve_batch(np.random.default_rng(7), 6)


def _engine(gen_model, *, kind="ring", delta=1e9, proxy=None, capacity=320,
            num_pages=0, budget=24):
    """Greedy tiny engine matching the paged/mesh equivalence tests; the
    generous ring capacity absorbs proxy-mode chunk overshoot (the
    generator decodes to the chunk boundary before a retract lands)."""
    model, params = gen_model
    ecfg = EngineConfig(
        max_reasoning_tokens=budget, capacity=capacity,
        pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
        newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS, chunk_len=8,
        sampler=SamplerConfig(greedy=True),
        cache=CacheConfig(kind=kind, page_size=16, num_pages=num_pages),
    )
    monitor = ReasoningMonitor(
        stopper=EATStopper(alpha=0.2, delta=delta),
        probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
        schedule="every_n", every_n=4, min_evals=1,
    )
    return ReasoningEngine(model, params, ecfg, monitor, proxy=proxy)


# ------------------------------------------------------------ bit-exactness
def test_same_params_proxy_bit_exact_with_self_eat(gen_model, serve_batch):
    """The acceptance A/B: a proxy running the generator's own params must
    reproduce self-EAT serving bit-for-bit (greedy sampling) — exit-at-
    first-eval AND run-to-budget regimes, ring AND paged backends, exact
    float equality on the EAT traces."""
    model, params = gen_model
    b = serve_batch
    for delta in (1e9, 0.0):
        ref = _engine(gen_model, delta=delta).serve(
            b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
            batch_size=4, max_tokens=24, answer_len=4, record_trace=True)
        for kind in ("ring", "paged"):
            eng = _engine(gen_model, kind=kind, delta=delta,
                          proxy=ProxyConfig(model=model, params=params))
            out = eng.serve(b["prompts"], b["prompt_len"],
                            jax.random.PRNGKey(0), batch_size=4,
                            max_tokens=24, answer_len=4, record_trace=True)
            for r, o in zip(ref, out):
                assert r["n_reasoning"] == o["n_reasoning"], (delta, kind)
                assert r["exit_reason"] == o["exit_reason"], (delta, kind)
                assert r["ended_think"] == o["ended_think"], (delta, kind)
                np.testing.assert_array_equal(r["reasoning_tokens"],
                                              o["reasoning_tokens"])
                np.testing.assert_array_equal(r["answer_tokens"],
                                              o["answer_tokens"])
                assert r["eat_trace"] == o["eat_trace"]   # bit-exact floats


# --------------------------------------------- small proxy, large generator
def test_small_proxy_stops_large_generator(gen_model, small_proxy,
                                           serve_batch):
    """A 1-layer/32-wide proxy monitoring the 2-layer/64-wide generator
    (Fig. 5 at toy scale): every overthinking request exits via the PROXY's
    EAT signal well before the budget."""
    pm, pp = small_proxy
    b = serve_batch
    eng = _engine(gen_model, delta=1e9,
                  proxy=ProxyConfig(model=pm, params=pp))
    out = eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                    batch_size=4, max_tokens=24)
    assert len(out) == 6
    for r in out:
        assert r["exit_reason"] == "eat", r
        assert r["n_reasoning"] < 24, r
        # the exit decision came from somewhere: the trace machinery must
        # carry the PROXY's evaluations
        assert r["status"] == "exited"


# ------------------------------------------------------ black-box contract
def test_generator_builds_no_probe_program_in_proxy_mode(gen_model,
                                                         small_proxy,
                                                         serve_batch):
    """Program-key audit: the black-box contract says no generator logits
    feed the exit decision — so the generator executor must never build a
    probe program or a monitored chunk; the shadow/probe programs live in
    the ProxyExecutor."""
    pm, pp = small_proxy
    b = serve_batch
    eng = _engine(gen_model, delta=1e9,
                  proxy=ProxyConfig(model=pm, params=pp))
    eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
              batch_size=4, max_tokens=24, answer_len=4)
    gen_keys = set(eng.executor._programs)
    assert not [k for k in gen_keys if k[0] == "probe"], gen_keys
    assert not [k for k in gen_keys if k[0] == "chunk" and k[2]], gen_keys
    # the generator DID decode (unmonitored chunks) and reconcile
    assert [k for k in gen_keys if k[0] == "chunk" and not k[2]], gen_keys
    assert [k for k in gen_keys if k[0] == "retract"], gen_keys
    # the probe work all lives in the proxy tier
    proxy_keys = set(eng.proxy_executor._programs)
    assert [k for k in proxy_keys if k[0] == "shadow"], proxy_keys
    # sanity of the audit method itself: self-EAT serving DOES build the
    # monitored chunk on the generator
    ref = _engine(gen_model, delta=1e9)
    ref.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
              batch_size=4, max_tokens=24)
    assert [k for k in ref.executor._programs if k[0] == "chunk" and k[2]]


def test_reason_refuses_proxy_mode(gen_model, serve_batch):
    """Monitored reason() has no prompt stream for the proxy to prefill —
    it must point callers at serve() instead of silently self-monitoring."""
    model, params = gen_model
    eng = _engine(gen_model, proxy=ProxyConfig(model=model, params=params))
    b = serve_batch
    st = eng.start(jnp.asarray(b["prompts"][:2]),
                   jnp.asarray(b["prompt_len"][:2]), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="serve"):
        eng.reason(st)
    # the unmonitored path stays available (pure decode, no probes)
    st2 = eng.start(jnp.asarray(b["prompts"][:2]),
                    jnp.asarray(b["prompt_len"][:2]), jax.random.PRNGKey(0))
    st2 = eng.reason(st2, use_monitor=False, max_tokens=8)
    assert int(np.asarray(st2.n_reasoning).min()) >= 8 or \
        bool(np.asarray(st2.ended_think).any())


# ------------------------------------------------------- proxy page pooling
def test_proxy_exit_frees_pages_for_same_batch_admissions(gen_model):
    """The PR 3 reuse scenario with the exit decision originating from the
    PROXY: a generator pool far too small for fourteen request lifetimes
    still serves the whole queue because proxy-driven exits reclaim pages
    mid-batch — and the proxy tier's own pool recycles the same way."""
    model, params = gen_model
    b = ChainTask().serve_batch(np.random.default_rng(9), 14)
    eng = _engine(gen_model, kind="paged", delta=1e9, num_pages=14,
                  capacity=640,
                  proxy=ProxyConfig(model=model, params=params))
    out = eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                    batch_size=4, max_tokens=24)
    assert len(out) == 14
    assert all(r["exit_reason"] == "eat" for r in out)
    # no-reuse lower bound: 14 lifetimes need >= 14 * (prompt + decode)
    # pages; 13 data pages only work because exits freed pages mid-batch
    counters = out.telemetry.summary()["counters"]
    assert counters["proxy.pages_reused"] > 0
    assert counters["proxy.peak_pages"] <= 13


def test_undersized_proxy_pool_still_serves_queue(gen_model):
    """The proxy pool gates admission independently: a ring generator
    (no page pressure at all) with a deliberately small PROXY pool still
    drains the queue — admissions wait for the proxy tier's harvest-time
    frees rather than failing."""
    model, params = gen_model
    b = ChainTask().serve_batch(np.random.default_rng(9), 14)
    eng = _engine(gen_model, kind="ring", delta=1e9, capacity=640,
                  proxy=ProxyConfig(
                      model=model, params=params,
                      cache=CacheConfig(kind="paged", page_size=16,
                                        num_pages=14)))
    out = eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                    batch_size=4, max_tokens=24)
    assert len(out) == 14 and all(r["exit_reason"] == "eat" for r in out)
    assert out.telemetry.summary()["counters"]["proxy.pages_reused"] > 0


def test_proxy_pool_too_small_for_one_request_fails_fast(gen_model):
    """A proxy pool that cannot hold even one prompt must raise the sizing
    error naming the PROXY pool, not hang with a forever-deferred queue."""
    model, params = gen_model
    b = ChainTask().serve_batch(np.random.default_rng(9), 3)
    eng = _engine(gen_model, kind="ring", delta=1e9, capacity=640,
                  proxy=ProxyConfig(
                      model=model, params=params,
                      cache=CacheConfig(kind="paged", page_size=4,
                                        num_pages=3)))
    with pytest.raises(RuntimeError, match="proxy|num_pages"):
        eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                  batch_size=2, max_tokens=24)


# ------------------------------------- ProxyMonitor stream-offset regression
def test_proxy_monitor_probes_at_generator_offset(gen_model):
    """Regression for the observe_chunk drift: the standalone monitor used
    to recompute positions from its own chunk counter, so a row re-seeded
    mid-stream (deferred admission into a recycled slot) probed at the
    previous occupant's offset.  ``next_pos`` from the request state is
    authoritative."""
    model, params = gen_model
    monitor = ReasoningMonitor(
        stopper=EATStopper(alpha=0.2, delta=1e-3),
        probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
        schedule="every_n", every_n=4, min_evals=1,
    )
    proxy = ProxyMonitor(model=model, params=params, monitor=monitor,
                         capacity=64)
    b = ChainTask().serve_batch(np.random.default_rng(3), 2)
    chunk = jnp.asarray(np.random.default_rng(0).integers(
        4, 40, size=(2, 6)), jnp.int32)

    ref = proxy.start(jnp.asarray(b["prompts"]), jnp.asarray(b["prompt_len"]))
    ref = proxy.observe_chunk(ref, chunk)
    ref_eat = np.asarray(ref["last_eat"])

    # same stream, but the monitor's internal counter has drifted (as after
    # a slot recycle): the generator-supplied next_pos must win
    drifted = proxy.start(jnp.asarray(b["prompts"]),
                          jnp.asarray(b["prompt_len"]))
    true_pos = drifted["next_pos"]
    drifted["next_pos"] = true_pos + 7            # stale internal counter
    out = proxy.observe_chunk(drifted, chunk, next_pos=true_pos)
    np.testing.assert_array_equal(np.asarray(out["last_eat"]), ref_eat)
    np.testing.assert_array_equal(np.asarray(out["next_pos"]),
                                  np.asarray(ref["next_pos"]))
    # and the drift reproduces without the override (the bug this pins)
    drifted2 = proxy.start(jnp.asarray(b["prompts"]),
                           jnp.asarray(b["prompt_len"]))
    drifted2["next_pos"] = drifted2["next_pos"] + 7
    bad = proxy.observe_chunk(drifted2, chunk)
    assert not np.array_equal(np.asarray(bad["last_eat"]), ref_eat)


# ----------------------------------------------------------------- CLI smoke
def test_serve_cli_proxy_smoke():
    """``launch.serve --monitor proxy --requests 4`` end to end (random
    weights): the launcher path for the proxy tier cannot rot."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--monitor", "proxy",
         "--requests", "4", "--batch", "2", "--budget", "16", "--chunk", "4",
         "--arch", "tiny", "--proxy-config", "tiny-proxy", "--local"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "served 4 requests" in r.stdout, r.stdout
    assert "monitor=proxy" in r.stdout, r.stdout
    # the last line is the serve call's telemetry summary
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["spans"]["serve"]["count"] == 1
    assert summary["counters"]["admissions"] == 2
