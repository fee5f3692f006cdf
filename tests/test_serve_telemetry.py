"""Serve telemetry (``serving/telemetry.py``): the spans and counters one
``serve()`` call hands back on its ``ServeResults``.

Over both loops, both monitor tiers and both cache backends: every span
closes inside its parent under one root; the counters agree with the
results (``row_steps``) and between the loops (admissions, pools); the
request stamps are ordered; and the sync loop's ``host_reads`` equals the
blocking reads its loop made before they were routed through
``Telemetry.fetch``, counted by hand below.
"""
import jax
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
from repro.serving.proxy import ProxyConfig
from repro.serving.sampler import SamplerConfig
from repro.serving.telemetry import ServeResults, Telemetry

NUM_PAGES = 60      # one explicit pool for both loops (auto-sizing differs)
CHUNK, PAGE, CAPACITY = 8, 16, 320
CASES = [(loop, tier, kind) for loop in ("sync", "overlap")
         for tier in ("self", "proxy") for kind in ("ring", "paged")]


@pytest.fixture(scope="module")
def gen_model():
    model = Model(get_config("tiny"), attn_impl="xla")
    return model, model.init(jax.random.PRNGKey(11))


@pytest.fixture(scope="module")
def batch():
    return ChainTask().serve_batch(np.random.default_rng(7), 6)


@pytest.fixture(scope="module")
def engines(gen_model):
    model, params = gen_model
    cache = {}

    def get(tier, kind):
        if (tier, kind) not in cache:
            ecfg = EngineConfig(
                max_reasoning_tokens=24, capacity=CAPACITY,
                pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
                newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS,
                chunk_len=CHUNK, sampler=SamplerConfig(greedy=True),
                cache=CacheConfig(kind=kind, page_size=PAGE,
                                  num_pages=NUM_PAGES))
            monitor = ReasoningMonitor(
                stopper=EATStopper(alpha=0.2, delta=1e9),
                probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
                schedule="every_n", every_n=4, min_evals=1)
            proxy = (ProxyConfig(model=model, params=params)
                     if tier == "proxy" else None)
            cache[tier, kind] = ReasoningEngine(model, params, ecfg, monitor,
                                                proxy=proxy)
        return cache[tier, kind]
    return get


@pytest.fixture(scope="module")
def served(engines, batch):
    """Results of one greedy job (6 requests, 4 slots, so two admissions)
    per (loop, tier, kind), served once and shared by the tests."""
    cache = {}

    def get(loop, tier, kind):
        if (loop, tier, kind) not in cache:
            cache[loop, tier, kind] = engines(tier, kind).serve(
                batch["prompts"], batch["prompt_len"], jax.random.PRNGKey(0),
                batch_size=4, max_tokens=24, answer_len=4,
                record_trace=True, overlap=loop == "overlap")
        return cache[loop, tier, kind]
    return get


@pytest.mark.parametrize("loop,tier,kind", CASES)
def test_spans_close_and_nest_under_one_root(served, loop, tier, kind):
    tel = served(loop, tier, kind).telemetry
    roots = [s for s in tel.spans if s[3] == -1]
    assert [r[0] for r in roots] == ["serve"]
    assert roots[0][4] == {"loop": loop, "slots": 4, "requests": 6}
    for name, t0, t1, parent, attrs in tel.spans:
        assert t1 is not None and t0 <= t1, name
        if parent >= 0:
            _, p0, p1, _, _ = tel.spans[parent]
            assert p0 <= t0 and t1 <= p1, (name, tel.spans[parent][0])
        if name == "serve.wait":
            assert "what" in attrs
    names = {s[0] for s in tel.spans}
    want = {"serve", "serve.setup", "serve.dispatch", "serve.wait",
            "serve.trace", "serve.harvest", "serve.admit"}
    want |= {"serve.proxy"} if tier == "proxy" else set()
    want |= {"serve.pages"} if kind == "paged" else set()
    assert names == want
    admits = [s for s in tel.spans if s[0] == "serve.admit"]
    assert sorted(s[4]["rid"] for s in admits) == [4, 5]


@pytest.mark.parametrize("loop,tier,kind", CASES)
def test_counters_match_the_results(served, loop, tier, kind):
    out = served(loop, tier, kind)
    c = out.telemetry.summary()["counters"]
    assert c["row_steps"] == sum(r["n_reasoning"] - 1 for r in out)
    assert c["slots"] == 4 and c["admissions"] == 2
    assert c["chunks"] >= 2 and c["host_reads"] > 0
    # every row's steps ran inside the batch-wide ones
    assert c["row_steps"] <= c["decode_steps"] * c["slots"]
    # the proxy tier inherits the engine's cache: a pool only when paged
    pools = {k.split(".")[0] for k in c if "." in k}
    assert pools == ({"gen", tier} & {"gen", "proxy"}
                     if kind == "paged" else set())
    for pool in pools:
        assert c[f"{pool}.pool_pages"] == NUM_PAGES - 1
        assert 0 < c[f"{pool}.peak_pages"] <= NUM_PAGES - 1
        assert c[f"{pool}.deferrals"] == 0


@pytest.mark.parametrize("tier", ["self", "proxy"])
def test_sync_and_overlapped_loops_agree_on_pools(served, tier):
    """Same greedy job, same explicit pools: the loops admit alike and
    report the same pools.  The proxy pool peaks alike too; the
    generator's overlapped peak may exceed the sync one by the documented
    overlap headroom (one chunk mapped ahead per row, one parked row)."""
    sync = served("sync", tier, "paged").telemetry.summary()["counters"]
    over = served("overlap", tier, "paged").telemetry.summary()["counters"]
    for k in ("admissions", "gen.pool_pages", "proxy.pool_pages",
              "proxy.peak_pages", "row_steps"):
        assert sync.get(k) == over.get(k), k
    headroom = 4 * -(-CHUNK // PAGE) + CAPACITY // PAGE
    assert (sync["gen.peak_pages"] <= over["gen.peak_pages"]
            <= sync["gen.peak_pages"] + headroom)


#: Blocking reads of the sync loop as its code stood before they were
#: routed through ``Telemetry.fetch`` (counted by hand from it), for this
#: job (record_trace, a 4-token answer):
#:   set-up          1   cur for the capacity check; proxy ring + 1 (the
#:                   proxy's cur for its ring guard)
#:   each boundary   any_active 1, active 1, trace 3 (n_reasoning, n_evals,
#:                   debiased var); paged + 2 (cur, n_reasoning for the
#:                   page map); proxy + 2 (out_len before and after the
#:                   chunk), proxy paged + 2 (the proxy's cur, n_reasoning)
#:   each harvest    answer 1 + 5 (out_tokens, out_len, n_reasoning,
#:                   ended_think, stop_flag); paged + 1 (cur for the
#:                   answer's pages)
#:   each admission  cur 1 for the ring guard; paged + 1 (cur for the row
#:                   table); proxy + 1 (the proxy's cur, for its ring
#:                   guard or its row table)
#: The recorder adds one read per job: the ring pointer after the last
#: chunk, for ``decode_steps``.
READS = {                 # set-up, boundary, harvest, admission
    ("self", "ring"): (1, 5, 6, 1),
    ("self", "paged"): (1, 7, 7, 2),
    ("proxy", "ring"): (2, 7, 6, 2),
    ("proxy", "paged"): (1, 11, 7, 3),
}


@pytest.mark.parametrize("tier", ["self", "proxy"])
@pytest.mark.parametrize("kind", ["ring", "paged"])
def test_sync_host_reads_are_the_loops_own(served, tier, kind):
    tel = served("sync", tier, kind).telemetry
    c = tel.summary()["counters"]
    harvests = sum(s[0] == "serve.harvest" for s in tel.spans)
    setup, boundary, harvest, admit = READS[tier, kind]
    assert c["host_reads"] == (setup + c["chunks"] * boundary
                               + harvests * harvest
                               + c["admissions"] * admit + 1)


@pytest.mark.parametrize("loop,tier,kind", CASES)
def test_request_stamps_are_ordered(served, loop, tier, kind):
    for r in served(loop, tier, kind):
        assert 0 <= r["queue_s"] <= r["first_token_s"] <= r["latency_s"]
    out = served(loop, tier, kind)
    # the cohort is admitted at once; the last two wait for freed slots
    assert max(r["queue_s"] for r in out[:4]) < min(r["queue_s"]
                                                    for r in out[4:])


def test_serve_results_is_the_plain_list(served):
    out = served("sync", "self", "ring")
    assert isinstance(out, ServeResults) and isinstance(out, list)
    plain = [dict(r) for r in out]
    assert out == plain and plain == out
    assert len(out) == 6 and out[0]["request"] == 0


def test_decode_steps_is_the_ring_pointers_advance(engines, batch):
    """One wave (slots = requests, self-EAT): the pointer advances once per
    batch-wide step, to the longest row, and forced-answer rollouts do not
    move it."""
    eng = engines("self", "ring")
    steps = []
    for answer_len in (0, 4):
        out = eng.serve(batch["prompts"], batch["prompt_len"],
                        jax.random.PRNGKey(0), batch_size=6, max_tokens=24,
                        answer_len=answer_len)
        c = out.telemetry.summary()["counters"]
        assert c["decode_steps"] == max(r["n_reasoning"] for r in out) - 1
        steps.append(c["decode_steps"])
    assert steps[0] == steps[1]


def test_summary_splits_self_time_from_children():
    tel = Telemetry.load({
        "spans": [["serve", 0, 10_000_000_000, -1, {}],
                  ["serve.wait", 1_000_000_000, 3_000_000_000, 0, {}],
                  ["serve.harvest", 4_000_000_000, 8_000_000_000, 0, {}],
                  ["serve.wait", 5_000_000_000, 6_000_000_000, 2, {}],
                  ["serve.admit", 9_000_000_000, None, 0, {}]],
        "counters": {"chunks": 3}})
    s = tel.summary()
    assert s["counters"] == {"chunks": 3}
    assert s["spans"]["serve"] == pytest.approx(
        {"count": 1, "total_s": 10.0, "self_s": 4.0})
    assert s["spans"]["serve.wait"] == pytest.approx(
        {"count": 2, "total_s": 3.0, "self_s": 3.0})
    assert s["spans"]["serve.harvest"] == pytest.approx(
        {"count": 1, "total_s": 4.0, "self_s": 3.0})
    assert "serve.admit" not in s["spans"]          # still open
    assert Telemetry.load(tel.dump()).summary() == s
