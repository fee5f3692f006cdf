"""Page-table-native decode attention (kernels/paged_attention + the
``attn_impl != "gather"`` serving modes):

* kernel parity — interpret-mode Pallas and the XLA block-scan ref vs the
  dense pure-jnp oracle over GQA/MQA shapes, holes, partial pages, windows;
* the bit-exactness construction — paged (mapped pages only) == ring (all
  logical blocks) EXACTLY, per impl, at the op level: skipped fully-masked
  blocks are identity steps on the online-softmax carry (ref.py);
* serve-level A/B — ``serve()`` with the page-native path reproduces the
  ring backend's token streams, exit steps, and EAT trajectories
  bit-for-bit, through BOTH monitor tiers (self and proxy);
* mapped-count sync — the compacted page list the attention reads is
  re-derived from the allocator table at every push, across
  admit/retract/free;
* CLI smoke — ``launch.serve --cache paged --attn-impl xla`` end to end.
"""
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
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import (
    block_positions,
    paged_decode_attention,
    ring_decode_attention,
)
from repro.models import Model
from repro.serving.cache import CacheConfig
from repro.serving.engine import EngineConfig, ReasoningEngine
from repro.serving.proxy import ProxyConfig
from repro.serving.sampler import SamplerConfig
from repro.serving.scheduler import PageAllocator


# ------------------------------------------------------------- op-level setup


def make_paged_case(mapped, *, Hq=4, Hkv=2, Dk=16, Dv=16, ps=16, NB=16,
                    m=1, dtype=jnp.float32, seed=0):
    """A dense ring cache and an equivalent page pool holding the same
    written values, with per-row mapped-block patterns ``mapped`` (interior
    holes model admitted rows).  Pool pages are pre-filled with garbage so
    stale/unwritten slots differ between the two layouts — the masking
    discipline must cancel them exactly."""
    rng = np.random.default_rng(seed)
    B = len(mapped)
    C = NB * ps
    kd = np.zeros((B, C, Hkv, Dk), np.float32)
    vd = np.zeros((B, C, Hkv, Dv), np.float32)
    kv_pos = np.full((B, C), -1, np.int32)
    P = sum(len(bl) for bl in mapped) + 4
    kp = rng.normal(size=(P, ps, Hkv, Dk)).astype(np.float32)   # garbage
    vp = rng.normal(size=(P, ps, Hkv, Dv)).astype(np.float32)
    NBK = max(len(bl) for bl in mapped) + 2                     # padded ranks
    pages = np.zeros((B, NBK), np.int32)
    logical = np.zeros((B, NBK), np.int32)
    counts = np.array([len(bl) for bl in mapped], np.int32)
    nxt = 1
    for b, blocks in enumerate(mapped):
        for j, blk in enumerate(blocks):
            pages[b, j], logical[b, j] = nxt, blk
            fill = ps if blk != blocks[-1] else ps // 2 + 1     # partial last
            vk = rng.normal(size=(fill, Hkv, Dk)).astype(np.float32)
            vv = rng.normal(size=(fill, Hkv, Dv)).astype(np.float32)
            kp[nxt, :fill], vp[nxt, :fill] = vk, vv
            kd[b, blk * ps:blk * ps + fill] = vk
            vd[b, blk * ps:blk * ps + fill] = vv
            kv_pos[b, blk * ps:blk * ps + fill] = np.arange(
                blk * ps, blk * ps + fill)
            nxt += 1
    q = jnp.asarray(rng.normal(size=(B, m, Hq, Dk)), dtype)
    q_pos = jnp.asarray(
        np.stack([np.arange(C - m, C)] * B), jnp.int32)
    case = dict(
        q=q, q_pos=q_pos,
        kd=jnp.asarray(kd, dtype), vd=jnp.asarray(vd, dtype),
        kv_pos=jnp.asarray(kv_pos),
        # the cache's pool layout: a (one-layer) lane-dense stack
        kp=jnp.asarray(kp.reshape(1, P, ps, Hkv * Dk), dtype),
        vp=jnp.asarray(vp.reshape(1, P, ps, Hkv * Dv), dtype),
        pages=jnp.asarray(pages), logical=jnp.asarray(logical),
        counts=jnp.asarray(counts), ps=ps,
    )
    case["bpos"] = block_positions(case["kv_pos"], case["pages"],
                                   case["logical"], ps)
    return case


HOLES = [[0, 1, 2, 12], [0, 1, 2, 3, 4, 5], [0, 12, 13],
         [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]]


@pytest.mark.parametrize("m", [1, 2, 5])
@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_oracle(m, window, dtype):
    """Interpret-mode Pallas and the XLA block ref vs the dense oracle."""
    c = make_paged_case(HOLES, m=m, dtype=dtype)
    ref = attention_ref(c["q"], c["kd"], c["vd"], c["q_pos"], c["kv_pos"],
                        window=window, scale=0.25)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for impl in ("xla", "pallas"):
        out = paged_decode_attention(
            c["q"], c["kp"], c["vp"], c["pages"], c["counts"], c["bpos"],
            c["q_pos"], window=window, scale=0.25, impl=impl, interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol, err_msg=impl)


@pytest.mark.parametrize("case", [
    dict(Hq=4, Hkv=2),            # GQA
    dict(Hq=8, Hkv=1),            # MQA
    dict(Hq=6, Hkv=3, Dk=32, Dv=16),   # Dv != Dk
])
def test_paged_equals_ring_bitwise(case):
    """THE construction the serving modes rely on: the paged op (mapped
    pages only) equals the ring op (all logical blocks) with EXACT float
    equality, per impl — skipped blocks are identity steps."""
    c = make_paged_case(HOLES, m=2, **case)
    for impl in ("xla", "pallas"):
        ring = ring_decode_attention(
            c["q"], c["kd"], c["vd"], c["q_pos"], c["kv_pos"],
            page_size=c["ps"], scale=0.25, impl=impl, interpret=True)
        paged = paged_decode_attention(
            c["q"], c["kp"], c["vp"], c["pages"], c["counts"], c["bpos"],
            c["q_pos"], scale=0.25, impl=impl, interpret=True)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(paged),
                                      err_msg=impl)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("Hq,Hkv", [(16, 8), (12, 2)],
                         ids=["qwen3-1.7b", "eat-proxy-1.5b"])
def test_kernel_reads_its_layer_of_the_stack(Hq, Hkv, layer):
    """The kernel reads layer ``layer`` of a three-layer lane-dense stack
    straight through its index maps, and equals the XLA reference run on
    that layer alone — at the served models' kv widths, with rows whose
    trailing ranks are skipped."""
    from repro.kernels.paged_attention.kernel import paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_xla

    cases = [make_paged_case(HOLES, Hq=Hq, Hkv=Hkv, Dk=128, Dv=128, m=2,
                             seed=s) for s in range(3)]
    c = cases[layer]
    assert (np.asarray(c["counts"]) < c["pages"].shape[1]).all()
    kp = jnp.concatenate([x["kp"] for x in cases])
    vp = jnp.concatenate([x["vp"] for x in cases])
    out = paged_attention_pallas(
        c["q"], kp, vp, c["pages"], c["counts"], c["bpos"], c["q_pos"],
        jnp.int32(layer), scale=128 ** -0.5, interpret=True)
    ref = paged_attention_xla(c["q"], c["kp"], c["vp"], c["pages"],
                              c["bpos"], c["q_pos"], scale=128 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_op_pads_non_multiple_capacity():
    """A ring capacity that is not a page multiple is padded with masked
    slots — appended identity steps, so the result is unchanged."""
    c = make_paged_case(HOLES, m=1)
    ref = ring_decode_attention(c["q"], c["kd"], c["vd"], c["q_pos"],
                                c["kv_pos"], page_size=16, scale=0.25,
                                impl="xla")
    odd = ring_decode_attention(
        c["q"], c["kd"][:, :-8], c["vd"][:, :-8], c["q_pos"],
        c["kv_pos"][:, :-8], page_size=16, scale=0.25, impl="xla")
    # the dropped tail slots are all pos=-1 in this case, so truncation +
    # re-padding must not change anything
    assert (np.asarray(c["kv_pos"])[:, -8:] == -1).all()
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(odd))


# ------------------------------------------------------------ serve-level A/B


def _engine(kind, attn, *, num_pages=0, capacity=256, delta=1e9, budget=24,
            proxy=False, chunk_len=8, interpret=False):
    cfg = get_config("tiny")
    model = Model(cfg, attn_impl="xla", interpret=interpret)
    params = model.init(jax.random.PRNGKey(11))
    ecfg = EngineConfig(
        max_reasoning_tokens=budget, capacity=capacity,
        pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
        newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS, chunk_len=chunk_len,
        sampler=SamplerConfig(greedy=True),
        cache=CacheConfig(kind=kind, page_size=16, num_pages=num_pages,
                          attn_impl=attn),
    )
    monitor = ReasoningMonitor(
        stopper=EATStopper(alpha=0.2, delta=delta),
        probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
        schedule="every_n", every_n=4, min_evals=1,
    )
    px = ProxyConfig(model=model, params=params) if proxy else None
    return ReasoningEngine(model, params, ecfg, monitor, proxy=px)


def _serve(eng, b, **kw):
    return eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                     batch_size=4, max_tokens=24, **kw)


def _assert_bit_equal(ref, out):
    for r, o in zip(ref, out):
        assert r["n_reasoning"] == o["n_reasoning"]
        assert r["exit_reason"] == o["exit_reason"]
        assert r["ended_think"] == o["ended_think"]
        np.testing.assert_array_equal(r["reasoning_tokens"],
                                      o["reasoning_tokens"])
        if "answer_tokens" in r and r["answer_tokens"] is not None:
            np.testing.assert_array_equal(r["answer_tokens"],
                                          o["answer_tokens"])
        assert r["eat_trace"] == o["eat_trace"]       # bit-exact floats


@pytest.fixture(scope="module")
def serve_batch():
    return ChainTask().serve_batch(np.random.default_rng(7), 6)


def test_page_native_serve_identical_to_ring(serve_batch):
    """The acceptance A/B: the page-native paged path reproduces the ring
    backend's token streams, exit steps, answers, and EAT trajectories
    bit-for-bit, both delta regimes."""
    b = serve_batch
    for delta in (1e9, 0.0):
        ref = _serve(_engine("ring", "xla", delta=delta), b,
                     answer_len=4, record_trace=True)
        out = _serve(_engine("paged", "xla", delta=delta), b,
                     answer_len=4, record_trace=True)
        _assert_bit_equal(ref, out)


def test_page_native_serve_with_admission_holes():
    """14 requests through a 24-data-page pool: admissions map prompt
    blocks + the current decode block, leaving interior unmapped holes the
    page-native read must skip — still bit-identical to the ring."""
    b = ChainTask().serve_batch(np.random.default_rng(9), 14)
    ref = _engine("ring", "xla", capacity=400, delta=0.0).serve(
        b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
        batch_size=4, max_tokens=24, record_trace=True)
    out = _engine("paged", "xla", capacity=400, num_pages=25,
                  delta=0.0).serve(
        b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
        batch_size=4, max_tokens=24, record_trace=True)
    _assert_bit_equal(ref, out)
    assert len(out) == 14


def test_page_native_proxy_tier_bit_exact(serve_batch):
    """Both monitor tiers through the new path: a same-params proxy serve
    (shadow decode + retract reconciliation, its own page pool read
    page-natively) reproduces self-EAT serving bit-for-bit, both
    backends."""
    b = serve_batch
    for kind in ("ring", "paged"):
        ref = _serve(_engine(kind, "xla", delta=0.2), b, record_trace=True)
        out = _serve(_engine(kind, "xla", delta=0.2, proxy=True), b,
                     record_trace=True)
        _assert_bit_equal(ref, out)


def test_pallas_interpret_serve_smoke():
    """The --attn-impl pallas path end to end on CPU (interpret mode): a
    short paged serve produces the same tokens and exit metadata as the
    XLA page-native path (allclose numerics -> identical greedy tokens)."""
    b = ChainTask().serve_batch(np.random.default_rng(3), 2)
    kw = dict(num_pages=0, capacity=64, delta=1e9, budget=8, chunk_len=4)
    ref = _engine("paged", "xla", **kw).serve(
        b["prompts"], b["prompt_len"], jax.random.PRNGKey(0), batch_size=2,
        max_tokens=8)
    out = _engine("paged", "pallas", interpret=True, **kw).serve(
        b["prompts"], b["prompt_len"], jax.random.PRNGKey(0), batch_size=2,
        max_tokens=8)
    for r, o in zip(ref, out):
        assert r["n_reasoning"] == o["n_reasoning"]
        assert r["exit_reason"] == o["exit_reason"]
        np.testing.assert_array_equal(r["reasoning_tokens"],
                                      o["reasoning_tokens"])


def test_gather_default_untouched(serve_batch):
    """attn_impl='gather' (the default) still takes the logical-view
    gather: no blocks arrays in the cache, and the program keys carry no
    impl suffix."""
    eng = _engine("paged", "gather")
    out = _serve(eng, serve_batch)
    assert len(out) == 6
    assert all(k[-1] == "paged" for k in eng.executor._programs
               if k[0] == "chunk")


def test_native_refuses_blockless_paged_cache():
    """A paged cache without the compacted page list under a page-native
    impl must fail at trace time — a silent gather fallback would split
    the per-impl paged==ring bit-exactness pairing."""
    import dataclasses

    from repro.serving.cache import alloc_paged_cache
    from repro.serving.executor import positions_for

    cfg = get_config("tiny")
    model = dataclasses.replace(Model(cfg, attn_impl="xla"),
                                paged_attn_impl="xla")
    params = model.init(jax.random.PRNGKey(0))
    cache = alloc_paged_cache(cfg, 2, 64, 16, 9)      # no block_bucket
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="compacted page list"):
        model.decode_step(params, tok, positions_for(cfg, pos), pos, cache)


def test_native_program_keys_carry_impl(serve_batch):
    """--attn-impl threads EngineConfig.cache -> executor program keys."""
    eng = _engine("paged", "xla")
    _serve(eng, serve_batch)
    kinds = {k[-1] for k in eng.executor._programs if k[0] == "chunk"}
    assert kinds == {"paged+xla"}
    assert eng.model.paged_attn_impl == "xla"     # baked into the model


# -------------------------------------------------------- mapped-count sync


def test_block_buckets_track_admit_and_free():
    """The compacted page list is a pure function of the allocator table,
    re-derived at every push — admit/free (and retract, which never
    unmaps) cannot desync it."""
    alloc = PageAllocator(num_pages=32, page_size=4, n_blocks=16, batch=3)
    alloc.ensure(0, 0, 11)                        # row 0: blocks 0..2
    alloc.ensure(1, 0, 3)                         # row 1: block 0
    w = alloc.bucket_width()
    pages, logical, counts = alloc.block_buckets(w)
    np.testing.assert_array_equal(counts, [3, 1, 0])
    assert (logical[0, :3] == [0, 1, 2]).all()
    assert (pages[counts == 0] == 0).all()        # padding = trash

    # harvest row 0, admit a new request into it: prompt blocks + the
    # batch's current decode block -> an interior hole in the mapping
    alloc.free_row(0)
    alloc.admit_row(0, prompt_slots=8, cur=40)    # blocks 0,1 + block 10
    pages, logical, counts = alloc.block_buckets(alloc.bucket_width())
    assert counts[0] == 3
    np.testing.assert_array_equal(logical[0, :3], [0, 1, 10])  # ascending
    assert (pages[0, :3] != 0).all()
    # counts always equal the table's nonzero row sums (the sync invariant)
    np.testing.assert_array_equal(counts, (alloc.table != 0).sum(1))


def test_executor_push_keeps_blocks_in_sync(serve_batch):
    """ensure_chunk_pages re-derives the device blocks from the allocator
    table whenever it is dirty: a freed row's ranks go back to trash, an
    admitted row's fresh mapping appears, counts follow."""
    b = serve_batch
    eng = _engine("paged", "xla")
    B, S = 4, b["prompts"].shape[1]
    st = eng.start(jnp.asarray(b["prompts"][:B]),
                   jnp.asarray(b["prompt_len"][:B]), jax.random.PRNGKey(1),
                   capacity=16)
    from repro.serving.cache import alloc_paged_cache, blocks_arrays

    alloc = PageAllocator(B * 16 + 1, 16, 16, B)
    for row in range(B):
        alloc.ensure(row, 0, S - 1)
    w = alloc.bucket_width()
    paged = alloc_paged_cache(eng.model.cfg, B, 256, 16, B * 16 + 1,
                              block_bucket=w)
    paged["blocks"] = blocks_arrays(*alloc.block_buckets(w))
    st = st._replace(cache=eng.executor.pack_paged(paged, st.cache,
                                                   alloc.table))

    alloc.free_row(2)
    st = eng.executor.ensure_chunk_pages(alloc, st, [0, 1, 3], 4)
    blk = jax.tree_util.tree_map(np.asarray, st.cache["blocks"])
    assert blk["count"][2] == 0
    assert (blk["pages"][2] == 0).all()
    np.testing.assert_array_equal(blk["count"],
                                  (alloc.table != 0).sum(1))
    np.testing.assert_array_equal(np.asarray(st.cache["page_table"]),
                                  alloc.table)

    # cur in a later block -> prompt blocks + a distinct decode block
    row_table = alloc.admit_row(2, S, cur=100)
    assert (row_table != 0).sum() >= 2
    st = eng.executor.ensure_chunk_pages(alloc, st, [0, 1, 2, 3], 4)
    blk = jax.tree_util.tree_map(np.asarray, st.cache["blocks"])
    assert blk["count"][2] == (alloc.table[2] != 0).sum()
    np.testing.assert_array_equal(blk["count"],
                                  (alloc.table != 0).sum(1))


# ----------------------------------------------------------------- CLI smoke


def test_serve_cli_attn_impl_smoke():
    """``launch.serve --cache paged --attn-impl xla`` end to end (random
    weights): the CLI path for the page-native read cannot rot."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--requests", "4",
         "--batch", "2", "--budget", "16", "--chunk", "4", "--arch", "tiny",
         "--cache", "paged", "--attn-impl", "xla", "--local"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "served 4 requests" in r.stdout, r.stdout
