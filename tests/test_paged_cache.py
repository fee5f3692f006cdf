"""Block-paged KV cache tests (docs/architecture.md §Paged KV cache):

* PageAllocator invariants — alloc/free/reuse, trash-page reservation,
  exhaustion, the prompt+one-decode-page admission rule;
* append-across-page-boundary — scatter/gather through the page table
  reproduces dense ring writes exactly, including writes that straddle a
  page edge;
* gather equivalence — ``serve()`` with ``CacheConfig(kind="paged")``
  reproduces the ring path's token streams, exit steps, and EAT
  trajectories bit-for-bit on identical inputs;
* admission — a pool too small to hold every request simultaneously still
  serves the full queue because an early-exiting request's pages are
  reused by admissions in the SAME batch (and the ring cache, given the
  same physical slot budget, refuses those admissions);
* donation — the chunk program aliases the page pools in place, like the
  ring path.
"""
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
from repro.serving.cache import (
    CacheConfig,
    PAGE_TRASH,
    alloc_cache,
    alloc_paged_cache,
    cache_bytes,
    gather_pages,
    scatter_pages,
    write_slots,
)
from repro.serving.engine import EngineConfig, ReasoningEngine
from repro.serving.sampler import SamplerConfig
from repro.serving.scheduler import PageAllocator, SlotScheduler


# ------------------------------------------------------------ PageAllocator


def test_allocator_never_hands_out_trash_page():
    alloc = PageAllocator(num_pages=8, page_size=4, n_blocks=6, batch=2)
    pages = [alloc.map_block(0, b) for b in range(6)]
    assert PAGE_TRASH not in pages
    assert len(set(pages)) == 6                      # all distinct
    assert alloc.free_pages == 1


def test_allocator_free_reuse_cycle():
    alloc = PageAllocator(num_pages=6, page_size=4, n_blocks=8, batch=2)
    first = [alloc.map_block(0, b) for b in range(4)]
    assert alloc.free_pages == 1
    assert alloc.free_row(0) == 4
    assert alloc.free_pages == 5
    assert (alloc.table[0] == PAGE_TRASH).all()      # row fully unmapped
    second = [alloc.map_block(1, b) for b in range(4)]
    # LIFO free list: the freed pages back the next mapping immediately
    assert set(second) <= set(first)
    assert alloc.pages_reused == 4


def test_allocator_exhaustion_raises_with_sizing_hint():
    alloc = PageAllocator(num_pages=3, page_size=4, n_blocks=8, batch=1)
    alloc.map_block(0, 0)
    alloc.map_block(0, 1)
    with pytest.raises(RuntimeError, match="num_pages"):
        alloc.map_block(0, 2)


def test_allocator_admission_rule():
    alloc = PageAllocator(num_pages=6, page_size=8, n_blocks=8, batch=2)
    # a 12-token prompt needs 2 blocks + 1 decode page = 3 of 5 free
    assert alloc.can_admit(12)
    table_row = alloc.admit_row(0, 12, cur=20)
    assert (table_row[:2] != PAGE_TRASH).all()       # prompt blocks mapped
    assert table_row[20 // 8] != PAGE_TRASH          # decode block mapped
    assert not alloc.can_admit(25)                   # 4 needed, 2 free
    alloc.free_row(0)
    assert alloc.can_admit(25)


def test_allocator_ensure_idempotent_and_row_isolation():
    alloc = PageAllocator(num_pages=10, page_size=4, n_blocks=8, batch=3)
    alloc.ensure(0, 0, 11)
    used = alloc.pages_in_use
    alloc.ensure(0, 0, 11)                           # re-ensure: no-op
    assert alloc.pages_in_use == used
    alloc.ensure(1, 8, 11)
    # rows never share data pages
    assert set(alloc.table[0][alloc.table[0] != 0]).isdisjoint(
        set(alloc.table[1][alloc.table[1] != 0]))


# ---------------------------------------------- scatter/gather vs dense ring


def test_append_across_page_boundary_matches_dense():
    """Writes through the page table — including a write that straddles a
    page edge — gather back to exactly the dense ring layout."""
    rng = np.random.default_rng(0)
    ps, NB, P_pages, B, H, hd = 4, 4, 16, 2, 2, 3
    C = NB * ps
    alloc = PageAllocator(P_pages, ps, NB, B)
    for row in range(B):
        alloc.ensure(row, 0, C - 1)
    table = jnp.asarray(alloc.table)
    pool = jnp.zeros((P_pages, ps, H, hd), jnp.float32)
    dense = jnp.zeros((B, C, H, hd), jnp.float32)

    cur = 0
    for m in (3, 2, 5, 1):                           # 3+2 straddles slot 4
        new = jnp.asarray(rng.normal(size=(B, m, H, hd)), jnp.float32)
        slots = write_slots(jnp.asarray(cur, jnp.int32), m, C)
        assert int(slots[0]) // ps != int(slots[-1]) // ps or m == 1 or cur % ps + m <= ps
        pool = scatter_pages(pool, table, slots, new)
        dense = dense.at[:, slots].set(new)
        cur += m
    np.testing.assert_array_equal(np.asarray(gather_pages(pool, table)),
                                  np.asarray(dense))


def test_unmapped_blocks_read_trash_and_write_nothing_live():
    """A row without a mapping scatters into the trash page; a mapped row's
    gathered view is unaffected by the trash row's writes."""
    ps, NB, P_pages, B = 4, 2, 4, 2
    alloc = PageAllocator(P_pages, ps, NB, B)
    alloc.ensure(0, 0, NB * ps - 1)                  # row 0 mapped, row 1 not
    table = jnp.asarray(alloc.table)
    pool = jnp.zeros((P_pages, ps, 1, 1), jnp.float32)
    slots = jnp.arange(4, dtype=jnp.int32)
    vals = jnp.stack([jnp.full((4, 1, 1), 7.0), jnp.full((4, 1, 1), -9.0)])
    pool = scatter_pages(pool, table, slots, vals)
    out = np.asarray(gather_pages(pool, table))
    np.testing.assert_array_equal(out[0, :4, 0, 0], 7.0)   # row 0 intact
    # row 1's view is the trash page — whatever is there, it is NOT row 0's
    assert not (out[1, :4, 0, 0] == 7.0).all()


# -------------------------------------------------------- serve-level checks


def _engine(kind, *, num_pages=0, capacity=256, delta=1e9, budget=24):
    cfg = get_config("tiny")
    model = Model(cfg, attn_impl="xla")
    params = model.init(jax.random.PRNGKey(11))
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
    return ReasoningEngine(model, params, ecfg, monitor)


@pytest.fixture(scope="module")
def serve_batch():
    return ChainTask().serve_batch(np.random.default_rng(7), 6)


def test_paged_serve_identical_to_ring(serve_batch):
    """The acceptance A/B: same token streams, exit steps, and EAT
    trajectories (bit-exact) through the paged path, both delta regimes."""
    b = serve_batch
    for delta in (1e9, 0.0):
        ref = _engine("ring", delta=delta).serve(
            b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
            batch_size=4, max_tokens=24, answer_len=4, record_trace=True)
        out = _engine("paged", delta=delta).serve(
            b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
            batch_size=4, max_tokens=24, answer_len=4, record_trace=True)
        for r, o in zip(ref, out):
            assert r["n_reasoning"] == o["n_reasoning"]
            assert r["exit_reason"] == o["exit_reason"]
            assert r["ended_think"] == o["ended_think"]
            np.testing.assert_array_equal(r["reasoning_tokens"],
                                          o["reasoning_tokens"])
            np.testing.assert_array_equal(r["answer_tokens"],
                                          o["answer_tokens"])
            assert r["eat_trace"] == o["eat_trace"]   # bit-exact floats


def test_freed_pages_back_same_batch_admissions():
    """Admission through page reuse: a pool far too small to hold all fourteen
    requests' lifetimes simultaneously still serves the whole queue —
    early-exiting requests' pages are reclaimed and back the admissions in
    the same batch — while the ring cache, given the same physical slot
    budget, refuses the extra admissions."""
    b = ChainTask().serve_batch(np.random.default_rng(9), 14)
    # delta=0: every request runs its full 24-token budget, so the shared
    # ring pointer genuinely sweeps the batch-lifetime token count
    # 24 data pages * 16 slots = 384 physical slots = ring capacity 96/row
    eng = _engine("paged", num_pages=25, delta=0.0)
    out = eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                    batch_size=4, max_tokens=24)
    assert len(out) == 14 and all(r["n_reasoning"] > 0 for r in out)

    # ...while a batch lifetime of 14 requests does not fit a 96-slot ring:
    need = SlotScheduler.required_capacity(b["prompts"].shape[1], 14, 4, 24)
    assert need > 96
    ring = _engine("ring", capacity=96, delta=0.0)
    with pytest.raises(RuntimeError, match="capacity"):
        ring.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                   batch_size=4, max_tokens=24)


def test_paged_chunk_donates_pools(serve_batch):
    """Donation contract through the paged path: the chunk program aliases
    the ServeState — page pools updated in place, no per-chunk pool copy."""
    b = serve_batch
    eng = _engine("paged")
    out = eng.serve(b["prompts"], b["prompt_len"], jax.random.PRNGKey(0),
                    batch_size=4, max_tokens=24)
    assert len(out) == 6
    # the serve above built the paged chunk program; recover its key
    keys = [k for k in eng.executor._programs
            if k[0] == "chunk" and k[-1] == "paged"]
    assert keys, eng.executor._programs.keys()
    # and the allocator path exercised page reuse end-to-end is covered by
    # test_freed_pages_back_same_batch_admissions; here assert aliasing
    B = 4
    st = eng.start(jnp.asarray(b["prompts"][:B]),
                   jnp.asarray(b["prompt_len"][:B]), jax.random.PRNGKey(1),
                   capacity=16)
    from repro.serving.scheduler import PageAllocator as PA

    alloc = PA(B * 16 + 1, 16, 16, B)
    for row in range(B):
        alloc.ensure(row, 0, 255)
    paged = alloc_paged_cache(eng.model.cfg, B, 256, 16, B * 16 + 1)
    packed = eng.executor.pack_paged(paged, st.cache, alloc.table)
    st = st._replace(cache=packed)
    args = (eng.params, st, jnp.asarray(24, jnp.int32),
            jnp.asarray(8, jnp.int32))
    prog = eng.executor.chunk_program(st, True)
    compiled = prog.lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        cache_bytes(st.cache)


def test_alloc_paged_cache_validation():
    cfg = get_config("tiny")
    with pytest.raises(ValueError, match="multiple"):
        alloc_paged_cache(cfg, 2, 100, 16, 8)        # capacity % ps != 0
    with pytest.raises(ValueError, match="num_pages"):
        alloc_paged_cache(cfg, 2, 256, 16, 1)
    cache = alloc_paged_cache(cfg, 2, 256, 16, 8)
    assert cache["page_table"].shape == (2, 16)
    assert cache["layers"]["seg"]["k"].shape == (
        cfg.n_layers, 8, 16, cfg.n_kv_heads * cfg.resolved_head_dim)
    # and the ring allocator still produces the dense layout
    dense = alloc_cache(cfg, 2, 256)
    assert "page_table" not in dense
