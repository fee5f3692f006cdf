"""The serve-path Pallas kernels compile for a TPU v5e at the widths of the
served models — Qwen3-1.7B (d=2048, 16/8 heads) and the eat-proxy-1.5b
monitor (d=1536, 12/2 heads), vocabulary 151936 padded to 152064 — and a
paged decode step through them moves no KV pool.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, so Mosaic's tiling and VMEM refusals show
up here instead of on the chip.  The topology is described inside a
fixture, never while a module is imported: only one process at a time may
load the TPU compiler library, and test workers import every test file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.entropy_probe.kernel import entropy_probe_pallas
from repro.kernels.entropy_probe.ops import pick_block_v
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.models import transformer as tfm
from repro.serving.cache import alloc_paged_cache, cache_bytes
from repro.sharding.partition import ShardCtx

GEN, PROXY = "qwen3-1.7b", "eat-proxy-1.5b"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", [GEN, PROXY])
@pytest.mark.parametrize("B", [8, 32])
def test_entropy_probe_compiles(one_chip, arch, B):
    cfg = get_config(arch)
    d, Vp = cfg.d_model, cfg.padded_vocab
    block_v = pick_block_v(B, d, Vp, jnp.dtype(cfg.dtype).itemsize)
    assert Vp % block_v == 0          # W is streamed without a pad copy

    def probe(h, w):
        return entropy_probe_pallas(h, w, cfg.vocab, block_v=block_v)

    compiled = jax.jit(probe).lower(
        _sds((B, d), cfg.dtype, one_chip),
        _sds((d, Vp), cfg.dtype, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("arch", [GEN, PROXY])
@pytest.mark.parametrize("m", [1, 2])       # decode step, </think>+prefix probe
def test_paged_attention_compiles(one_chip, arch, m):
    cfg = get_config(arch)
    B, ps, NBK, n_pages = 8, 16, 40, 8 * 40 + 1
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def attend(q, kp, vp, pages, counts, bpos, q_pos, layer):
        return paged_attention_pallas(q, kp, vp, pages, counts, bpos, q_pos,
                                      layer, scale=hd ** -0.5)

    i32 = jnp.int32
    compiled = jax.jit(attend).lower(
        _sds((B, m, Hq, hd), cfg.dtype, one_chip),
        _sds((cfg.n_layers, n_pages, ps, Hkv * hd), cfg.dtype, one_chip),
        _sds((cfg.n_layers, n_pages, ps, Hkv * hd), cfg.dtype, one_chip),
        _sds((B, NBK), i32, one_chip),
        _sds((B,), i32, one_chip),
        _sds((B, NBK, ps), i32, one_chip),
        _sds((B, m), i32, one_chip),
        _sds((), i32, one_chip)).compile()
    _assert_kernel(compiled)


#: opcodes that move data wholesale; a reshape left in optimized HLO is a
#: relayout (a free one is printed as ``bitcast``)
_MOVES = {"copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice", "reshape"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(")
_SHAPE = re.compile(r"\b(?:pred|bf16|[suf]\d+)\[([\d,]*)\]")


def pool_moves(hlo: str, sizes: set[int]) -> list[str]:
    """Instructions of optimized HLO (fused computations included) that
    copy, slice, update-slice or relayout an operand or result of one of
    ``sizes`` elements — any layout of the array counts."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(1) not in _MOVES:
            continue
        counts = {math.prod(int(d) for d in dims.split(",") if d)
                  for dims in _SHAPE.findall(line)}
        if counts & sizes:
            found.append(line.strip()[:200])
    return found


def test_pool_moves_reads_hlo_lines():
    hlo = """
  %copy.3 = bf16[4,6]{1,0} copy(bf16[4,6]{0,1} %p)
  %ds = bf16[6]{0} dynamic-slice(bf16[4,6]{1,0} %p, s32[] %i, s32[] %j)
  %b = bf16[24]{0} bitcast(bf16[4,6]{1,0} %p)
  ROOT %r.1 = bf16[2,12]{1,0} reshape(bf16[4,6]{1,0} %p)
  %s = bf16[4,6]{1,0} scatter(bf16[4,6]{1,0} %p, s32[1]{0} %i, bf16[6] %u)"""
    names = [re.search(r"%[\w.]+", ln).group() for ln in pool_moves(hlo, {24})]
    assert names == ["%copy.3", "%ds", "%r.1"]
    assert pool_moves(hlo, {6}) == [pool_moves(hlo, {24})[1]]


@pytest.mark.parametrize("arch", [GEN, PROXY])
def test_paged_decode_step_moves_no_pool(one_chip, monkeypatch, arch):
    """One paged decode step of ``forward_cached`` through the kernel, the
    cache donated: the lane-dense K/V stacks ride the layer scan's carry,
    take one scatter per layer in place, and are read by the kernel at the
    layer index — no copy, slice, update-slice or relayout of a layer's
    pool or of the stack anywhere in the program."""
    from repro.kernels.paged_attention import ops as paged_ops

    # the backend here is the CPU's: take the kernel as the chip would
    monkeypatch.setattr(paged_ops, "resolve_impl", lambda impl, **_: impl)
    cfg = get_config(arch)
    # a stack over the chip's VMEM, so it stays in HBM as a real pool does
    B, ps, C, n_pages = 8, 16, 256, 1025
    dt = jnp.dtype(cfg.dtype)
    sds = lambda tree: jax.tree_util.tree_map(                # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    params = jax.eval_shape(lambda k: tfm.init_stack(k, cfg, dt),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: alloc_paged_cache(
        cfg, B, C, ps, n_pages, block_bucket=C // ps))

    def step(params, x, pos1d, cache):
        slots = tfm.write_slots(cache["cur"], 1, C)
        _, cache, _ = tfm.forward_cached(
            params, x, pos1d, pos1d, slots, cache, cfg, ShardCtx(),
            paged_impl="pallas", page_block=ps)
        return cache

    compiled = jax.jit(step, donate_argnums=3).lower(
        sds(params), _sds((B, 1, cfg.d_model), dt, one_chip),
        _sds((B, 1), jnp.int32, one_chip), sds(cache)).compile()
    hlo = compiled.as_text()
    assert "paged_attention" in hlo
    layer_pool = n_pages * ps * cfg.n_kv_heads * cfg.resolved_head_dim
    assert pool_moves(hlo, {layer_pool, cfg.n_layers * layer_pool}) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        cache_bytes(cache["layers"])
