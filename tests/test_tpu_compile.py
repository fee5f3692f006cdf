"""The serve-path Pallas kernels compile for a TPU v5e at the widths of the
served models — Qwen3-1.7B (d=2048, 16/8 heads) and the eat-proxy-1.5b
monitor (d=1536, 12/2 heads), vocabulary 151936 padded to 152064.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, so Mosaic's tiling and VMEM refusals show
up here instead of on the chip.  The topology is described inside a
fixture, never while a module is imported: only one process at a time may
load the TPU compiler library, and test workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.entropy_probe.kernel import entropy_probe_pallas
from repro.kernels.entropy_probe.ops import pick_block_v
from repro.kernels.paged_attention.kernel import paged_attention_pallas

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

    def attend(q, kp, vp, pages, counts, bpos, q_pos):
        return paged_attention_pallas(q, kp, vp, pages, counts, bpos, q_pos,
                                      scale=hd ** -0.5)

    i32 = jnp.int32
    compiled = jax.jit(attend).lower(
        _sds((B, m, Hq, hd), cfg.dtype, one_chip),
        _sds((n_pages, ps, Hkv, hd), cfg.dtype, one_chip),
        _sds((n_pages, ps, Hkv, hd), cfg.dtype, one_chip),
        _sds((B, NBK), i32, one_chip),
        _sds((B,), i32, one_chip),
        _sds((B, NBK, ps), i32, one_chip),
        _sds((B, m), i32, one_chip)).compile()
    _assert_kernel(compiled)
