"""Page-table-native decode attention: XLA reference + the accumulation
contract.

``block_decode_attention`` is the ONE algorithm every impl in this package
follows: an online-softmax ``lax.scan`` over fixed-size KV *blocks* (pages),
carrying ``(running max, sum-of-exp, weighted-V accumulator)`` per query row.
Blocks are visited in increasing logical order and a fully-masked block is an
exact identity step on the carry:

    m_new  = max(m_prev, max(s_block))   -> m_prev          (all s == -1e30)
    alpha  = exp(m_prev - m_new)         -> exp(0) == 1.0   (exact)
    l_new  = l_prev * 1.0 + sum(0.0)     -> l_prev          (exact)
    acc    = acc * 1.0 + P@V(P == 0.0)   -> acc             (exact)

so SKIPPING a fully-masked block — which is all an unmapped page can ever be,
because every read of the trash page is position-masked to a hard zero —
produces bitwise-identical output to processing it.  That identity, not any
property of XLA's reduction lowering, is what makes the page-native path
reproduce the dense ring path bit-for-bit: the ring caller scans ALL logical
blocks of its (B, C) cache, the paged caller scans only the mapped subset in
the same logical order, and the carries agree at every common block.
(Compacting pages through the dense chunked-softmax ``_xla_attention`` was
measured to drift by ~1 ulp on XLA CPU — its single-chunk reductions are not
zero-removal-invariant — which is why this package owns its own scan.)

``paged_attention_xla`` is the no-materialize fallback: a *block-bucketed*
gather — only the pages named by the compacted per-row page list are pulled
from the pool, so per-token cost is O(batch-max mapped pages), independent of
the logical capacity — followed by the block scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def block_decode_attention(
    q: jax.Array,        # (B, m, Hq, Dk)   m small (decode/probe positions)
    kb: jax.Array,       # (B, NBK, ps, Hkv, Dk)  KV blocks, logical order
    vb: jax.Array,       # (B, NBK, ps, Hkv, Dv)
    bpos: jax.Array,     # (B, NBK, ps) int32 slot positions (-1 = masked)
    q_pos: jax.Array,    # (B, m)
    *,
    scale: float,
    window: int = 0,
) -> jax.Array:          # (B, m, Hq, Dv)
    """Sequential per-block online softmax — THE accumulation-order contract
    shared by the ring (all blocks) and paged (mapped blocks only) callers."""
    B, m, Hq, Dk = q.shape
    Hkv = kb.shape[3]
    Dv = vb.shape[-1]
    g = Hq // Hkv

    # matmul inputs stay in storage dtype, f32 only in accumulators — the
    # same discipline as flash_attention's XLA path (§Perf P3')
    qf = q * jnp.asarray(scale, q.dtype)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kcb, vcb, pb = xs                     # (B, ps, Hkv, D*), (B, ps)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, jnp.repeat(kcb, g, axis=2),
                       preferred_element_type=jnp.float32)  # (B, Hq, m, ps)
        valid = pb[:, None, None, :] >= 0
        valid &= pb[:, None, None, :] <= q_pos[:, None, :, None]
        if window:
            valid &= (q_pos[:, None, :, None] - pb[:, None, None, :]) < window
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vcb.dtype),
                        jnp.repeat(vcb, g, axis=2),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, Hq, m), _NEG_INF, jnp.float32),
        jnp.zeros((B, Hq, m), jnp.float32),
        jnp.zeros((B, Hq, m, Dv), jnp.float32),
    )
    (_, l, acc), _ = lax.scan(
        body, init,
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
         jnp.moveaxis(bpos, 1, 0)),
    )
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30),
                    0.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # (B, m, Hq, Dv)


def paged_attention_xla(
    q: jax.Array,        # (B, m, Hq, Dk)
    k_pool: jax.Array,   # (L, P, ps, Hkv*Dk)  stacked physical page pools
    v_pool: jax.Array,   # (L, P, ps, Hkv*Dv)
    pages: jax.Array,    # (B, NBK) int32 physical page per mapped-block rank
    bpos: jax.Array,     # (B, NBK, ps) int32 positions (-1 = masked)
    q_pos: jax.Array,    # (B, m)
    layer=0,             # int32 scalar: the layer of the stacks to read
    *,
    scale: float,
    window: int = 0,
) -> jax.Array:
    """Block-bucketed gather of only-mapped pages, then the block scan.

    The gather touches ``NBK`` pages per row of one layer — the compacted
    mapped-block list, NOT the logical extent, and never a whole layer of
    the stack — so HBM traffic and compute are O(mapped pages).  Padding
    ranks point at the trash page with all positions -1: identity steps
    (see module docstring)."""
    B, NBK = pages.shape
    ps, Dk = k_pool.shape[2], q.shape[-1]
    Hkv = k_pool.shape[3] // Dk
    kb = k_pool[layer, pages].reshape(B, NBK, ps, Hkv, Dk)
    vb = v_pool[layer, pages].reshape(B, NBK, ps, Hkv, -1)
    return block_decode_attention(q, kb, vb, bpos, q_pos,
                                  scale=scale, window=window)
