"""Pallas TPU page-table-native flash-decode kernel.

Unlike ``decode_attention`` (which reads a dense per-row (B, C, Hkv, hd)
cache), this kernel reads K/V **directly from the physical page pools**
through a per-row compacted page list: the grid is (batch, page-rank) and
the BlockSpec index maps resolve rank ``j`` of row ``b`` to physical page
``pages[b, j]`` via scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so
the only KV bytes that ever move are the mapped pages — per-token cost is
O(mapped pages), independent of the logical cache capacity.  Ranks at or
past ``counts[b]`` skip the whole accumulation (``pl.when``) and their
index map repeats the row's last mapped page, so Pallas issues no new DMA
for them.

Layout (Mosaic tiles the last two dimensions of every block; each is
either 8x128-aligned or the full array dimension):

* one grid step moves one whole page for all kv heads: the pools are
  viewed as (P, ps, Hkv*D) — a free reshape — and a block is
  (1, ps, Hkv*D), full in both tiled dims; head ``h`` is the static lane
  slice [h*D, (h+1)*D) of it;
* q is regrouped to (B, Hkv, R, Dk) with R = m*g rounded up to a multiple
  of 8; padding rows carry position -1, so they are fully masked and are
  sliced off the output;
* per-slot positions ride as (B*NBK, 1, ps) and query positions as
  (B, R, 1): unit dims make the tiled dims full;
* the running max / sum are (Hkv, R, 1) scratch, the accumulator
  (Hkv, R, Dv): nothing is rank-1.

GQA grouping and the hard-zero masking discipline are carried over from
``decode_attention``: masking uses explicit per-slot positions, and masked
probabilities are exact 0.0 — combined with the sequential per-page
accumulation order of ``ref.block_decode_attention`` this keeps the
paged==ring bit-exactness argument intact (skipped pages are identity
steps; see ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(pages_ref, counts_ref, qp_ref, bpos_ref, q_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale, window, n_ranks, n_kv,
            dk, dv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # ranks past the row's mapped count hold no KV: skip the accumulation
    # entirely (the identity-step argument in ref.py makes this exact)
    @pl.when(j < counts_ref[b])
    def _accumulate():
        qp = qp_ref[0]                                # (R, 1)
        kp = bpos_ref[0]                              # (1, ps)
        valid = (kp >= 0) & (kp <= qp)                # (R, ps)
        if window:
            valid &= (qp - kp) < window
        k_all = k_ref[0].astype(jnp.float32)          # (ps, Hkv*Dk)
        v_all = v_ref[0].astype(jnp.float32)          # (ps, Hkv*Dv)
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale       # (R, Dk)
            k = k_all[:, h * dk:(h + 1) * dk]                 # (ps, Dk)
            v = v_all[:, h * dv:(h + 1) * dv]                 # (ps, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, _NEG_INF)
            m_prev, l_prev = m_scr[h], l_scr[h]               # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            m_scr[h] = m_new
            l_scr[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + pv

    @pl.when(j == n_ranks - 1)
    def _emit():
        for h in range(n_kv):
            l = l_scr[h]
            out = jnp.where(l > 0, acc_scr[h] / jnp.maximum(l, 1e-30), 0.0)
            o_ref[0, h] = out.astype(o_ref.dtype)


def _page_of(b, j, pg, ct):
    """Physical page for rank ``j`` of row ``b``.  A skipped rank repeats
    the row's last mapped page: the same block index as the step before,
    so Pallas issues no DMA for it."""
    return pg[b, jnp.maximum(jnp.minimum(j, ct[b] - 1), 0)]


def paged_attention_pallas(
    q: jax.Array,        # (B, m, Hq, Dk)   m small (decode/probe positions)
    k_pool: jax.Array,   # (P, ps, Hkv, Dk) physical page pool
    v_pool: jax.Array,   # (P, ps, Hkv, Dv)
    pages: jax.Array,    # (B, NBK) int32 physical page per mapped rank
    counts: jax.Array,   # (B,) int32 mapped ranks per row
    bpos: jax.Array,     # (B, NBK, ps) int32 positions (-1 = masked)
    q_pos: jax.Array,    # (B, m)
    *,
    window: int = 0,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, m, Hq, Dk = q.shape
    P_, ps, Hkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    Dv = v_pool.shape[-1]
    NBK = pages.shape[1]
    g = Hq // Hkv
    rows = m * g
    R = -(-rows // 8) * 8
    scale = scale if scale is not None else 1.0 / (Dk ** 0.5)

    # regroup q to (B, Hkv, R, Dk): row r = position (r // g), head (r % g)
    qg = q.reshape(B, m, Hkv, g, Dk).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, rows, Dk)
    qpg = jnp.broadcast_to(q_pos[:, :, None], (B, m, g)).reshape(B, rows)
    if R != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - rows), (0, 0)))
        qpg = jnp.pad(qpg, ((0, 0), (0, R - rows)), constant_values=-1)
    qpg = qpg.astype(jnp.int32)[:, :, None]                  # (B, R, 1)
    bpos = bpos.astype(jnp.int32).reshape(B * NBK, 1, ps)
    k_flat = k_pool.reshape(P_, ps, Hkv * Dk)
    v_flat = v_pool.reshape(P_, ps, Hkv * Dv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # pages, counts
        grid=(B, NBK),
        in_specs=[
            pl.BlockSpec((1, R, 1), lambda b, j, pg, ct: (b, 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda b, j, pg, ct: (b * NBK + j, 0, 0)),
            pl.BlockSpec((1, Hkv, R, Dk), lambda b, j, pg, ct: (b, 0, 0, 0)),
            # the page-table hop: rank j of row b -> physical pool page
            pl.BlockSpec((1, ps, Hkv * Dk),
                         lambda b, j, pg, ct: (_page_of(b, j, pg, ct), 0, 0)),
            pl.BlockSpec((1, ps, Hkv * Dv),
                         lambda b, j, pg, ct: (_page_of(b, j, pg, ct), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, R, Dv),
                               lambda b, j, pg, ct: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, n_ranks=NBK,
                          n_kv=Hkv, dk=Dk, dv=Dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, Dv), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(jnp.asarray(pages, jnp.int32), jnp.asarray(counts, jnp.int32),
      qpg, bpos, qg, k_flat, v_flat)
    # back to (B, m, Hq, Dv)
    return out[:, :, :rows].reshape(B, Hkv, m, g, Dv).transpose(
        0, 2, 1, 3, 4).reshape(B, m, Hq, Dv)
