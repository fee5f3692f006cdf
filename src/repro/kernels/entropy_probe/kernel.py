"""Pallas TPU kernel: fused unembedding + online next-token entropy.

The EAT probe (paper §4.1) needs H(softmax(h W)) for a handful of rows but
the *full* vocabulary (paper App. H computes entropy "over the logits of the
full vocabulary", up to 256k columns).  Materializing (B, V) logits in HBM
makes the probe memory-bound: 2·B·V·2 bytes of logit traffic per
evaluation.  This kernel streams vocab tiles of W through VMEM and keeps
FlashAttention-style running accumulators

    m  = running max(logit)
    Z  = sum exp(logit - m)
    T  = sum exp(logit - m) * logit

merging tiles by rescaling, and emits  H = m + log Z - T / Z.

Grid: (B tiles, V tiles), V innermost.  Block shapes: h (bB, d) stays
resident across the V loop (index map ignores j); the W tile (d, bV)
streams in its storage dtype and feeds the MXU as is, accumulating in
float32 — W is never upcast in VMEM.  bB is a multiple of 8 (the batch is
padded), bV a multiple of 128 or the whole padded vocab.  The accumulators
and the output are (bB, 128) lane-broadcast tiles: Mosaic tiles the last
two dimensions of every block, so nothing here is rank-1.  ``ops.py`` sizes
bV from the VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
#: lane width of the accumulator / output tiles (one vreg row)
LANES = 128


def _kernel(h_ref, w_ref, o_ref, m_scr, z_scr, t_scr, *, vocab, block_v, n_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # (bB, bV) f32

    # mask padded vocab columns
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = col < vocab
    logits = jnp.where(valid, logits, _NEG_INF)

    m_prev = m_scr[...][:, :1]                   # (bB, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    e = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    z_new = z_scr[...][:, :1] * alpha + jnp.sum(e, axis=-1, keepdims=True)
    t_new = t_scr[...][:, :1] * alpha + jnp.sum(
        e * jnp.where(valid, logits, 0.0), axis=-1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    z_scr[...] = jnp.broadcast_to(z_new, z_scr.shape)
    t_scr[...] = jnp.broadcast_to(t_new, t_scr.shape)

    @pl.when(j == n_v - 1)
    def _emit():
        m, z, t = m_scr[...], z_scr[...], t_scr[...]
        o_ref[...] = (m + jnp.log(z) - t / z).astype(o_ref.dtype)


def entropy_probe_pallas(
    h: jax.Array,      # (B, d)
    w: jax.Array,      # (d, Vp)
    vocab: int,
    *,
    block_b: int = 0,
    block_v: int = 1024,
    interpret: bool = False,
) -> jax.Array:        # (B,) float32
    """``block_b=0`` keeps the whole (8-padded) batch resident, so W streams
    from HBM exactly once per call."""
    B, d = h.shape
    Vp = w.shape[1]
    # the matmul runs in the wider of the two storage dtypes (bf16 x bf16
    # on the serve path), accumulating in f32
    dt = jnp.promote_types(h.dtype, w.dtype)
    h, w = h.astype(dt), w.astype(dt)
    Bp = -(-B // 8) * 8
    block_b = Bp if block_b <= 0 else min(block_b, Bp)
    block_v = min(block_v, Vp)

    pad_b = (-B) % block_b
    if pad_b:
        h = jnp.pad(h, ((0, pad_b), (0, 0)))
    pad_v = (-Vp) % block_v
    if pad_v:
        w = jnp.pad(w, ((0, 0), (0, pad_v)))
    Bpp, Vpp = h.shape[0], w.shape[1]
    n_b, n_v = Bpp // block_b, Vpp // block_v

    out = pl.pallas_call(
        functools.partial(_kernel, vocab=vocab, block_v=block_v, n_v=n_v),
        grid=(n_b, n_v),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bpp, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_b, LANES), jnp.float32),
            pltpu.VMEM((block_b, LANES), jnp.float32),
            pltpu.VMEM((block_b, LANES), jnp.float32),
        ],
        name="entropy_probe",
        interpret=interpret,
    )(h, w)
    return out[:B, 0]
