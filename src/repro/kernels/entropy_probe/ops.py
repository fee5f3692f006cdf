"""Dispatching wrapper for the fused EAT entropy probe.

``next_token_entropy(h, w, vocab)`` returns the Shannon entropy (nats) of
softmax(h @ w)[:, :vocab] per row — Eq. (2) of the paper evaluated at the
probe position (Eq. 5 / Eq. 13).

Implementations (``kernels.dispatch.resolve_impl`` picks for ``auto``):
  * pallas — fused streaming kernel (TPU; ``interpret=True`` elsewhere)
  * xla    — chunked scan over vocab tiles with the same online (m, Z, T)
             accumulators; memory-bounded, used on CPU, on meshes and by
             the dry-run
  * ref    — naive oracle
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.dispatch import resolve_impl
from repro.kernels.entropy_probe.ref import next_token_entropy_ref

_NEG_INF = -1e30

#: VMEM the kernel's blocks may hold.  v5e's default scoped-VMEM limit is
#: 16 MiB; the rest is headroom for Mosaic's own scratch.
VMEM_BUDGET = 12 * 2**20


def pick_block_v(B: int, d: int, Vp: int, itemsize: int) -> int:
    """The widest vocab tile (a multiple of 128 that divides ``Vp``, so W is
    never padded in HBM) whose VMEM footprint fits ``VMEM_BUDGET``:

      W tile       d * bV * itemsize, double-buffered
      h block      Bp * d * itemsize, double-buffered
      f32 temps    ~4 live (Bp, bV) tiles: logits, exp, product, mask
      accumulators 3 scratch + 2 output buffers of (Bp, 128) f32

    A ``Vp`` with no 128-multiple divisor gets the widest fitting tile and
    the kernel pads W (test shapes only: ``padded_vocab`` is 256-aligned)."""
    Bp = -(-B // 8) * 8
    fixed = 2 * Bp * d * itemsize + 5 * Bp * 128 * 4
    per_col = 2 * d * itemsize + 4 * Bp * 4
    cap = max(128, (VMEM_BUDGET - fixed) // per_col // 128 * 128)
    if Vp <= cap:
        return Vp
    for bv in range(cap, 127, -128):
        if Vp % bv == 0:
            return bv
    return cap


def _xla_entropy(h, w, vocab, *, block_v=8192):
    B, d = h.shape
    Vp = w.shape[1]
    block_v = min(block_v, Vp)
    pad_v = (-Vp) % block_v
    if pad_v:
        w = jnp.pad(w, ((0, 0), (0, pad_v)))
    n_v = w.shape[1] // block_v
    hf = h.astype(jnp.float32)
    wt = jnp.moveaxis(w.reshape(d, n_v, block_v), 1, 0)  # (n_v, d, bV)

    def step(carry, inp):
        m_prev, z_prev, t_prev = carry
        w_tile, j = inp
        logits = hf @ w_tile.astype(jnp.float32)          # (B, bV)
        col = j * block_v + jnp.arange(block_v)
        valid = col < vocab
        logits = jnp.where(valid, logits, _NEG_INF)
        m_new = jnp.maximum(m_prev, logits.max(-1))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.where(valid, jnp.exp(logits - m_new[:, None]), 0.0)
        z_new = z_prev * alpha + e.sum(-1)
        t_new = t_prev * alpha + (e * jnp.where(valid, logits, 0.0)).sum(-1)
        return (m_new, z_new, t_new), None

    init = (
        jnp.full((B,), _NEG_INF, jnp.float32),
        jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.float32),
    )
    (m, z, t), _ = lax.scan(step, init, (wt, jnp.arange(n_v)))
    return m + jnp.log(z) - t / z


def next_token_entropy(
    h: jax.Array,       # (B, d) final hidden states at the probe position
    w: jax.Array,       # (d, Vp) unembedding (possibly vocab-padded)
    vocab: int,
    *,
    impl: str = "auto",
    interpret: bool = False,
    n_devices: int = 1,
) -> jax.Array:         # (B,) float32, nats
    if impl == "ref":
        return next_token_entropy_ref(h, w, vocab)
    impl = resolve_impl(impl, interpret=interpret, n_devices=n_devices)
    if impl == "pallas":
        from repro.kernels.entropy_probe.kernel import entropy_probe_pallas

        itemsize = jnp.promote_types(h.dtype, w.dtype).itemsize
        block_v = pick_block_v(h.shape[0], h.shape[1], w.shape[1], itemsize)
        return entropy_probe_pallas(h, w, vocab, block_v=block_v,
                                    interpret=interpret)
    return _xla_entropy(h, w, vocab)
