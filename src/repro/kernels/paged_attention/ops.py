"""Dispatching wrapper for page-table-native decode attention.

Two entry points, one algorithm (``ref.block_decode_attention``'s
sequential per-page online softmax):

* ``paged_decode_attention`` — decode/probe attention straight off the
  physical page pools through a compacted per-row page list (no gathered
  logical view; O(mapped pages) per token).
* ``ring_decode_attention``  — the SAME algorithm over a dense ring cache,
  viewed as logical blocks via a free reshape (all blocks "mapped").

Because the paged caller visits exactly the mapped subsequence of the
blocks the ring caller visits — and skipped blocks are exact identity
steps (ref.py) — a paged serve and a ring serve through these ops produce
bit-identical outputs.  That per-impl invariant is what the serving stack's
``attn_impl != "gather"`` modes rely on (docs/architecture.md §Paged
attention kernel).

``impl``: ``auto`` (``kernels.dispatch.resolve_impl``: the kernel on one
TPU device, else xla), ``xla`` (the block-scan reference), ``pallas`` (the
kernel; off the TPU only with ``interpret=True``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_impl
from repro.kernels.paged_attention.ref import (
    block_decode_attention,
    paged_attention_xla,
)

#: physical page id reserved as the trash page (serving.cache.PAGE_TRASH);
#: duplicated here so the kernel package stays import-light
PAGE_TRASH = 0


def block_positions(kv_pos: jax.Array, pages: jax.Array,
                    logical: jax.Array, page_size: int) -> jax.Array:
    """Per-bucket slot positions from the logical ``pos`` array.

    kv_pos: (B, C); pages/logical: (B, NBK).  Rank ``j`` of row ``b`` holds
    logical block ``logical[b, j]`` — its positions are the corresponding
    ps-slice of ``kv_pos``.  Ranks mapped to the trash page are forced to
    -1 (fully masked): THE hard-zero discipline that makes unmapped /
    padding ranks exact identity steps."""
    B, C = kv_pos.shape
    pos_blocks = kv_pos.reshape(B, C // page_size, page_size)
    bpos = jnp.take_along_axis(pos_blocks, logical[:, :, None], axis=1)
    return jnp.where((pages != PAGE_TRASH)[:, :, None], bpos, -1)


def paged_decode_attention(
    q: jax.Array,        # (B, m, Hq, Dk)
    k_pool: jax.Array,   # (P, ps, Hkv, Dk)
    v_pool: jax.Array,   # (P, ps, Hkv, Dv)
    pages: jax.Array,    # (B, NBK) int32
    counts: jax.Array,   # (B,) int32 mapped ranks per row
    bpos: jax.Array,     # (B, NBK, ps) int32 (-1 = masked)
    q_pos: jax.Array,    # (B, m)
    *,
    window: int = 0,
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
    n_devices: int = 1,
) -> jax.Array:
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    impl = resolve_impl(impl, interpret=interpret, n_devices=n_devices)
    if impl == "pallas":
        from repro.kernels.paged_attention.kernel import paged_attention_pallas

        return paged_attention_pallas(
            q, k_pool, v_pool, pages, counts, bpos, q_pos,
            window=window, scale=scale, interpret=interpret,
        )
    return paged_attention_xla(q, k_pool, v_pool, pages, bpos, q_pos,
                               scale=scale, window=window)


def ring_decode_attention(
    q: jax.Array,        # (B, m, Hq, Dk)
    k: jax.Array,        # (B, C, Hkv, Dk) dense ring cache
    v: jax.Array,        # (B, C, Hkv, Dv)
    q_pos: jax.Array,    # (B, m)
    kv_pos: jax.Array,   # (B, C)
    *,
    page_size: int,
    window: int = 0,
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
    n_devices: int = 1,
) -> jax.Array:
    """The ring cache through the block algorithm: every logical block is
    "mapped" at its own rank, so the scan covers the whole capacity in
    logical order — the dense comparator whose accumulation the paged path
    reproduces bit-for-bit.  A capacity that is not a page multiple is
    padded with masked slots (an exact no-op: appended identity steps)."""
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    impl = resolve_impl(impl, interpret=interpret, n_devices=n_devices)
    B, C = kv_pos.shape
    pad = (-C) % page_size
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    Cp = kv_pos.shape[1]
    NB = Cp // page_size
    bpos = kv_pos.reshape(B, NB, page_size)
    if impl == "pallas":
        from repro.kernels.paged_attention.kernel import paged_attention_pallas

        # the dense rows become a (B*NB)-page pool with an identity list
        Hkv, Dk = k.shape[2], k.shape[3]
        pool_k = k.reshape(B * NB, page_size, Hkv, Dk)
        pool_v = v.reshape(B * NB, page_size, Hkv, v.shape[-1])
        ranks = jnp.arange(NB, dtype=jnp.int32)[None, :]
        pages = jnp.arange(B, dtype=jnp.int32)[:, None] * NB + ranks
        counts = jnp.full((B,), NB, jnp.int32)
        return paged_attention_pallas(
            q, pool_k, pool_v, pages, counts, bpos, q_pos,
            window=window, scale=scale, interpret=interpret,
        )
    kb = k.reshape(B, NB, page_size, k.shape[2], k.shape[3])
    vb = v.reshape(B, NB, page_size, v.shape[2], v.shape[3])
    return block_decode_attention(q, kb, vb, bpos, q_pos,
                                  scale=scale, window=window)
