"""Layer-stack assembly for all architecture families.

Layers are *stacked* along a leading axis and driven by ``lax.scan``
(MaxText-style) so the lowered HLO is O(1) in depth — essential for the
512-device dry-run compiles of 60-layer models on one CPU core.

Two forward paths:

* ``forward_train`` — full-sequence self-attention, no cache, optional
  rematerialization + Megatron-style sequence-parallel residual stream
  (S sharded over the model axis between blocks).
* ``forward_cached`` — the serving path, unified for prefill (m = S) and
  decode/probe (m small).  The KV/SSM cache is a pytree carried through the
  layer scan; new K/V are scattered into caller-chosen ``slots``.  Probing
  (EAT) is just a forward_cached call whose returned cache is discarded.

Cache layout (created in serving/cache.py):
  {"layers": <per-segment stacked entries>, "pos": (B, C) int32 slot
   positions (-1 = empty), "cur": scalar int32 committed length}
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as att
from repro.models import ssm as ssm_mod
from repro.models.common import mlp_apply, mlp_init, rmsnorm, rmsnorm_init
from repro.models.moe import moe_apply, moe_init
from repro.sharding.partition import ShardCtx

Params = dict
Cache = dict


def write_slots(cur, m: int, capacity: int):
    """Slot indices for the next ``m`` tokens (ring when capacity
    exceeded) — the slot convention forward_cached expects."""
    return (cur + jnp.arange(m, dtype=jnp.int32)) % capacity


# ------------------------------------------------------------ paged KV cache
#
# The block-paged cache (docs/architecture.md §Paged KV cache) keeps the
# ring cache's LOGICAL addressing — the same ``slots`` / ``pos`` / ``cur``
# convention above — but stores K/V in a pool of fixed-size physical pages:
# pool (num_pages, page_size, ...tail) plus a per-row page table (B, NB)
# mapping logical block ``slot // page_size`` -> physical page.  Entry 0 of
# the pool is a reserved trash page: unmapped blocks read and write it, and
# every read from it is position-masked (pos=-1 slots contribute exactly
# 0.0 in all attention impls), so the gathered logical view is
# element-for-element identical to the ring buffer wherever it matters.


def gather_pages(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Physical pages -> the logical ring view.

    pool: (P, ps, ...tail); table: (B, NB) int32.
    Returns (B, NB*ps, ...tail) — the per-row logical cache the attention
    mask addresses by ``kv_pos`` exactly as it addresses the ring buffer.
    """
    B, NB = table.shape
    g = pool[table]                                   # (B, NB, ps, ...tail)
    return g.reshape((B, NB * pool.shape[1]) + pool.shape[2:])


def scatter_pages(pool: jax.Array, table: jax.Array, slots: jax.Array,
                  new: jax.Array) -> jax.Array:
    """Write ``new`` (B, m, ...tail) at logical ``slots`` (m,) through the
    page table.  Rows whose block is unmapped (table entry 0) land in the
    trash page — a don't-care, since their ``pos`` stays -1/masked."""
    ps = pool.shape[1]
    pages = table[:, slots // ps]                     # (B, m)
    offs = jnp.broadcast_to((slots % ps)[None, :], pages.shape)
    return pool.at[pages, offs].set(new.astype(pool.dtype))


def page_native_ok(cfg: ModelConfig, ctx: ShardCtx, m: int) -> bool:
    """True when the page-native decode attention (kernels/paged_attention)
    can serve this call: GQA entries (MLA latents keep the gather path),
    decode/probe-sized query widths, and — on a mesh — kv heads divisible
    by the model axis so the pools shard over heads (the not-divisible case
    belongs to ``seq_sharded_decode_attention``).  The SAME predicate gates
    the ring and the paged branches, so both backends always pick the same
    implementation — the per-impl paged==ring bit-exactness contract."""
    return (
        cfg.mla is None and m <= 8
        and (ctx.mesh is None or cfg.n_kv_heads % ctx.model_size == 0)
    )


# ===================================================================== init


def _stack_init(key, n: int, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def attn_block_init(key, cfg: ModelConfig, dtype, *, use_moe: bool,
                    d_ff: int, d_in: int | None = None, cross: bool = False) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"norm1": rmsnorm_init(d_in or d, dtype, cfg.rmsnorm_one_plus)}
    p["attn"] = (
        att.mla_init(k1, cfg, dtype) if cfg.mla is not None
        else att.gqa_init(k1, cfg, dtype, d_in=d_in)
    )
    if cross:
        p["norm_c"] = rmsnorm_init(d, dtype, cfg.rmsnorm_one_plus)
        p["cross"] = att.cross_attn_init(k3, cfg, dtype)
    p["norm2"] = rmsnorm_init(d, dtype, cfg.rmsnorm_one_plus)
    if use_moe:
        p["moe"] = moe_init(k2, cfg, dtype)
    else:
        p["ffn"] = mlp_init(k2, cfg, d_ff, dtype, d_in=d)
    return p


def ssm_block_init(key, cfg: ModelConfig, dtype) -> dict:
    k1, _ = jax.random.split(key)
    return {
        "norm": rmsnorm_init(cfg.d_model, dtype, cfg.rmsnorm_one_plus),
        "ssm": ssm_mod.ssm_init(k1, cfg, dtype),
    }


def init_stack(key, cfg: ModelConfig, dtype) -> Params:
    """All non-embedding parameters, organized by scan segment."""
    ks = jax.random.split(key, 8)
    p: Params = {"final_norm": rmsnorm_init(cfg.d_model, dtype, cfg.rmsnorm_one_plus)}

    if cfg.arch_type in ("dense", "vlm"):
        p["layers"] = _stack_init(
            ks[0], cfg.n_layers,
            lambda k: attn_block_init(k, cfg, dtype, use_moe=False, d_ff=cfg.d_ff),
        )
    elif cfg.arch_type == "moe":
        fk = cfg.moe.first_k_dense
        dense_ff = cfg.moe.dense_d_ff or cfg.d_ff
        if fk:
            p["dense_layers"] = _stack_init(
                ks[0], fk,
                lambda k: attn_block_init(k, cfg, dtype, use_moe=False, d_ff=dense_ff),
            )
        p["moe_layers"] = _stack_init(
            ks[1], cfg.n_layers - fk,
            lambda k: attn_block_init(k, cfg, dtype, use_moe=True, d_ff=cfg.d_ff),
        )
    elif cfg.arch_type == "ssm":
        p["layers"] = _stack_init(ks[0], cfg.n_layers, lambda k: ssm_block_init(k, cfg, dtype))
    elif cfg.arch_type == "hybrid":
        kinds = cfg.block_kinds()
        pat = cfg.hybrid_pattern
        n_ssm_per = sum(1 for k in pat if k == "ssm")
        n_groups = len(kinds) // len(pat)
        p["groups"] = _stack_init(
            ks[0], n_groups,
            lambda k: _stack_init(k, n_ssm_per, lambda kk: ssm_block_init(kk, cfg, dtype)),
        )
        p["shared_attn"] = attn_block_init(
            ks[1], cfg, dtype, use_moe=False, d_ff=cfg.d_ff, d_in=2 * cfg.d_model
        )
    elif cfg.arch_type == "encdec":
        p["enc_layers"] = _stack_init(
            ks[0], cfg.n_encoder_layers,
            lambda k: attn_block_init(k, cfg, dtype, use_moe=False, d_ff=cfg.d_ff),
        )
        p["enc_norm"] = rmsnorm_init(cfg.d_model, dtype, cfg.rmsnorm_one_plus)
        p["dec_layers"] = _stack_init(
            ks[1], cfg.n_layers,
            lambda k: attn_block_init(k, cfg, dtype, use_moe=False, d_ff=cfg.d_ff, cross=True),
        )
    else:
        raise ValueError(cfg.arch_type)
    return p


# ===================================================================== blocks


def _res_constraint(x, ctx: ShardCtx, seq_parallel: bool):
    if ctx.mesh is None:
        return x
    b = ctx.batch_spec_entry()
    return ctx.wsc(x, P(b, ctx.model_axis, None) if seq_parallel else P(b, None, None))


def _heads_constraint(q, cfg, ctx: ShardCtx):
    if ctx.mesh is None:
        return q
    n_h = q.shape[2]
    ax = ctx.model_axis if n_h % ctx.model_size == 0 else None
    return ctx.wsc(q, P(ctx.batch_spec_entry(), None, ax, None))


def attn_block_full(
    p: dict, x, positions, pos1d, cfg: ModelConfig, ctx: ShardCtx, *,
    use_moe: bool, causal: bool = True, window: int = 0, attn_impl: str = "auto",
    seq_parallel: bool = False, enc_kv=None, enc_pos=None, x_extra=None,
):
    """Full-sequence block (train / encoder).  Returns (x, aux)."""
    h_in = x if x_extra is None else jnp.concatenate([x, x_extra], axis=-1)
    if seq_parallel:
        # §Perf P3': force the sequence-parallel all-gather HERE — on the
        # bf16 d_model-wide RESIDUAL — not after the q/k projections (GSPMD
        # otherwise gathers 128 heads x 192 dims for MLA, in f32: ~20x the
        # bytes).  Gathering before the norm keeps the moved tensor bf16
        # (the norm's f32 intermediates stay local; its recompute over the
        # model axis is elementwise — negligible).
        h_in = _res_constraint(h_in, ctx, False)
    h = rmsnorm(h_in, p["norm1"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    if cfg.mla is not None:
        y, _ = att.mla_self_attention(
            p["attn"], h, positions, pos1d, cfg, window=window, attn_impl=attn_impl
        )
    else:
        y, _ = att.gqa_self_attention(
            p["attn"], h, positions, pos1d, cfg, causal=causal, window=window,
            attn_impl=attn_impl,
        )
    x = _res_constraint(x + y, ctx, seq_parallel)

    if enc_kv is not None:
        hc = rmsnorm(x, p["norm_c"], cfg.norm_eps, cfg.rmsnorm_one_plus)
        ck, cv = att.cross_attn_kv(p["cross"], enc_kv, cfg)
        x = x + att.cross_attention(p["cross"], hc, ck, cv, enc_pos, cfg, attn_impl=attn_impl)

    x_full = _res_constraint(x, ctx, False) if seq_parallel else x
    h2 = rmsnorm(x_full, p["norm2"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    if use_moe:
        f, aux = moe_apply(p["moe"], h2, cfg, ctx)
    else:
        f, aux = mlp_apply(p["ffn"], h2, cfg), jnp.zeros((), jnp.float32)
    x = _res_constraint(x + f, ctx, seq_parallel)
    return x, aux


def attn_block_cached(
    p: dict, x, positions, pos1d, cfg: ModelConfig, ctx: ShardCtx,
    entry: dict, kv_pos, slots, *,
    use_moe: bool, window: int = 0, attn_impl: str = "auto",
    cross_cache: tuple | None = None, enc_pos=None, x_extra=None,
    paged: tuple | None = None, paged_impl: str = "gather",
    page_block: int = 16, interpret: bool = False,
):
    """Cached block (prefill m=S / decode m small).  Returns (x, entry, aux).

    ``entry`` holds this layer's cache arrays; new K/V are scattered into
    ``slots`` (B-shared (m,) int32) before the attention read.  With
    ``paged=(page_table, page_size, blocks)`` the entry arrays are page
    POOLS ((P, ps, ...) instead of (B, C, ...)): new K/V scatter through
    the page table, and the attention read depends on ``paged_impl``:

    * ``"gather"`` (default): materialize the gathered logical view — same
      mask, same ``kv_pos``, bit-identical to the ring (docs/architecture.md);
    * ``"auto"/"xla"/"pallas"`` with ``blocks`` present (the engine's
      compacted mapped-page list): read K/V straight off the pools through
      the page list — O(mapped pages) per token, no logical-view
      materialization.  The ring branch routes through the SAME
      block-sequential algorithm (``ring_decode_attention``) so the two
      backends stay bit-identical per impl (kernels/paged_attention/ref.py).
    """
    h_in = x if x_extra is None else jnp.concatenate([x, x_extra], axis=-1)
    h = rmsnorm(h_in, p["norm1"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    if cfg.mla is not None:
        q_nope, q_rope = att.mla_q(p["attn"], h, positions, cfg)
        c_new, kr_new = att.mla_latent(p["attn"], h, positions, cfg)
        entry = dict(entry)
        if paged is not None:
            table = paged[0]
            entry["c"] = scatter_pages(entry["c"], table, slots, c_new)
            entry["kr"] = scatter_pages(entry["kr"], table, slots, kr_new)
            cache_c = gather_pages(entry["c"], table)
            cache_kr = gather_pages(entry["kr"], table)
        else:
            entry["c"] = entry["c"].at[:, slots].set(c_new.astype(entry["c"].dtype))
            entry["kr"] = entry["kr"].at[:, slots].set(kr_new.astype(entry["kr"].dtype))
            cache_c, cache_kr = entry["c"], entry["kr"]
        y = att.mla_absorbed_attend(
            p["attn"], q_nope, q_rope, pos1d, cfg, cache_c, cache_kr, kv_pos,
            window=window, attn_impl=attn_impl, ctx=ctx,
        )
    else:
        q, k_new, v_new = att.gqa_qkv(p["attn"], h, positions, cfg)
        q = _heads_constraint(q, cfg, ctx)
        native = paged_impl != "gather" and page_native_ok(cfg, ctx, x.shape[1])
        entry = dict(entry)
        o = None
        if paged is not None:
            table, ps, blocks = paged
            entry["k"] = scatter_pages(entry["k"], table, slots, k_new)
            entry["v"] = scatter_pages(entry["v"], table, slots, v_new)
            if native and blocks is None:
                # a silent gather fallback here would split the per-impl
                # paged==ring pairing (the ring side WOULD run the block
                # scan) — fail at trace time instead; paged caches for the
                # native impls come from cache.alloc_paged_template
                raise ValueError(
                    f"paged_impl={paged_impl!r} needs the compacted page "
                    f"list: allocate the cache with "
                    f"serving.cache.alloc_paged_template(..., native=True) "
                    f"(or alloc_paged_cache(block_bucket=...))")
            if native:
                # page-native read: pools + compacted page list, no
                # gathered logical view (O(mapped pages) per token)
                from repro.kernels.paged_attention import ops as paged_ops

                bpos = paged_ops.block_positions(
                    kv_pos, blocks["pages"], blocks["logical"], ps)
                o = paged_ops.paged_decode_attention(
                    q, entry["k"], entry["v"], blocks["pages"],
                    blocks["count"], bpos, pos1d, window=window,
                    scale=att.attn_scale(cfg), impl=paged_impl,
                    interpret=interpret, n_devices=ctx.n_devices,
                )
            else:
                k_view = gather_pages(entry["k"], table)
                v_view = gather_pages(entry["v"], table)
        else:
            ps = page_block
            entry["k"] = entry["k"].at[:, slots].set(k_new.astype(entry["k"].dtype))
            entry["v"] = entry["v"].at[:, slots].set(v_new.astype(entry["v"].dtype))
            k_view, v_view = entry["k"], entry["v"]
        if o is not None:
            pass
        elif att.use_seq_sharded_cache(cfg, ctx, x.shape[1]):
            # §Perf P1': partial-softmax decode over the seq-sharded cache
            # (avoids GSPMD all-gathering the cache every attention read)
            o = att.seq_sharded_decode_attention(
                q, k_view, v_view, pos1d, kv_pos, ctx,
                window=window, scale=att.attn_scale(cfg),
            )
        elif native and paged is None:
            # the ring comparator of the page-native path: the SAME
            # block-sequential accumulation over the dense cache (all
            # blocks visited in logical order — ref.py's identity-step
            # argument makes the paged path bit-identical to this one)
            from repro.kernels.paged_attention import ops as paged_ops

            o = paged_ops.ring_decode_attention(
                q, k_view, v_view, pos1d, kv_pos, page_size=ps,
                window=window, scale=att.attn_scale(cfg), impl=paged_impl,
                interpret=interpret, n_devices=ctx.n_devices,
            )
        else:
            o = att.attention(
                q, k_view, v_view, pos1d, kv_pos, causal=True, window=window,
                scale=att.attn_scale(cfg), impl=attn_impl,
            )
        y = att.gqa_out(p["attn"], o)
    x = _res_constraint(x + y, ctx, False)

    if cross_cache is not None:
        hc = rmsnorm(x, p["norm_c"], cfg.norm_eps, cfg.rmsnorm_one_plus)
        ck, cv = cross_cache
        x = x + att.cross_attention(p["cross"], hc, ck, cv, enc_pos, cfg, attn_impl=attn_impl)

    h2 = rmsnorm(x, p["norm2"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    if use_moe:
        f, aux = moe_apply(p["moe"], h2, cfg, ctx)
    else:
        f, aux = mlp_apply(p["ffn"], h2, cfg), jnp.zeros((), jnp.float32)
    x = _res_constraint(x + f, ctx, False)
    return x, entry, aux


def ssm_block_full(p: dict, x, cfg: ModelConfig, ctx: ShardCtx, *,
                   valid=None, state=None, seq_parallel: bool = False):
    h = rmsnorm(x, p["norm"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    y, new_state = ssm_mod.ssm_forward(
        p["ssm"], h, cfg, valid=valid,
        conv_tail=None if state is None else state["conv"],
        h0=None if state is None else state["ssm"],
    )
    # NOTE: SSD's chunk recurrence couples the sequence dim — no seq-parallel
    # residual stream for SSM blocks (the scan must see contiguous chunks).
    x = _res_constraint(x + y, ctx, False)
    return x, new_state


def ssm_block_step(p: dict, x, cfg: ModelConfig, ctx: ShardCtx, state, *, valid=None):
    h = rmsnorm(x, p["norm"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    y, new_state = ssm_mod.ssm_step(p["ssm"], h, cfg, state, valid=valid)
    x = _res_constraint(x + y, ctx, False)
    return x, new_state


# ===================================================================== stacks


def _scan(body, carry, xs, *, remat: bool, length=None, unroll: bool = False):
    """lax.scan over stacked layers, or a python loop when ``unroll``.

    Unrolling exists for the dry-run *cost probes*: XLA's cost_analysis
    counts a while-loop body once, so the roofline extracts per-layer costs
    from two small unrolled depths and extrapolates (EXPERIMENTS.md §Dry-run
    methodology).  Production lowering always uses the scan.
    """
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if not unroll:
        return lax.scan(body, carry, xs, length=length)
    n = length if length is not None else jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys_list = []
    for i in range(n):
        xi = None if xs is None else jax.tree_util.tree_map(lambda x: x[i], xs)
        carry, y = body(carry, xi)
        ys_list.append(y)
    if ys_list and ys_list[0] is not None:
        ys = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys_list)
    else:
        ys = None
    return carry, ys


def forward_train(
    params: Params, x, positions, pos1d, cfg: ModelConfig, ctx: ShardCtx, *,
    valid=None, enc_out=None, enc_pos=None, attn_impl: str = "auto",
    remat: bool = True, window: int = 0, unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward over the stack.  Returns (hidden, aux_loss)."""
    seq_par = ctx.mesh is not None and cfg.arch_type not in ("ssm", "hybrid")
    x = _res_constraint(x, ctx, seq_par)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.arch_type in ("dense", "vlm"):
        def body(carry, p_layer):
            xx, aux = carry
            xx, a = attn_block_full(
                p_layer, xx, positions, pos1d, cfg, ctx, use_moe=False,
                window=window, attn_impl=attn_impl, seq_parallel=seq_par,
            )
            return (xx, aux + a), None

        (x, aux_total), _ = _scan(body, (x, aux_total), params["layers"], remat=remat, unroll=unroll)

    elif cfg.arch_type == "moe":
        if "dense_layers" in params:
            def body_d(carry, p_layer):
                xx, aux = carry
                xx, a = attn_block_full(
                    p_layer, xx, positions, pos1d, cfg, ctx, use_moe=False,
                    window=window, attn_impl=attn_impl, seq_parallel=seq_par,
                )
                return (xx, aux + a), None

            (x, aux_total), _ = _scan(body_d, (x, aux_total), params["dense_layers"], remat=remat, unroll=unroll)

        def body_m(carry, p_layer):
            xx, aux = carry
            xx, a = attn_block_full(
                p_layer, xx, positions, pos1d, cfg, ctx, use_moe=True,
                window=window, attn_impl=attn_impl, seq_parallel=seq_par,
            )
            return (xx, aux + a), None

        (x, aux_total), _ = _scan(body_m, (x, aux_total), params["moe_layers"], remat=remat, unroll=unroll)

    elif cfg.arch_type == "ssm":
        def body_s(xx, p_layer):
            xx, _ = ssm_block_full(p_layer, xx, cfg, ctx, valid=valid)
            return xx, None

        x, _ = _scan(body_s, x, params["layers"], remat=remat, unroll=unroll)

    elif cfg.arch_type == "hybrid":
        emb0 = x

        def body_g(xx, p_group):
            def body_s(xxx, p_layer):
                xxx, _ = ssm_block_full(p_layer, xxx, cfg, ctx, valid=valid)
                return xxx, None

            xx, _ = _scan(body_s, xx, p_group, remat=False, unroll=unroll)
            xx, _ = attn_block_full(
                params["shared_attn"], xx, positions, pos1d, cfg, ctx,
                use_moe=False, window=window, attn_impl=attn_impl, x_extra=emb0,
            )
            return xx, None

        x, _ = _scan(body_g, x, params["groups"], remat=remat, unroll=unroll)

    elif cfg.arch_type == "encdec":
        assert enc_out is not None

        def body_dec(carry, p_layer):
            xx, aux = carry
            xx, a = attn_block_full(
                p_layer, xx, positions, pos1d, cfg, ctx, use_moe=False,
                window=window, attn_impl=attn_impl, seq_parallel=seq_par,
                enc_kv=enc_out, enc_pos=enc_pos,
            )
            return (xx, aux + a), None

        (x, aux_total), _ = _scan(body_dec, (x, aux_total), params["dec_layers"], remat=remat, unroll=unroll)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    return x, aux_total


def encode(params: Params, frames, enc_pos, cfg: ModelConfig, ctx: ShardCtx, *,
           attn_impl: str = "auto", remat: bool = False, unroll: bool = False) -> jax.Array:
    """Bidirectional encoder over stub frontend frames (B, T, d)."""
    x = frames

    def body(xx, p_layer):
        xx, _ = attn_block_full(
            p_layer, xx, enc_pos, enc_pos, cfg, ctx, use_moe=False,
            causal=False, attn_impl=attn_impl,
        )
        return xx, None

    x, _ = _scan(body, x, params["enc_layers"], remat=remat, unroll=unroll)
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps, cfg.rmsnorm_one_plus)


def forward_cached(
    params: Params, x, positions, pos1d, slots, cache: Cache,
    cfg: ModelConfig, ctx: ShardCtx, *,
    attn_impl: str = "auto", window: int = 0, unroll: bool = False,
    paged_impl: str = "gather", page_block: int = 16, interpret: bool = False,
) -> tuple[jax.Array, Cache, jax.Array]:
    """Unified prefill (m=S) / decode / probe forward against a cache.

    Returns (hidden (B,m,d), new_cache, aux).  Committing vs probing is the
    caller's choice of whether to keep ``new_cache``.
    """
    B, m, _ = x.shape
    kv_pos = cache["pos"].at[:, slots].set(pos1d)
    new_cache = dict(cache)
    new_cache["pos"] = kv_pos
    new_cache["cur"] = cache["cur"] + m
    aux_total = jnp.zeros((), jnp.float32)
    x = _res_constraint(x, ctx, False)
    layers = cache.get("layers", {})
    # block-paged cache: thread (page_table, page_size, blocks) into the
    # attention blocks — logical addressing (slots/pos/cur) is unchanged;
    # ``blocks`` (the engine's compacted mapped-page list) enables the
    # page-native read when ``paged_impl`` asks for it
    paged = None
    if "page_table" in cache:
        table = cache["page_table"]
        paged = (table, cache["pos"].shape[1] // table.shape[1],
                 cache.get("blocks"))

    if cfg.arch_type in ("dense", "vlm", "moe", "encdec"):
        segs = []
        if cfg.arch_type == "moe":
            if "dense_layers" in params:
                segs.append(("dense_seg", params["dense_layers"], False))
            segs.append(("moe_seg", params["moe_layers"], True))
        elif cfg.arch_type == "encdec":
            segs.append(("dec_seg", params["dec_layers"], False))
        else:
            segs.append(("seg", params["layers"], False))

        new_layers = dict(layers)
        for seg_name, seg_params, use_moe in segs:
            seg_cache = layers[seg_name]
            cross = cfg.arch_type == "encdec"

            def body(carry, xs):
                xx, aux = carry
                p_layer, entry = xs
                cc = (entry["ck"], entry["cv"]) if cross else None
                xx, entry_new, a = attn_block_cached(
                    p_layer, xx, positions, pos1d, cfg, ctx, entry, kv_pos, slots,
                    use_moe=use_moe, window=window, attn_impl=attn_impl,
                    cross_cache=cc, enc_pos=cache.get("enc_pos"), paged=paged,
                    paged_impl=paged_impl, page_block=page_block,
                    interpret=interpret,
                )
                if cross:  # cross kv is static; don't re-emit to save copies
                    entry_new["ck"], entry_new["cv"] = entry["ck"], entry["cv"]
                return (xx, aux + a), entry_new

            (x, aux_total), seg_new = _scan(body, (x, aux_total), (seg_params, seg_cache), remat=False, unroll=unroll)
            new_layers[seg_name] = seg_new
        new_cache["layers"] = new_layers

    elif cfg.arch_type == "ssm":
        # prefill (large m) uses the chunked SSD path; decode steps recur
        use_full = m > 16
        valid = pos1d >= 0

        def body_s(xx, xs):
            p_layer, st = xs
            if use_full:
                xx, st_new = ssm_block_full(p_layer, xx, cfg, ctx, valid=valid, state=st)
            else:
                xx, st_new = ssm_block_step(p_layer, xx, cfg, ctx, st)
            return xx, st_new

        x, st_all = _scan(body_s, x, (params["layers"], layers["seg"]), remat=False, unroll=unroll)
        new_cache["layers"] = {"seg": st_all}

    elif cfg.arch_type == "hybrid":
        emb0 = x
        seg_cache = layers["ssm_seg"]      # pytree stacked (G, n_ssm_per, ...)
        attn_cache = layers["attn_seg"]    # entries stacked (G, ...)
        use_full = m > 16
        valid = pos1d >= 0

        def body_g(carry, xs):
            xx, aux = carry
            p_group, st_group, attn_entry = xs

            def body_s(xxx, xs_inner):
                p_layer, st = xs_inner
                if use_full:
                    xxx, st_new = ssm_block_full(p_layer, xxx, cfg, ctx, valid=valid, state=st)
                else:
                    xxx, st_new = ssm_block_step(p_layer, xxx, cfg, ctx, st)
                return xxx, st_new

            xx, st_group_new = _scan(body_s, xx, (p_group, st_group), remat=False, unroll=unroll)
            xx, attn_entry_new, a = attn_block_cached(
                params["shared_attn"], xx, positions, pos1d, cfg, ctx,
                attn_entry, kv_pos, slots, use_moe=False, window=window,
                attn_impl=attn_impl, x_extra=emb0, paged=paged,
                paged_impl=paged_impl, page_block=page_block,
                interpret=interpret,
            )
            return (xx, aux + a), (st_group_new, attn_entry_new)

        (x, aux_total), (st_new, attn_new) = _scan(
            body_g, (x, aux_total), (params["groups"], seg_cache, attn_cache),
            remat=False, unroll=unroll,
        )
        new_cache["layers"] = {"ssm_seg": st_new, "attn_seg": attn_new}

    else:
        raise ValueError(cfg.arch_type)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, cfg.rmsnorm_one_plus)
    return x, new_cache, aux_total
