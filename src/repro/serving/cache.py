"""Decode-state allocation: KV caches, MLA latent caches, SSM states.

Layout contract (consumed by ``models.transformer.forward_cached``):

  cache = {
    "layers": { <segment>: <stacked entries> },
    "pos":  (B, C) int32  — absolute position held in each slot, -1 = empty,
    "cur":  ()   int32    — committed length (ring: total tokens seen),
    ["enc_pos"]: (B, T)   — encoder positions (encdec only),
  }

Segments mirror the parameter stack segments:
  dense/vlm : {"seg":       {"k","v": (L,B,C,Hkv,hd)}}
  moe       : {"dense_seg": ..., "moe_seg": ...}
  mla (moe) : entries {"c": (L,B,C,r), "kr": (L,B,C,rope_d)}
  encdec    : {"dec_seg":   {"k","v", "ck","cv": (L,B,T,Hkv,hd)}}
  ssm       : {"seg":       {"ssm": (L,B,nh,N,hp), "conv": {...}}}
  hybrid    : {"ssm_seg": (G,n_per,B,...), "attn_seg": {"k","v": (G,B,C,H,hd)}}

Sliding-window configs use a ring buffer: capacity == window and slots are
``(cur + arange(m)) % capacity`` (see ``write_slots``); masking relies on the
explicit ``pos`` array, so ring order is irrelevant to attention.

Block-paged variant (``CacheConfig.kind="paged"``, docs/architecture.md):
same logical layout and ``pos``/``cur`` semantics, but the attention
entries become page POOLS — ``(L, num_pages, page_size, Hkv*hd)`` instead
of ``(L, B, C, Hkv, hd)``: lane-dense, the layout the paged-attention
kernel reads, and written in place at (layer, page, offset) by the layer
scan (MLA latents: ``(L, num_pages, page_size, r)``) — plus a
``page_table`` (B, NB) int32 mapping each row's logical blocks to physical
pages.  ``gather_pages`` reconstructs
the per-row logical view for attention; unmapped blocks read the reserved
trash page (entry 0), whose contents are always position-masked.  The
physical footprint is live tokens (page-granular), not batch-lifetime
capacity — the unlock for long continuous-batching queues.

Sharding (DESIGN.md §7): batch -> (pod,data); kv-heads -> model when
divisible, otherwise the capacity dim C -> model (GSPMD inserts the
partial-softmax collectives); MLA latent and SSM state follow the same rule
(C -> model for MLA; SSD heads -> model for SSM).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.ssm import ssm_dims
from repro.models.transformer import (  # noqa: F401  (re-exports)
    gather_pages,
    scatter_pages,
    write_slots,
)
from repro.sharding.partition import ShardCtx


#: physical page id reserved as the trash page — never handed out by the
#: allocator; unmapped page-table entries point here, so stray writes from
#: rows without a mapping land in it and every read of it is position-masked
PAGE_TRASH = 0

#: cache leaf names stored as page pools in a paged cache (attention K/V and
#: the MLA latent/rope entries — everything with a capacity axis)
POOLED_LEAVES = ("k", "v", "c", "kr")


def page_align(n_slots: int, page_size: int) -> int:
    """Round a slot count up to a whole number of pages."""
    return -(-n_slots // page_size) * page_size


@dataclasses.dataclass
class CacheConfig:
    """KV-cache backend selection for the serving stack.

    ``kind="ring"`` is the classic dense ring buffer: ``capacity`` logical
    slots are physically allocated per batch row, so capacity is a
    batch-lifetime bound (``SlotScheduler.required_capacity``).

    ``kind="paged"`` keeps the same logical addressing but backs it with a
    block-paged pool (``num_pages`` pages of ``page_size`` slots each,
    shared by all rows): physical memory is bounded by LIVE tokens, a
    request's pages return to the free list the moment it exits, and
    admission becomes per-block bookkeeping (``scheduler.PageAllocator``).
    See docs/architecture.md — the paged path reproduces the ring path's
    token streams, exit steps, and EAT trajectories exactly.
    """

    kind: str = "ring"                 # "ring" | "paged"
    page_size: int = 16                # logical slots per physical page
    # 0 = auto: ring-equivalent pool (batch * capacity/page_size data pages
    # + the trash page) — never refuses an admission the ring would accept
    num_pages: int = 0
    # decode/probe attention implementation (kernels/paged_attention):
    #   "gather"              — classic: the paged path materializes the
    #                           gathered logical view before dense attention
    #   "auto" | "xla" | "pallas" — page-native: K/V are read straight off
    #                           the page pools through the compacted
    #                           mapped-page list, so per-token decode cost is
    #                           O(mapped pages) instead of O(logical
    #                           capacity); the ring backend runs the same
    #                           block-sequential algorithm, keeping
    #                           paged == ring bit-exact per impl
    #                           (docs/serving.md §--attn-impl)
    attn_impl: str = "gather"

    def __post_init__(self):
        if self.kind not in ("ring", "paged"):
            raise ValueError(f"CacheConfig.kind must be 'ring' or 'paged', "
                             f"got {self.kind!r}")
        if self.page_size < 1:
            raise ValueError("CacheConfig.page_size must be >= 1")
        if self.attn_impl not in ("gather", "auto", "xla", "pallas"):
            raise ValueError(f"CacheConfig.attn_impl must be one of "
                             f"gather/auto/xla/pallas, got {self.attn_impl!r}")


def _attn_entry(cfg: ModelConfig, lead: tuple[int, ...], B: int, C: int, dtype):
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c": jnp.zeros(lead + (B, C, m.kv_lora_rank), dtype),
            "kr": jnp.zeros(lead + (B, C, m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros(lead + (B, C, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros(lead + (B, C, cfg.n_kv_heads, hd), dtype),
    }


def _ssm_entry(cfg: ModelConfig, lead: tuple[int, ...], B: int, dtype):
    dm = ssm_dims(cfg)
    gn = dm.n_groups * dm.d_state
    return {
        "ssm": jnp.zeros(lead + (B, dm.n_heads, dm.d_state, dm.head_dim), jnp.float32),
        "conv": {
            "x": jnp.zeros(lead + (B, dm.conv_width - 1, dm.d_inner), dtype),
            "bc": jnp.zeros(lead + (B, dm.conv_width - 1, 2 * gn), dtype),
        },
    }


def alloc_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> dict:
    """Allocate an empty cache with ``capacity`` kv slots per sequence."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    B, C = batch, capacity
    cache: dict = {
        "pos": jnp.full((B, C), -1, jnp.int32),
        "cur": jnp.zeros((), jnp.int32),
    }
    if cfg.arch_type in ("dense", "vlm"):
        cache["layers"] = {"seg": _attn_entry(cfg, (cfg.n_layers,), B, C, dtype)}
    elif cfg.arch_type == "moe":
        fk = cfg.moe.first_k_dense
        layers = {}
        if fk:
            layers["dense_seg"] = _attn_entry(cfg, (fk,), B, C, dtype)
        layers["moe_seg"] = _attn_entry(cfg, (cfg.n_layers - fk,), B, C, dtype)
        cache["layers"] = layers
    elif cfg.arch_type == "encdec":
        T = cfg.encoder_len
        entry = _attn_entry(cfg, (cfg.n_layers,), B, C, dtype)
        hd = cfg.resolved_head_dim
        entry["ck"] = jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads, hd), dtype)
        entry["cv"] = jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads, hd), dtype)
        cache["layers"] = {"dec_seg": entry}
        cache["enc_pos"] = jnp.zeros((B, T), jnp.int32)
    elif cfg.arch_type == "ssm":
        cache["layers"] = {"seg": _ssm_entry(cfg, (cfg.n_layers,), B, dtype)}
    elif cfg.arch_type == "hybrid":
        pat = cfg.hybrid_pattern
        n_per = sum(1 for k in pat if k == "ssm")
        G = cfg.n_layers // len(pat)
        cache["layers"] = {
            "ssm_seg": _ssm_entry(cfg, (G, n_per), B, dtype),
            "attn_seg": _attn_entry(cfg, (G,), B, C, dtype),
        }
    else:
        raise ValueError(cfg.arch_type)
    return cache


# ------------------------------------------------------------ paged variant


def _pooled_attn_entry(cfg: ModelConfig, lead: tuple[int, ...],
                       num_pages: int, page_size: int, dtype):
    """Page-pool form of ``_attn_entry``: (B, C) -> (num_pages, page_size),
    GQA heads folded into lanes (Hkv, hd) -> Hkv*hd."""
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c": jnp.zeros(lead + (num_pages, page_size, m.kv_lora_rank), dtype),
            "kr": jnp.zeros(lead + (num_pages, page_size, m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros(lead + (num_pages, page_size, cfg.n_kv_heads * hd), dtype),
        "v": jnp.zeros(lead + (num_pages, page_size, cfg.n_kv_heads * hd), dtype),
    }


def blocks_arrays(pages, logical, counts) -> dict:
    """Device form of the allocator's compacted mapped-page list (the
    page-native attention's read index; ``PageAllocator.block_buckets``).
    pages/logical: (B, NBK) int32 — physical page and logical block per
    mapped rank, trash/0-padded past ``counts`` (B,); padding ranks read
    the trash page with every position masked, so they are exact identity
    steps in the block scan (kernels/paged_attention/ref.py)."""
    return {
        "pages": jnp.asarray(pages, jnp.int32),
        "logical": jnp.asarray(logical, jnp.int32),
        "count": jnp.asarray(counts, jnp.int32),
    }


def alloc_paged_template(cfg: ModelConfig, batch: int, capacity: int,
                         page_size: int, num_pages: int, *,
                         alloc=None, native: bool = False,
                         dtype=None) -> dict:
    """The pack_paged_cache template every paged serve start builds: an
    empty paged cache, plus — in page-native mode — the allocator's
    current compacted mapped-page buckets baked in (``alloc`` is a
    ``scheduler.PageAllocator``; later refreshes ride
    ``Executor.put_page_table``).  THE single definition of the blocks
    baking ritual, shared by the engine, the proxy tier, and the
    benchmarks — so the read-index format cannot fork between them."""
    if not native:
        return alloc_paged_cache(cfg, batch, capacity, page_size, num_pages,
                                 dtype)
    width = alloc.bucket_width()
    cache = alloc_paged_cache(cfg, batch, capacity, page_size, num_pages,
                              dtype, block_bucket=width)
    cache["blocks"] = blocks_arrays(*alloc.block_buckets(width))
    return cache


def alloc_paged_cache(cfg: ModelConfig, batch: int, capacity: int,
                      page_size: int, num_pages: int, dtype=None,
                      block_bucket: int = 0) -> dict:
    """Allocate an empty block-paged cache.

    ``capacity`` is the LOGICAL ring length (must be a page multiple); the
    physical K/V footprint is ``num_pages * page_size`` slots shared by all
    ``batch`` rows through the page table (initialised all-trash).  Leaves
    without a capacity axis (SSM/conv states, encdec cross K/V) stay dense —
    they are per-row recurrent state, not slot-addressed storage.

    ``block_bucket`` > 0 adds the ``blocks`` arrays (width ``block_bucket``,
    all-trash) that the page-native ``attn_impl`` modes read; the engine
    refreshes them from the allocator before every dispatch.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    if capacity % page_size:
        raise ValueError(f"paged capacity {capacity} must be a multiple of "
                         f"page_size {page_size}")
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is the trash page)")
    B, NB = batch, capacity // page_size
    cache: dict = {
        "pos": jnp.full((B, capacity), -1, jnp.int32),
        "cur": jnp.zeros((), jnp.int32),
        "page_table": jnp.full((B, NB), PAGE_TRASH, jnp.int32),
    }
    if block_bucket:
        z = np.zeros((B, block_bucket), np.int32)
        cache["blocks"] = blocks_arrays(z, z, np.zeros((B,), np.int32))
    if cfg.arch_type in ("dense", "vlm"):
        cache["layers"] = {
            "seg": _pooled_attn_entry(cfg, (cfg.n_layers,), num_pages, page_size, dtype)
        }
    elif cfg.arch_type == "moe":
        fk = cfg.moe.first_k_dense
        layers = {}
        if fk:
            layers["dense_seg"] = _pooled_attn_entry(cfg, (fk,), num_pages, page_size, dtype)
        layers["moe_seg"] = _pooled_attn_entry(
            cfg, (cfg.n_layers - fk,), num_pages, page_size, dtype)
        cache["layers"] = layers
    elif cfg.arch_type == "encdec":
        T = cfg.encoder_len
        entry = _pooled_attn_entry(cfg, (cfg.n_layers,), num_pages, page_size, dtype)
        hd = cfg.resolved_head_dim
        entry["ck"] = jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads, hd), dtype)
        entry["cv"] = jnp.zeros((cfg.n_layers, B, T, cfg.n_kv_heads, hd), dtype)
        cache["layers"] = {"dec_seg": entry}
        cache["enc_pos"] = jnp.zeros((B, T), jnp.int32)
    elif cfg.arch_type == "hybrid":
        pat = cfg.hybrid_pattern
        n_per = sum(1 for k in pat if k == "ssm")
        G = cfg.n_layers // len(pat)
        cache["layers"] = {
            "ssm_seg": _ssm_entry(cfg, (G, n_per), B, dtype),
            "attn_seg": _pooled_attn_entry(cfg, (G,), num_pages, page_size, dtype),
        }
    elif cfg.arch_type == "ssm":
        raise ValueError("arch 'ssm' has no KV capacity axis to page — use "
                         "the ring cache (its state is O(1) per row already)")
    else:
        raise ValueError(cfg.arch_type)
    return cache


# rank of a single-layer pooled entry (page, page_size, lanes); any extra
# leading axes are layer stacks
_POOL_NDIM = 3


def _is_pooled(path: str) -> bool:
    return path.startswith("layers/") and path.split("/")[-1] in POOLED_LEAVES


def pack_paged_cache(paged: dict, dense: dict, table) -> dict:
    """Scatter a freshly prefilled DENSE cache into an empty paged cache —
    the serve()-start conversion (one jitted dispatch, ``paged`` donated).

    ``dense`` has prefill capacity C_pre (a page multiple, C_pre <= logical
    capacity); ``table`` is the allocator's (B, NB) page table with the
    prompt blocks mapped.  Blocks of ``dense`` beyond a row's mapped prompt
    scatter into the trash page (zeros over garbage — a don't-care).
    Non-pooled leaves (SSM/conv state, cross K/V, enc_pos) copy wholesale.
    """
    from repro.utils.treeutil import tree_flatten_with_paths

    NB = table.shape[1]
    ps = paged["pos"].shape[1] // NB
    C_pre = dense["pos"].shape[1]
    nbp = C_pre // ps
    flat_d = dict(tree_flatten_with_paths(dense))
    merged = []
    for path, leaf in tree_flatten_with_paths(paged):
        name = path.split("/")[-1]
        if path.startswith("blocks/"):
            # the compacted page list is host-owned: the engine bakes the
            # allocator's current buckets into the template before packing
            # and refreshes them before every dispatch (put_page_table)
            merged.append(leaf)
        elif name == "page_table":
            merged.append(jnp.asarray(table, jnp.int32))
        elif name == "pos":
            merged.append(leaf.at[:, :C_pre].set(dense["pos"]))
        elif name == "cur":
            merged.append(dense["cur"])
        elif _is_pooled(path):
            src = flat_d[path]
            lead = leaf.ndim - _POOL_NDIM
            B = src.shape[lead]
            tail = leaf.shape[lead + 2:]        # heads fold into lanes
            srcb = src.reshape(src.shape[:lead] + (B, nbp, ps) + tail)
            idx = (slice(None),) * lead + (table[:, :nbp],)
            merged.append(leaf.at[idx].set(srcb.astype(leaf.dtype)))
        else:
            merged.append(flat_d[path])
    treedef = jax.tree_util.tree_structure(paged)
    return jax.tree_util.tree_unflatten(treedef, merged)


def merge_paged_row(cache: dict, one: dict, row, row_table) -> dict:
    """Paged-cache slot admission: write the single-sequence DENSE cache
    ``one`` (batch=1, prefill capacity C_pre) into batch row ``row``.

    The paged analog of ``merge_cache_row``: the row's page-table entry is
    replaced by ``row_table`` (the allocator's fresh mapping: prompt blocks
    + the current decode block), the prompt K/V scatter into those pages,
    the row's logical ``pos`` is replaced (tail stays -1), and ``cur``
    advances to ``max(cur, one_cur)`` — identical ring semantics, so the
    admitted row's token stream matches the ring path's bit-for-bit.
    """
    from repro.utils.treeutil import tree_flatten_with_paths

    C = cache["pos"].shape[1]
    NB = cache["page_table"].shape[1]
    ps = C // NB
    C_pre = one["pos"].shape[1]
    nbp = C_pre // ps
    flat_one = dict(tree_flatten_with_paths(one))
    merged = []
    for path, leaf in tree_flatten_with_paths(cache):
        name = path.split("/")[-1]
        if path.startswith("blocks/"):
            # host-owned (see pack_paged_cache): the admitting engine pushes
            # the allocator's fresh buckets before the next attention read
            merged.append(leaf)
        elif name == "page_table":
            merged.append(leaf.at[row].set(jnp.asarray(row_table, jnp.int32)))
        elif name == "pos":
            row_pos = jnp.full((C,), -1, jnp.int32).at[:C_pre].set(one["pos"][0])
            merged.append(leaf.at[row].set(row_pos))
        elif name == "cur":
            merged.append(jnp.maximum(leaf, one["cur"]))
        elif _is_pooled(path):
            src = flat_one[path]
            lead = leaf.ndim - _POOL_NDIM
            tail = leaf.shape[lead + 2:]        # heads fold into lanes
            srcb = src[(slice(None),) * lead + (0,)]
            srcb = srcb.reshape(src.shape[:lead] + (nbp, ps) + tail)
            idx = (slice(None),) * lead + (jnp.asarray(row_table)[:nbp],)
            merged.append(leaf.at[idx].set(srcb.astype(leaf.dtype)))
        else:
            src = flat_one[path]
            lead = (leaf.ndim - _BASE_NDIM[name]
                    if path.startswith("layers/") else 0)
            idx = (slice(None),) * lead + (row,)
            merged.append(leaf.at[idx].set(src[(slice(None),) * lead + (0,)]))
    treedef = jax.tree_util.tree_structure(cache)
    return jax.tree_util.tree_unflatten(treedef, merged)


# per-leaf rank of a single-sequence (no stacked-layer axes) cache entry;
# any extra leading axes are layer stacks, so batch axis = ndim - base rank
_BASE_NDIM = {"k": 4, "v": 4, "ck": 4, "cv": 4, "c": 3, "kr": 3,
              "ssm": 4, "x": 3, "bc": 3}


def merge_cache_row(cache: dict, one: dict, row: int) -> dict:
    """Write the single-sequence cache ``one`` (batch=1, same capacity) into
    batch row ``row`` of ``cache`` — slot admission for continuous batching.

    The row is replaced wholesale (KV slots, positions, SSM states), so no
    stale slot of the previous occupant survives: the admitted sequence's
    prompt KV lives at slots ``0..P-1`` (``one`` was prefilled from
    ``cur=0``), every other slot has ``pos=-1``, and attention masks by
    position, not slot order.  The shared ring pointer advances to
    ``max(cur, one_cur)`` so subsequent batch-wide decode writes land past
    the admitted prompt; collisions can only occur once ``cur`` wraps the
    capacity, i.e. capacity must cover the batch-lifetime token count (the
    same contract as the non-recycling path).
    """
    from repro.utils.treeutil import tree_flatten_with_paths

    flat = tree_flatten_with_paths(cache)
    one_flat = dict(tree_flatten_with_paths(one))
    merged = []
    for path, leaf in flat:
        src = one_flat[path]
        name = path.split("/")[-1]
        if name == "cur":
            merged.append(jnp.maximum(leaf, src))
            continue
        lead = leaf.ndim - _BASE_NDIM[name] if path.startswith("layers/") else 0
        idx = (slice(None),) * lead + (row,)
        merged.append(leaf.at[idx].set(src[(slice(None),) * lead + (0,)]))
    treedef = jax.tree_util.tree_structure(cache)
    return jax.tree_util.tree_unflatten(treedef, merged)


# recurrent (non-slot-addressed) state: advances in place each step, so an
# inactive row's update must be rolled back rather than position-masked
_RECURRENT = ("ssm", "x", "bc")


def freeze_inactive_rows(new_cache: dict, old_cache: dict, active) -> dict:
    """Roll back recurrent-state rows for sequences with ``active=False``.

    KV caches are slot-addressed and masked by position, so a finished
    sequence's writes can be made invisible by writing ``pos=-1``; SSM /
    conv states are cumulative — stepping them with a PAD token pollutes the
    row for later forced rollouts.  Restores the pre-step rows (tiny arrays:
    per-layer state, not the KV cache) for ssm/hybrid caches; a no-op tree
    for attention-only caches.
    """
    from repro.utils.treeutil import tree_flatten_with_paths

    flat_new = tree_flatten_with_paths(new_cache)
    old = dict(tree_flatten_with_paths(old_cache))
    merged = []
    for path, leaf in flat_new:
        name = path.split("/")[-1]
        if name in _RECURRENT:
            lead = leaf.ndim - _BASE_NDIM[name]
            mask = active.reshape((1,) * lead + (-1,) + (1,) * (leaf.ndim - lead - 1))
            leaf = jnp.where(mask, leaf, old[path])
        merged.append(leaf)
    treedef = jax.tree_util.tree_structure(new_cache)
    return jax.tree_util.tree_unflatten(treedef, merged)


def cache_pspecs(cfg: ModelConfig, ctx: ShardCtx, cache) -> dict:
    """PartitionSpec pytree for a cache (for jit in/out shardings).

    Paged caches (``page_table`` present): the page POOLS shard over the
    model axis — kv-heads when divisible, else the page_size axis (the
    paged analog of capacity-sharding) — and replicate over the data axis
    (pages are shared by all batch rows, so there is no batch dim to ride
    it); page tables and the logical ``pos`` replicate / ride data exactly
    like the ring metadata.
    """
    if ctx.mesh is None:
        return jax.tree_util.tree_map(lambda _: P(), cache)
    m = ctx.model_axis
    ms = ctx.model_size
    kv_on_model = cfg.n_kv_heads % ms == 0 and cfg.mla is None
    paged = "page_table" in cache
    # batch=1 shapes (long_500k) cannot shard the batch axis
    bsz = cache["pos"].shape[0] if hasattr(cache["pos"], "shape") else 1
    b = ctx.batch_entry_for(bsz)

    def pool_spec_for(path_leaf: str, lead: int) -> P:
        # pooled entries: (lead..., num_pages, page_size, lanes); GQA lanes
        # are Hkv*hd, head-major, so sharding them shards whole kv heads
        if path_leaf in ("k", "v") and kv_on_model:
            return P(*([None] * lead), None, None, m)
        return P(*([None] * lead), None, m, None)            # shard page_size
    def spec_for(path_leaf: str, ndim: int, lead: int) -> P:
        # lead = number of stacked layer axes before the batch axis
        if path_leaf == "page_table":
            return P(None, None)                             # replicated
        if path_leaf in ("pages", "logical"):
            return P(None, None)      # blocks/ page lists: replicated int32
        if path_leaf == "count":
            return P(None)
        if path_leaf in ("k", "v", "ck", "cv"):
            if kv_on_model:
                return P(*([None] * lead), b, None, m, None)
            return P(*([None] * lead), b, m, None, None)  # shard capacity
        if path_leaf in ("c", "kr"):
            return P(*([None] * lead), b, m, None)        # shard capacity
        if path_leaf == "ssm":
            return P(*([None] * lead), b, m, None, None)  # shard SSD heads
        if path_leaf == "x":
            return P(*([None] * lead), b, None, m)        # conv x channels
        if path_leaf == "bc":
            return P(*([None] * lead), b, None, None)
        if path_leaf in ("pos", "enc_pos"):
            return P(b, None)
        return P()

    from repro.utils.treeutil import tree_flatten_with_paths

    flat = tree_flatten_with_paths(cache)
    specs = []
    for path, leaf in flat:
        parts = path.split("/")
        leafname = parts[-1]
        if leafname == "cur":
            specs.append(P())
            continue
        # count stacked lead axes: layers/<seg>/... entries have ndim-known
        if paged and _is_pooled(path):
            specs.append(pool_spec_for(leafname, leaf.ndim - _POOL_NDIM))
            continue
        lead = 0
        if parts[0] == "layers":
            lead = leaf.ndim - _BASE_NDIM[leafname]
        specs.append(spec_for(leafname, leaf.ndim, lead))
    treedef = jax.tree_util.tree_structure(cache)
    return jax.tree_util.tree_unflatten(treedef, specs)


def cache_bytes(cache) -> int:
    from repro.utils.treeutil import param_bytes

    return param_bytes(cache)
