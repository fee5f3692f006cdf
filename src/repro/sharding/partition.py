"""Sharding rules: logical parameter layout -> mesh PartitionSpecs.

Strategy (DESIGN.md §7):
  * batch            -> (pod, data)          activations
  * d_model (weight reduction dims) -> data  (FSDP / ZeRO-3 style)
  * heads / d_ff / d_inner / vocab  -> model (tensor parallel), only when
    the dimension is divisible by the model-axis size; otherwise that dim
    stays unsharded and the weight is only FSDP-sharded (e.g. gemma-2b's
    8 q-heads / MQA kv=1 on a 16-wide model axis).
  * experts          -> model (expert parallel) AND expert d_model -> data
    at rest (236B must be 2D-sharded to fit); the MoE block re-gathers the
    ``data`` shards transiently (see models/moe.py).

Everything is name-based over the parameter pytree: init functions use
stable key names (wq/wk/wv/wo, w_up/w_gate/w_down, experts/*, ssm w_*),
and ``param_pspecs`` maps each path to a PartitionSpec.  Stacked-layer
leading axes (from ``lax.scan`` stacking) are detected via the ``layers/``
path prefix and get a leading ``None``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.utils.treeutil import tree_flatten_with_paths


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + axis-name bundle threaded through model code.

    ``mesh=None`` means single-process local execution (tests): all
    constraints become no-ops and the MoE block runs its local path.
    """

    mesh: Optional[Mesh] = None
    batch_axes: tuple[str, ...] = ("data",)   # ('pod','data') for multi-pod
    model_axis: str = "model"
    # FSDP (ZeRO-3) sharding of weights over the data axis.  True for
    # training (optimizer state must be cut 256 ways); False for serving
    # (§Perf iteration: decode re-gathers every weight every step under
    # FSDP — replicating over `data` removes that all-gather entirely).
    fsdp: bool = True
    # MoE expert-combine collective (§Perf iteration on the MoE giants):
    #   psum_f32   — baseline: all-reduce the full f32 token tensor
    #   psum_bf16  — cast to bf16 before the all-reduce (2x bytes)
    #   scatter    — bf16 reduce-scatter over tokens onto the model axis
    #                (matches the sequence-parallel residual layout; ~4x)
    moe_combine: str = "psum_f32"

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    @property
    def data_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.batch_axes) + (self.model_axis,)

    def batch_spec_entry(self):
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]

    def batch_entry_for(self, batch: int):
        """PartitionSpec entry for a batch axis of size ``batch``: the data
        axes when the size divides them, else None (replicated — e.g. B=1
        slot-admission states, batch=1 long-context shapes).  THE single
        divisibility rule for every batch-dim spec in the tree."""
        return self.batch_spec_entry() if batch % self.data_size == 0 else None

    def wsc(self, x, spec: P):
        """with_sharding_constraint if a mesh is active, else identity."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def sharding(self, spec: P) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _spec_for(path: str, shape: tuple[int, ...], cfg: ModelConfig, ctx: ShardCtx) -> P:
    ms = ctx.model_size
    data = "data"  # FSDP always on the in-pod data axis only
    m = ctx.model_axis
    leaf = path.split("/")[-1]

    if ctx.mesh is None:
        return P()

    dsz = ctx.mesh.shape[data]

    def fsdp(n):
        if not ctx.fsdp:
            return None
        return data if _div(n, dsz) else None

    def tp(n):
        return m if _div(n, ms) else None

    # ---- embeddings
    if leaf == "embedding":
        return P(tp(shape[0]), fsdp(shape[1]))
    if leaf == "lm_head":
        return P(fsdp(shape[0]), tp(shape[1]))

    # ---- MoE experts (E, d, ff) / (E, ff, d)
    if "/experts/" in path or path.endswith("router"):
        if leaf == "router":
            return P(*( [None] * (len(shape) - 2) + [fsdp(shape[-2]), None] ))
        body = [tp(shape[-3]), None, None]
        if leaf in ("w_up", "w_gate"):
            body = [tp(shape[-3]), fsdp(shape[-2]), None]
        elif leaf == "w_down":
            body = [tp(shape[-3]), None, fsdp(shape[-1])]
        return P(*([None] * (len(shape) - 3) + body))

    # ---- attention
    if leaf == "wq":
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]),
                 m if _div(cfg.n_heads, ms) else None)
    if leaf in ("wk", "wv"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]),
                 m if _div(cfg.n_kv_heads, ms) else None)
    if leaf == "wo":
        return P(*([None] * (len(shape) - 2)),
                 m if _div(cfg.n_heads, ms) else None, fsdp(shape[-1]))
    if leaf in ("bq",):
        return P(*([None] * (len(shape) - 1)), m if _div(cfg.n_heads, ms) else None)
    if leaf in ("bk", "bv"):
        return P(*([None] * (len(shape) - 1)), m if _div(cfg.n_kv_heads, ms) else None)

    # ---- MLA
    if leaf in ("w_dq", "w_dkv", "w_kr"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]), None)
    if leaf in ("w_uq", "w_uk", "w_uv"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]),
                 m if _div(cfg.n_heads, ms) else None)

    # ---- dense MLP
    if leaf in ("w_up", "w_gate"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]), tp(shape[-1]))
    if leaf == "w_down":
        return P(*([None] * (len(shape) - 2)), tp(shape[-2]), fsdp(shape[-1]))

    # ---- SSM (separated projections; d_inner / heads are model-sharded)
    if leaf in ("w_z", "w_x"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]), tp(shape[-1]))
    if leaf in ("w_b", "w_c"):
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]), None)
    if leaf == "w_dt":
        return P(*([None] * (len(shape) - 2)), fsdp(shape[-2]), tp(shape[-1]))
    if leaf in ("conv_x_w", "conv_x_b"):
        return P(*([None] * (len(shape) - 1)), tp(shape[-1]))
    if leaf in ("conv_bc_w", "conv_bc_b"):
        return P(*([None] * (len(shape) - 1)), None)
    if leaf in ("dt_bias", "A_log", "D"):
        return P(*([None] * (len(shape) - 1)), tp(shape[-1]))
    if leaf == "norm_w" and "ssm" in path:
        return P(*([None] * (len(shape) - 1)), tp(shape[-1]))
    if leaf == "out_proj":
        return P(*([None] * (len(shape) - 2)), tp(shape[-2]), fsdp(shape[-1]))

    # ---- everything else (norms, biases, scalars): replicated
    return P(*([None] * len(shape)))


def param_pspecs(params, cfg: ModelConfig, ctx: ShardCtx):
    """PartitionSpec pytree matching ``params``."""
    flat = tree_flatten_with_paths(params)
    specs = {}
    for path, leaf in flat:
        specs[path] = _spec_for(path, leaf.shape, cfg, ctx)
    # rebuild tree
    treedef = jax.tree_util.tree_structure(params)
    leaves_with_paths = tree_flatten_with_paths(params)
    spec_leaves = [specs[p] for p, _ in leaves_with_paths]
    return jax.tree_util.tree_unflatten(treedef, spec_leaves)


def param_shardings(params, cfg: ModelConfig, ctx: ShardCtx):
    if ctx.mesh is None:
        return None
    specs = param_pspecs(params, cfg, ctx)
    return jax.tree_util.tree_map(lambda s: NamedSharding(ctx.mesh, s), specs)


def proxy_stream_pspecs(ctx: ShardCtx, batch: int):
    """PartitionSpecs for the generator-stream inputs of the proxy shadow
    program (``serving.executor.ProxyExecutor.observe_chunk``): the emitted
    token buffer (B, T) and the per-row offset/count vectors (B,).  Rows
    ride the data axis exactly like every other per-slot array (same
    divisibility rule as ``batch_entry_for`` — B=1 admission shapes
    replicate), columns replicate.  Returns ``(tokens, per_row)`` specs;
    scalars (the chunk bound) use ``P()`` at the call site.
    """
    b = ctx.batch_entry_for(batch)
    return P(b, None), P(b)


def serve_snapshot_pspecs(ctx: ShardCtx, batch: int):
    """PartitionSpecs for a chunk snapshot — the packed host-facing output
    of ``serving.executor.Executor.chunk_snapshot_program`` that the
    overlap pipeline harvests one boundary late.  The (R, B) int row-pack
    shards its batch COLUMN on the data axis (rows enumerate
    ``executor.SNAP_ROWS``), the (B,) debiased-variance vector and the
    (B, T+1) token-buffer copy ride the data axis like every per-slot
    array — same ``batch_entry_for`` divisibility rule, so B=1 shapes
    replicate.  Keys mirror the snapshot dict of ``_snapshot_of``."""
    b = ctx.batch_entry_for(batch)
    return {"ints": P(None, b), "var": P(b), "tokens": P(b, None)}


def serve_state_pspecs(cfg: ModelConfig, ctx: ShardCtx, state):
    """PartitionSpec pytree for a ``serving.executor.ServeState``.

    The cache follows ``serving.cache.cache_pspecs`` (kv-heads / capacity /
    SSD-heads on the model axis; paged caches shard the page POOLS over the
    model axis and replicate the page table); every other field is a
    per-slot array with a leading batch dim that rides the data axis (when
    divisible — B=1 admission states stay replicated); the rng key is
    replicated.  This is the spec the executor feeds to ``jax.jit`` in/out
    shardings for its decode-chunk / admit / per-token programs.
    """
    # lazy: serving.cache imports ShardCtx from this module
    from repro.serving.cache import cache_pspecs

    if ctx.mesh is None:
        return jax.tree_util.tree_map(lambda _: P(), state)
    b = ctx.batch_entry_for(state.active.shape[0])

    def bspec(x):
        if getattr(x, "ndim", 0) == 0:
            return P()
        return P(b, *([None] * (x.ndim - 1)))

    specs = jax.tree_util.tree_map(bspec, state)
    return specs._replace(
        cache=cache_pspecs(cfg, ctx, state.cache),
        rng=P(),
    )
