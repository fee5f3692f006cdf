"""Model facade: embedding glue, losses, prefill/decode/probe entry points.

A ``Model`` is stateless — parameters are explicit pytrees; methods are pure
functions suitable for ``jax.jit`` with in/out shardings.  The EAT probe
(``probe_entropy``) is a first-class serving operation: a forward over the
probe tokens (``</think>`` [+ prefix]) against the live cache whose returned
cache is *discarded*, followed by the fused entropy kernel.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.entropy_probe.ops import next_token_entropy
from repro.models import transformer as tfm
from repro.models.transformer import write_slots
from repro.models.common import embed_apply, embed_init, lm_head_apply
from repro.sharding.partition import ShardCtx


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    ctx: ShardCtx = ShardCtx()
    attn_impl: str = "auto"
    unroll: bool = False      # unroll layer scans (dry-run cost probes only)
    # decode/probe attention over a serving cache (kernels/paged_attention):
    #   "gather"            — classic: paged caches materialize the gathered
    #                         logical view, ring caches read densely
    #   "auto"/"xla"/"pallas" — page-native: paged caches read K/V straight
    #                         off the pools through the compacted page list
    #                         (O(mapped pages) per token); ring caches run
    #                         the same block-sequential algorithm, so the
    #                         two backends stay bit-identical per impl.
    # ``paged_attn_page`` is the ring comparator's block size — it must
    # match the paged cache's CacheConfig.page_size for the bit-exactness
    # A/B (the engine threads both from EngineConfig.cache).
    paged_attn_impl: str = "gather"
    paged_attn_page: int = 16
    # run the Pallas kernels (paged attention, entropy probe) in interpret
    # mode: the one switch that lets a CPU test drive the serving stack
    # through the kernels.  Off, a kernel impl compiles for the TPU, and
    # ``impl="pallas"`` on another backend is an error.
    interpret: bool = False

    # ---------------------------------------------------------------- init
    def init(self, key) -> dict:
        k1, k2 = jax.random.split(key)
        dtype = jnp.dtype(self.cfg.dtype)
        return {
            "embed": embed_init(k1, self.cfg, dtype),
            "stack": tfm.init_stack(k2, self.cfg, dtype),
        }

    # ---------------------------------------------------------------- embed
    def embed_stream(self, params, tokens, image_embeds=None) -> jax.Array:
        """Token embeddings; VLM prepends stub patch embeddings."""
        x = embed_apply(params["embed"], tokens, self.cfg)
        if self.cfg.arch_type == "vlm" and image_embeds is not None:
            x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
        return x

    def unembed_matrix(self, params) -> jax.Array:
        e = params["embed"]
        return e["embedding"].T if self.cfg.tie_embeddings else e["lm_head"]

    def logits(self, params, hidden) -> jax.Array:
        return lm_head_apply(params["embed"], hidden, self.cfg)

    # ---------------------------------------------------------------- train
    def train_loss(self, params, batch: dict, *, remat: bool = True,
                   z_loss: float = 1e-4, window: int | None = None):
        """batch keys: tokens (B,S); targets, loss_mask (B,S_total);
        positions (B,S_total[,3]); pos1d (B,S_total); [frames (B,T,d)];
        [image_embeds (B,P,d)].  Returns (loss, metrics dict)."""
        cfg, ctx = self.cfg, self.ctx
        window = cfg.sliding_window if window is None else window
        x = self.embed_stream(params, batch["tokens"], batch.get("image_embeds"))
        pos = batch["positions"]
        pos1d = batch["pos1d"]

        enc_out = enc_pos = None
        if cfg.arch_type == "encdec":
            frames = batch["frames"]
            Bf, T, _ = frames.shape
            enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (Bf, T))
            enc_out = tfm.encode(
                params["stack"], frames.astype(x.dtype), enc_pos, cfg, ctx,
                attn_impl=self.attn_impl, remat=remat, unroll=self.unroll,
            )

        hidden, aux = tfm.forward_train(
            params["stack"], x, pos, pos1d, cfg, ctx,
            valid=pos1d >= 0, enc_out=enc_out, enc_pos=enc_pos,
            attn_impl=self.attn_impl, remat=remat, window=window,
            unroll=self.unroll,
        )
        logits = self.logits(params, hidden)
        if ctx.mesh is not None:
            logits = ctx.wsc(logits, P(ctx.batch_spec_entry(), None, ctx.model_axis))
        loss, metrics = cross_entropy_loss(
            logits, batch["targets"], batch["loss_mask"], cfg.vocab, z_loss=z_loss
        )
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * aux
            metrics["aux_loss"] = aux
        metrics["loss"] = loss
        return loss, metrics

    # ---------------------------------------------------------------- serve
    def prefill(self, params, tokens, positions, pos1d, cache, *,
                frames=None, image_embeds=None, window: int | None = None):
        """Fill the cache with the prompt; returns (hidden (B,S,d), cache)."""
        cfg, ctx = self.cfg, self.ctx
        window = cfg.sliding_window if window is None else window
        x = self.embed_stream(params, tokens, image_embeds)
        cache = dict(cache)

        if cfg.arch_type == "encdec":
            Bf, T, _ = frames.shape
            enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (Bf, T))
            enc_out = tfm.encode(
                params["stack"], frames.astype(x.dtype), enc_pos, cfg, ctx,
                attn_impl=self.attn_impl, unroll=self.unroll,
            )
            from repro.models.attention import cross_attn_kv

            ck, cv = jax.vmap(lambda p: cross_attn_kv(p, enc_out, cfg))(
                params["stack"]["dec_layers"]["cross"]
            )
            layers = dict(cache["layers"])
            dec = dict(layers["dec_seg"])
            dec["ck"], dec["cv"] = ck.astype(dec["ck"].dtype), cv.astype(dec["cv"].dtype)
            layers["dec_seg"] = dec
            cache["layers"] = layers
            cache["enc_pos"] = enc_pos

        m = x.shape[1]
        capacity = cache["pos"].shape[1]
        slots = write_slots(cache["cur"], m, capacity)
        hidden, cache, _ = tfm.forward_cached(
            params["stack"], x, positions, pos1d, slots, cache, cfg, ctx,
            attn_impl=self.attn_impl, window=window, unroll=self.unroll,
            paged_impl=self.paged_attn_impl, page_block=self.paged_attn_page,
            interpret=self.interpret,
        )
        return hidden, cache

    def decode_step(self, params, tokens, positions, pos1d, cache, *,
                    window: int | None = None):
        """One decode step (m new tokens, usually 1).
        Returns (logits (B,m,Vp), cache)."""
        cfg, ctx = self.cfg, self.ctx
        window = cfg.sliding_window if window is None else window
        x = self.embed_stream(params, tokens)
        capacity = cache["pos"].shape[1]
        slots = write_slots(cache["cur"], x.shape[1], capacity)
        hidden, cache, _ = tfm.forward_cached(
            params["stack"], x, positions, pos1d, slots, cache, cfg, ctx,
            attn_impl=self.attn_impl, window=window, unroll=self.unroll,
            paged_impl=self.paged_attn_impl, page_block=self.paged_attn_page,
            interpret=self.interpret,
        )
        return self.logits(params, hidden), cache

    def decode_and_probe(self, params, token, positions, pos1d, cache,
                         probe_tokens, *, window: int | None = None,
                         entropy_impl: str = "auto"):
        """Fused serve step (§Perf): ONE forward over [token, probe...]
        instead of decode + separate probe — halves the per-step weight
        traffic (under FSDP: one all-gather instead of two).

        Commits only the decode token: ``cur`` advances by 1; the probe
        K/V land in the next slots and are masked by position until
        overwritten (future q positions < stale probe positions).  With a
        ring-buffer (sliding-window) cache the probe writes sacrifice the
        len(probe) oldest window slots — window is effectively W-m.

        token: (B,1); probe_tokens: (B,m).  Returns (logits (B,1,Vp),
        eat (B,), cache).

        SSM/hybrid states are *cumulative* (not slot-addressed), so a fused
        commit would bake the probe into the recurrence — those arch types
        transparently fall back to the separate decode + non-committing
        probe (same signature, no fusion win).
        """
        cfg, ctx = self.cfg, self.ctx
        if cfg.arch_type in ("ssm", "hybrid"):
            logits, cache = self.decode_step(
                params, token, positions[:, :1], pos1d[:, :1], cache, window=window
            )
            m = probe_tokens.shape[1]
            eat = self.probe_entropy(
                params, probe_tokens, positions[:, 1:1 + m], pos1d[:, 1:1 + m],
                cache, window=window, entropy_impl=entropy_impl,
            )
            return logits, eat, cache
        window = cfg.sliding_window if window is None else window
        toks = jnp.concatenate([token, probe_tokens], axis=1)
        x = self.embed_stream(params, toks)
        capacity = cache["pos"].shape[1]
        slots = write_slots(cache["cur"], x.shape[1], capacity)
        hidden, new_cache, _ = tfm.forward_cached(
            params["stack"], x, positions, pos1d, slots, cache, cfg, ctx,
            attn_impl=self.attn_impl, window=window, unroll=self.unroll,
            paged_impl=self.paged_attn_impl, page_block=self.paged_attn_page,
            interpret=self.interpret,
        )
        new_cache["cur"] = cache["cur"] + 1            # commit decode only
        logits = self.logits(params, hidden[:, :1])
        w = self.unembed_matrix(params)
        eat = self.entropy(hidden[:, -1], w, entropy_impl)
        return logits, eat, new_cache

    def probe_entropy(self, params, probe_tokens, positions, pos1d, cache, *,
                      window: int | None = None, entropy_impl: str = "auto"):
        """EAT (paper Eq. 5/13): run the probe tokens (``</think>`` + optional
        prefix) against the cache WITHOUT committing it, and return the
        next-token entropy at the last probe position.  (B,) float32 nats."""
        cfg, ctx = self.cfg, self.ctx
        window = cfg.sliding_window if window is None else window
        x = self.embed_stream(params, probe_tokens)
        capacity = cache["pos"].shape[1]
        slots = write_slots(cache["cur"], x.shape[1], capacity)
        hidden, _discarded, _ = tfm.forward_cached(
            params["stack"], x, positions, pos1d, slots, cache, cfg, ctx,
            attn_impl=self.attn_impl, window=window, unroll=self.unroll,
            paged_impl=self.paged_attn_impl, page_block=self.paged_attn_page,
            interpret=self.interpret,
        )
        h_last = hidden[:, -1]
        w = self.unembed_matrix(params)
        return self.entropy(h_last, w, entropy_impl)

    def entropy(self, h, w, impl: str = "auto") -> jax.Array:
        """Next-token entropy over the valid vocabulary (the probe's last
        step), through the kernel dispatch with this model's interpret
        switch and device count."""
        return next_token_entropy(h, w, self.cfg.vocab, impl=impl,
                                  interpret=self.interpret,
                                  n_devices=self.ctx.n_devices)


def cross_entropy_loss(logits, targets, mask, vocab: int, *, z_loss: float = 1e-4):
    """Masked CE over the valid vocabulary (padding columns excluded).

    Uses the one-hot-contraction form (SPMD-friendly over a vocab-sharded
    logits tensor) + MaxText-style z-loss on log Z.
    """
    Vp = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    col_valid = jnp.arange(Vp) < vocab
    lf = jnp.where(col_valid, lf, -1e30)
    m = jax.lax.stop_gradient(lf.max(-1, keepdims=True))
    shifted = lf - m
    logz = jnp.log(jnp.exp(shifted).sum(-1))         # (B,S)
    onehot = jax.nn.one_hot(targets, Vp, dtype=jnp.float32)
    ll = (shifted * onehot).sum(-1) - logz           # log p[target]
    maskf = mask.astype(jnp.float32)
    denom = jnp.maximum(maskf.sum(), 1.0)
    ce = -(ll * maskf).sum() / denom
    zl = ((logz + m[..., 0]) ** 2 * maskf).sum() / denom
    loss = ce + z_loss * zl
    acc = ((lf.argmax(-1) == targets) * maskf).sum() / denom
    return loss, {"ce": ce, "z_loss": zl, "accuracy": acc, "tokens": maskf.sum()}
