"""EAT — Entropy After ``</think>`` (paper §4.1).

EAT = H( f(Q, <think>, r_1..r_n, </think> [, prefix]; phi) )       (Eq. 5/13)

where phi is the monitored model (the reasoning model itself in the
white-box setting, or a proxy in the black-box setting).  The probe is a
forward over the probe-token suffix against the live decode cache whose
returned cache is discarded (``Model.probe_entropy``); the entropy itself is
the fused ``entropy_probe`` kernel.

This module owns probe-token construction and the batched EAT evaluation
helper used by the serving engine and benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import Model


@dataclasses.dataclass(frozen=True)
class ProbeSpec:
    """The token suffix appended (virtually) for an EAT evaluation.

    ``tokens[0]`` must be the stop-thinking token ``</think>``; the rest is
    the optional answer-inducing prefix (paper Eq. 13: "\\nThe final
    answer:"), which App. I.3 finds tightens the EAT <-> Pass@1 coupling for
    older models.  All probe tokens prefill in parallel against the existing
    cache, so the cost is ~one extra forward position regardless of length.
    """

    tokens: tuple[int, ...]

    def __len__(self) -> int:
        return len(self.tokens)


def make_probe(end_think_id: int, prefix_ids: Sequence[int] = ()) -> ProbeSpec:
    return ProbeSpec(tokens=(end_think_id, *prefix_ids))


def eval_eat(
    model: Model,
    params,
    cache,
    probe: ProbeSpec,
    next_pos: jax.Array,        # (B,) position the next real token would take
    *,
    entropy_impl: str = "auto",
) -> jax.Array:
    """Batched EAT for every sequence sharing the cache.  (B,) float32.

    The probe tokens take positions next_pos + [0..m); the cache is NOT
    committed.
    """
    B = next_pos.shape[0]
    m = len(probe)
    toks = jnp.broadcast_to(jnp.asarray(probe.tokens, jnp.int32), (B, m))
    pos1d = next_pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    if model.cfg.mrope_sections:
        positions = jnp.broadcast_to(pos1d[..., None], (B, m, 3))
    else:
        positions = pos1d
    return model.probe_entropy(
        params, toks, positions, pos1d, cache,
        entropy_impl=entropy_impl,
    )


def entropy_of_logits(logits: jax.Array, vocab: int | None = None) -> jax.Array:
    """Reference entropy over (..., V) logits (Eq. 2), restricted to
    [:vocab] when the table is padded."""
    lf = logits.astype(jnp.float32)
    if vocab is not None and vocab < lf.shape[-1]:
        mask = jnp.arange(lf.shape[-1]) < vocab
        lf = jnp.where(mask, lf, -jnp.inf)
    logp = jax.nn.log_softmax(lf, axis=-1)
    p = jnp.exp(logp)
    return -jnp.where(p > 0, p * logp, 0.0).sum(-1)
