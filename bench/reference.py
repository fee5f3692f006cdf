"""Plain float32 reference of the dense Qwen2 / Qwen3 decoder, written from
the published description and independent of the program under test.

Block (pre-norm, per layer):
    h = RMSNorm(x)
    q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)      bias: Qwen2 only
    q, k = RMSNorm_head(q), RMSNorm_head(k)             Qwen3 only (qk-norm)
    q, k = RoPE(q, pos), RoPE(k, pos)                   rotate-half, theta
    x = x + softmax(q k^T / sqrt(hd) + mask) v Wo       GQA: kv head = q head // group
    x = x + (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wu)) Wd
logits = RMSNorm(x) E^T (tied, Qwen3-1.7B) or RMSNorm(x) W_lm (Qwen2).

Departures from the published models, all shared with the served one:
the weights are random (``init_weights`` draws them from the seed by the
documented initialisation: normal weights scaled by 1/sqrt(fan-in), an
embedding of standard deviation 0.02, unit norm gains, zero biases); the
vocabulary is padded to a multiple of 256 and logits past ``vocab`` are
dropped; RoPE has no scaling and positions start at 0 at the first real
prompt token.

Everything runs in float32 under ``jax.default_matmul_precision("highest")``
(a TPU otherwise multiplies float32 in bfloat16), one layer at a time
inside a scan, and a few requests at a time, so it fits beside nothing
else on the chip once the served model is freed.  ``quantize="fp8"`` is
the control, computed in the next precision below the served bfloat16:
every weight matrix and every input of a matmul (the attention's queries,
keys, values and weights among them) rounded to float8 e4m3 with one
scale per tensor; accumulation stays in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import head_dim, padded_vocab


# ------------------------------------------------------------------ weights
def _dense(key, fan_in, fan_out, dtype):
    return (jax.random.normal(key, (fan_in, fan_out))
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _init(m: dict, key, dtype):
    d, hd, dff = m["d_model"], head_dim(m), m["d_ff"]
    hq, hkv, vp = m["n_heads"], m["n_kv_heads"], padded_vocab(m)
    k_embed, k_stack = jax.random.split(key)
    ke, kh = jax.random.split(k_embed)
    w = {"embedding": (jax.random.normal(ke, (vp, d)) * 0.02).astype(dtype)}
    if not m["tie_embeddings"]:
        w["lm_head"] = _dense(kh, d, vp, dtype)
    w["final_norm"] = jnp.ones((d,), dtype)

    def layer(k):
        k_attn, k_ffn, _, _ = jax.random.split(k, 4)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        ku, kd, kg = jax.random.split(k_ffn, 3)
        p = {"norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype),
             "wq": _dense(kq, d, hq * hd, dtype),
             "wk": _dense(kk, d, hkv * hd, dtype),
             "wv": _dense(kv, d, hkv * hd, dtype),
             "wo": _dense(ko, hq * hd, d, dtype),
             "w_up": _dense(ku, d, dff, dtype),
             "w_down": _dense(kd, dff, d, dtype),
             "w_gate": _dense(kg, d, dff, dtype)}
        if m["attn_bias"]:
            p["bq"] = jnp.zeros((hq * hd,), dtype)
            p["bk"] = jnp.zeros((hkv * hd,), dtype)
            p["bv"] = jnp.zeros((hkv * hd,), dtype)
        if m["qk_norm"]:
            p["q_norm"] = jnp.ones((hd,), dtype)
            p["k_norm"] = jnp.ones((hd,), dtype)
        return p

    layer_keys = jax.random.split(jax.random.split(k_stack, 8)[0],
                                  m["n_layers"])
    w["layers"] = jax.vmap(layer)(layer_keys)
    return w


def init_weights(m: dict, seed: int):
    """The served model's weights, drawn again from its seed in the dtype
    the configuration serves (``m["dtype"]``)."""
    dtype = jnp.dtype(m["dtype"])
    return jax.jit(functools.partial(_init, m, dtype=dtype))(
        jax.random.PRNGKey(seed))


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 448.0
    return ((xf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale)


MATRICES = ("embedding", "lm_head", "wq", "wk", "wv", "wo", "w_up",
            "w_down", "w_gate")


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, T, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(m: dict, quantize: str | None, w, tokens, pos, mask, rows):
    """Logits (B, R, vocab) at ``rows`` of each sequence.  ``mask``
    (B, T, T) says which keys each query sees.  ``quantize="fp8"`` rounds
    every weight matrix and every input of a matmul to float8."""
    eps, theta = m["norm_eps"], m["rope_theta"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    q_per_kv = hq // hkv
    low = quantize == "fp8"

    def weight(a, name):
        return _fp8(a) if low and name in MATRICES else a.astype(jnp.float32)

    def mm(a, b):
        return (_fp8(a) if low else a) @ b

    B, T = tokens.shape
    x = weight(w["embedding"], "embedding")[tokens] if low else \
        w["embedding"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = {k: weight(v, k) for k, v in p.items()}
        h = _rms(x, p["norm1"], eps)
        q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, T, hq, hd)
        k = k.reshape(B, T, hkv, hd)
        v = v.reshape(B, T, hkv, hd)
        if "q_norm" in p:
            q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        if low:
            q, k, v = _fp8(q), _fp8(k), _fp8(v)
        k = jnp.repeat(k, q_per_kv, axis=2)
        v = jnp.repeat(v, q_per_kv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        a = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", _fp8(a) if low else a, v)
        x = x + mm(o.reshape(B, T, hq * hd), p["wo"])
        h = _rms(x, p["norm2"], eps)
        x = x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                   p["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = jnp.take_along_axis(x, rows[..., None], axis=1)        # (B, R, d)
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    if m["tie_embeddings"]:
        head = weight(w["embedding"], "embedding").T
    else:
        head = weight(w["lm_head"], "lm_head")
    return mm(x, head)[..., :m["vocab"]]


@functools.lru_cache(maxsize=None)
def _compiled(m_items: tuple, quantize):
    m = dict(m_items)
    fwd = functools.partial(_forward, m, quantize)
    return jax.jit(fwd)


def forward(m: dict, w, tokens, pos, mask, rows, *, quantize=None):
    with jax.default_matmul_precision("highest"):
        fn = _compiled(tuple(sorted(m.items())), quantize)
        return np.asarray(fn(w, tokens, pos, mask, rows), np.float64)


def entropy(logits: np.ndarray) -> np.ndarray:
    """Shannon entropy (nats) over the last axis, in float64."""
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    return -(np.exp(logp) * logp).sum(-1)
