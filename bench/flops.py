"""Operations and bytes that the served work needs, computed from a
configuration's shapes (``model`` block of ``bench/configs/<name>.json``)
and from what each request did.  These are the algorithm's needs, not what
the program happens to move: padding ranks, rows left idle in a batch,
overshoot a proxy retracts and probes of rows that were not due are not
counted, so a share built on them cannot pass 100% for a sound timing.

Request accounting (one request of prompt length ``P`` that emitted ``n``
reasoning tokens; the serving step feeds token ``i`` at position ``P + i``
and never feeds the last sampled one):

* decode: steps ``i = 1 .. n-1``, one query over ``P + i`` cached tokens;
* EAT evaluations ``k = 1 .. (n-1) // every``: two probe queries
  (``</think>``, answer marker) over ``P + k*every + 2`` tokens;
* forced answer: ``</think>`` then ``answer_len - 1`` fed answer tokens,
  one query each over ``P + n + j`` tokens, ``j = 0 .. answer_len-1``.
"""
from __future__ import annotations

BF16 = 2


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def matmul_flops_per_token(m: dict) -> float:
    """Weight-matmul FLOPs of one token through every layer and the
    unembedding (2 per multiply-add), SwiGLU MLP, GQA projections."""
    d, hd, dff = m["d_model"], head_dim(m), m["d_ff"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    per_layer = 2 * d * (hq * hd + 2 * hkv * hd) + 2 * hq * hd * d \
        + 3 * 2 * d * dff
    return m["n_layers"] * per_layer + 2 * d * m["vocab"]


def attention_flops(m: dict, queries: int, ctx: int) -> float:
    """Scores and weighted sum of ``queries`` query positions over ``ctx``
    cached tokens, every layer."""
    return m["n_layers"] * 4 * queries * m["n_heads"] * head_dim(m) * ctx


def token_flops(m: dict, ctx: int) -> float:
    """Model FLOPs of one token's forward at context length ``ctx``."""
    return matmul_flops_per_token(m) + attention_flops(m, 1, ctx)


def attention_bytes(m: dict, queries: int, ctx: int,
                    itemsize: int = BF16) -> float:
    """K and V of ``ctx`` cached tokens plus the queries and outputs,
    every layer."""
    hd = head_dim(m)
    kv = 2 * ctx * m["n_kv_heads"] * hd
    qo = 2 * queries * m["n_heads"] * hd
    return m["n_layers"] * (kv + qo) * itemsize


def request_calls(P: int, n: int, every: int, answer_len: int):
    """The attention calls one request needs: ``(kind, queries, ctx)``."""
    for i in range(1, n):
        yield "decode", 1, P + i
    for k in range(1, (n - 1) // every + 1):
        yield "probe", 2, P + k * every + 2
    for j in range(answer_len):
        yield "answer", 1, P + n + j


def emitted_token_flops(m: dict, P: int, n: int, answer_len: int) -> float:
    """Model FLOPs of every token a request emitted: ``n`` reasoning and
    ``answer_len`` answer tokens, token ``j`` at context ``P + j``."""
    return sum(token_flops(m, P + j) for j in range(n + answer_len))


def paged_attention_work(m: dict, P: int, n: int, every: int,
                         answer_len: int, *, probe: bool = True,
                         decode: bool = True, answer: bool = True):
    """(flops, bytes) of a request's page-native attention calls on model
    ``m``; the flags pick the call kinds that model runs."""
    keep = {"decode": decode, "probe": probe, "answer": answer}
    fl = by = 0.0
    for kind, q, ctx in request_calls(P, n, every, answer_len):
        if keep[kind]:
            fl += attention_flops(m, q, ctx)
            by += attention_bytes(m, q, ctx)
    return fl, by


def padded_vocab(m: dict) -> int:
    return -(-m["vocab"] // 256) * 256


def entropy_probe_work(m: dict, rows: int, itemsize: int = BF16):
    """(flops, bytes) of one entropy-probe call over ``rows`` hidden
    states: the unembedding ``W`` (d x padded vocab) must be read once,
    and every row needs one dot product per vocabulary entry."""
    d, vp = m["d_model"], padded_vocab(m)
    return 2.0 * rows * d * vp, float(d * vp * itemsize + rows * d * itemsize)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """(least time / measured time, the bound that sets the least time)."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return max(t_flops, t_bytes) / seconds, bound
