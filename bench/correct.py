"""The comparison that decides ``correct``.

Once the window has closed and the served model is freed, a sample of the
requests the window finished (drawn from the seed, the longest always in
it) is run through the plain reference, one forward per request over its
prompt and the tokens the program served.  The configuration names its
reference (``"reference": "<name>"``); ``bench/references/<name>.py``
gives ``init_weights(model, seed, devices)``, the served weights drawn
again from their seed over the cell's chips, and ``forward(model, w,
tokens, pos, mask, rows, quantize=None)``, float32 logits at ``rows``.

* ``answer_gap`` — the widest gap by which a greedy forced-answer token's
  reference logit lies below the reference's best logit at that position.
  The answer is rolled out greedily after ``</think>`` from the served
  cache, so it checks the prefill, every paged-decode write the request
  made and, in a proxy cell, the generator's rewind to the proxy's exit.
  (The reasoning tokens are sampled, so their own logits prove nothing.)
* ``eat_var_rel`` — the widest relative gap between the EMA variance of
  EAT that the serve loop recorded after each evaluation and the one the
  reference's EAT values give: the monitor model's probe (``</think>``
  and the answer marker over the served prefix) and its entropy.
* ``exit_mismatch`` — requests whose exit disagrees with the stopping rule
  applied to the reference's trajectory (first evaluation past
  ``min_evals`` whose variance is under delta).  A request whose
  reference variance comes within the ``eat_var_rel`` limit of delta is
  ambiguous and not counted.

The control puts the same reference, with every weight matrix in float8,
in the program's place and reads the same three numbers.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import job_seed

CHECK_REQUESTS = 8      # requests the reference checks per run
BATCH = 4               # reference forward batch
NUMBERS = ("answer_gap", "eat_var_rel", "exit_mismatch")


def verdict(nums: dict, limits: dict, failed: int = 0) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit: a run
    is correct when no request failed and no number is over its limit.
    The one rule for a run, the program's readings and the control's."""
    compared = {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}
    ok = failed == 0 and all(v["value"] <= v["limit"]
                             for v in compared.values())
    return ok, compared


def shapes(mix: dict) -> dict:
    """Fixed reference shapes of a mix, so one compile serves every run."""
    evals = (mix["budget"] - 1) // mix["eval_every"]
    T = (mix["prompt_width"] + mix["budget"] - 1 + 2 * evals
         + mix["answer_len"])
    return {"T": -(-T // 128) * 128, "evals": evals,
            "R": evals + mix["answer_len"]}


def sequence(prompt, reasoning, answer, *, every: int, probe: list,
             end_think: int, T: int, R: int) -> dict:
    """One request as the reference sees it: the fed tokens (prompt, then
    every reasoning token but the last sampled one), a probe block after
    each EAT evaluation's prefix, and the forced answer block; ``mask``
    gives each block the prefix its query saw in the serve loop."""
    P, n = len(prompt), len(reasoning)
    toks = list(prompt) + list(reasoning[:n - 1])
    pos = list(range(len(toks)))
    mask = np.zeros((T, T), bool)
    for i in range(len(toks)):
        mask[i, :i + 1] = True
    eat_rows, ans_rows = [], []

    def block(tokens, ctx):
        start = len(toks)
        for j, t in enumerate(tokens):
            toks.append(int(t))
            pos.append(ctx + j)
            i = len(toks) - 1
            mask[i, :ctx] = True
            mask[i, start:i + 1] = True

    for k in range(1, (n - 1) // every + 1):
        block(probe, P + k * every)
        eat_rows.append(len(toks) - 1)
    start = len(toks)
    block([end_think] + list(answer[:-1]), P + n - 1)
    ans_rows = list(range(start, len(toks)))
    if len(toks) > T or len(eat_rows) + len(ans_rows) > R:
        raise ValueError(f"request of {n} tokens exceeds the reference "
                         f"shapes T={T} R={R}")
    for i in range(len(toks), T):
        mask[i, i] = True
    rows = eat_rows + ans_rows
    return {"tokens": np.asarray(toks + [0] * (T - len(toks)), np.int32),
            "pos": np.asarray(pos + [0] * (T - len(pos)), np.int32),
            "mask": mask,
            "rows": np.asarray(rows + [0] * (R - len(rows)), np.int32),
            "n_eat": len(eat_rows), "n_ans": len(ans_rows)}


def reference_weights(ref, cfg: dict, devices) -> dict:
    """The reference's weights of every model a configuration serves."""
    return {side: ref.init_weights(cfg[side]["model"],
                                   cfg[side]["weights_seed"], devices)
            for side in ("generator", "proxy") if cfg.get(side)}


def entropy(logits: np.ndarray) -> np.ndarray:
    """Shannon entropy (nats) over the last axis, in float64."""
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    return -(np.exp(logp) * logp).sum(-1)


def run_reference(ref, m: dict, w, seqs: list[dict], quantize=None) -> list:
    """Logits (rows, vocab) of each sequence's checked rows."""
    out = []
    for b in range(0, len(seqs), BATCH):
        part = seqs[b:b + BATCH]
        pad = part + [part[-1]] * (BATCH - len(part))
        lg = ref.forward(m, w, np.stack([s["tokens"] for s in pad]),
                         np.stack([s["pos"] for s in pad]),
                         np.stack([s["mask"] for s in pad]),
                         np.stack([s["rows"] for s in pad]),
                         quantize=quantize)
        out += [lg[i, :s["n_eat"] + s["n_ans"]] for i, s in enumerate(part)]
    return out


def ema_vars(values, alpha: float) -> list[float]:
    """Debiased EMA variance after each value (the paper's Alg. 1, mean and
    variance starting at 0)."""
    m = v = 0.0
    out = []
    for i, x in enumerate(values, 1):
        m = (1 - alpha) * m + alpha * x
        v = (1 - alpha) * v + alpha * (x - m) ** 2
        out.append(v / (1 - (1 - alpha) ** i))
    return out


def served_vars(eat_trace) -> dict:
    """{evaluation k: variance the serve loop recorded after it}."""
    out = {}
    for _, k, var in eat_trace:
        if k > 0:
            out[int(k)] = float(var)
    return out


def exit_eval(vars_by_k: dict, delta: float, min_evals: int):
    for k in sorted(vars_by_k):
        if k >= min_evals and vars_by_k[k] < delta:
            return k
    return None


def served_exit(result: dict, every: int):
    """Evaluation the program exited at: an EAT exit at the last
    evaluation, None for the budget, "end" for a natural ``</think>``."""
    if result["exit_reason"] == "eat":
        return (result["n_reasoning"] - 1) // every
    if result["exit_reason"] == "budget":
        return None
    return "end"


def sample(results: list[dict], seed: int, k: int = CHECK_REQUESTS) -> list:
    """Indices of the checked requests: the longest, then ``k - 1`` more
    drawn from the seed."""
    longest = max(range(len(results)),
                  key=lambda i: results[i]["n_reasoning"])
    rng = np.random.default_rng(job_seed(seed, 0, stream=2))
    rest = [i for i in rng.permutation(len(results)) if i != longest]
    return [longest] + [int(i) for i in rest[:k - 1]]


def compare(cfg: dict, mix: dict, finished: list[tuple], ref,
            weights: dict, *, margin: float, control: bool = False) -> dict:
    """The three numbers for the program and, with ``control``, for the
    float8 reference in its place.  ``finished`` is a list of
    ``(prompt tokens, result)``; ``ref`` is the reference module and
    ``weights`` its ``reference_weights``."""
    sh = shapes(mix)
    every, delta = mix["eval_every"], float(cfg["delta"])
    seqs = [sequence(p, r["reasoning_tokens"], r["answer_tokens"],
                     every=every, probe=cfg["probe_ids"],
                     end_think=cfg["end_think_id"], T=sh["T"], R=sh["R"])
            for p, r in finished]
    mon = "proxy" if cfg["monitor"] == "proxy" else "generator"

    def logits(quantize):
        """(generator logits, monitor logits) of every sequence."""
        gen = run_reference(ref, cfg["generator"]["model"],
                            weights["generator"], seqs, quantize)
        if mon == "generator":
            return gen, gen
        return gen, run_reference(ref, cfg["proxy"]["model"],
                                  weights["proxy"], seqs, quantize)

    ref_gen, ref_mon = logits(None)
    sides = {"program": None}
    if control:
        sides["control"] = logits("fp8")
    out = {}
    for side, low in sides.items():
        gap = rel = 0.0
        mismatch = ambiguous = 0
        for i, ((_, r), s) in enumerate(zip(finished, seqs)):
            ne = s["n_eat"]
            ref_ans = ref_gen[i][ne:]
            if low is None:
                picked = np.asarray(r["answer_tokens"])
            else:
                picked = low[0][i][ne:].argmax(-1)
            gap = max(gap, float(np.max(
                ref_ans.max(-1) - ref_ans[np.arange(len(picked)), picked])))
            ref_v = ema_vars(entropy(ref_mon[i][:ne]), cfg["alpha"])
            ref_by_k = dict(enumerate(ref_v, 1))
            if low is None:
                got_by_k = served_vars(r["eat_trace"])
                got_exit = served_exit(r, every)
            else:
                got_v = ema_vars(entropy(low[1][i][:ne]), cfg["alpha"])
                got_by_k = dict(enumerate(got_v, 1))
                got_exit = exit_eval(got_by_k, delta, cfg["min_evals"])
                if got_exit is not None and got_exit < ne:
                    got_by_k = {k: v for k, v in got_by_k.items()
                                if k <= got_exit}
            for k, v in got_by_k.items():
                if k in ref_by_k:
                    rel = max(rel, abs(v - ref_by_k[k]) / abs(ref_by_k[k]))
            if got_exit == "end":
                continue
            near = any(abs(v - delta) <= margin * delta
                       for k, v in ref_by_k.items()
                       if k >= cfg["min_evals"])
            if near:
                ambiguous += 1
            elif exit_eval(ref_by_k, delta, cfg["min_evals"]) != got_exit:
                mismatch += 1
        out[side] = {"answer_gap": gap, "eat_var_rel": float(rel),
                     "exit_mismatch": mismatch, "ambiguous": ambiguous}
    return out
