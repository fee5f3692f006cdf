"""The one traffic generator: a mix's parameters (``bench/traffic/<name>.json``)
and a seed in, one job's prompts out.

A job is the request list one ``serve()`` call drains.  Every job of every
seed has the same multiset of prompt lengths: the quantiles of a lognormal
clipped to ``[min, max]``, so a seed changes the order of the lengths and
the token ids, never the amount of prompt work.  Token ids are uniform over
the vocabulary above the engine's special ids, so no prompt contains
``</think>``, the paragraph break or the answer marker.  Prompts are
left-padded to ``prompt_width`` so that prefill has one shape.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

PAD_ID = 0


def job_seed(seed: int, job: int, stream: int = 0) -> np.random.SeedSequence:
    """Seed of job ``job`` in stream ``stream`` (0 = the measured window,
    1 = set-up's warm-up jobs, 2 = the sample the reference checks)."""
    return np.random.SeedSequence([int(seed), int(stream), int(job)])


def jax_seed(seed: int, job: int, stream: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` (the seeds the benchmark
    is given may exceed 32 bits)."""
    return int(job_seed(seed, job, stream).generate_state(1)[0] & 0x7FFFFFFF)


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    """The fixed length set: lognormal quantiles at (i + 0.5) / n."""
    p = mix["prompt_len"]
    mu, sigma = math.log(p["median"]), p["sigma"]
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    lens = [min(max(round(math.exp(mu + sigma * zi)), p["min"]), p["max"])
            for zi in z]
    return np.asarray(lens, np.int32)


def make_job(mix: dict, vocab: int, seed: int, job: int,
             stream: int = 0, n: int | None = None) -> dict:
    """``{"prompts": (n, width) int32 left-padded, "prompt_len": (n,)}``."""
    n = n or mix["requests_per_job"]
    rng = np.random.default_rng(job_seed(seed, job, stream))
    lens = rng.permutation(prompt_lengths(mix, n))
    width = mix["prompt_width"]
    lo = mix["special_ids_below"]
    prompts = np.full((n, width), PAD_ID, np.int32)
    for i, L in enumerate(lens):
        prompts[i, width - L:] = rng.integers(lo, vocab, L, dtype=np.int32)
    return {"prompts": prompts, "prompt_len": lens.astype(np.int32)}
