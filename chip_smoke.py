"""Chip smoke: the EAT serving stack end to end on a TPU, at published
widths, with random weights made from fixed seeds.

  python chip_smoke.py             # one chip: phases a, b, c
  python chip_smoke.py --chips 4   # four chips: the sharded phase only

One chip (generator Qwen3-1.7B, black-box monitor eat-proxy-1.5b):
  a. the Pallas kernels of the serve path (entropy probe, paged attention)
     against their XLA references, random bf16 inputs at both models'
     widths;
  b. self-EAT serving: 16 requests through 8 slots on a 1x1 mesh, paged
     KV cache read by the page-native kernel (``--attn-impl auto``), the
     synchronous loop, budget 512, chunk 32, and a delta at which EAT
     exits fire;
  c. proxy-tier serving on the overlapped loop: 8 requests through 4
     slots, the generator decoding blind while eat-proxy-1.5b decides when
     it stops.
Four chips (eat-paper-8b, 16.4 GB of bf16 weights, more than one v5e
holds): phase b's requests served greedily on 1x4 and on 2x2 meshes, with
prefill logits and first EAT values compared between the two.

Engines are built by the launcher's own construction
(``repro.launch.serve.parse_args`` + ``build_engine``).  Each phase prints
its first-run time (compilation included) and its warm time on lines of
their own: information, not metrics.  A failed check raises, so the script
exits non-zero at the first one.  The last line of standard output is the
JSON result line.  Without a TPU the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GEN, PROXY, PAPER_8B = "qwen3-1.7b", "eat-proxy-1.5b", "eat-paper-8b"

#: phase a: |pallas - xla| <= ATOL + RTOL * |xla|.  Both sides read the
#: same bf16 inputs and accumulate in f32; the XLA attention reference
#: also rounds q*scale and the softmax weights to bf16 before its matmuls.
ENTROPY_TOL = (1e-2, 0.0)          # nats, on entropies of ~11.4 nats
ATTENTION_TOL = (2e-2, 2e-2)       # on bf16 attention outputs

#: four-chip phase: a 1x4 and a 2x2 mesh reduce the tensor-parallel
#: matmuls in different orders, so bf16 results differ by rounding.
LOGIT_TOL = 5e-2                   # max |d logit| / max |logit|
EAT_TOL = 5e-2                     # max |d EAT|, nats


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ------------------------------------------------------------- phase a
def phase_kernels(archs=(GEN, PROXY), *, interpret: bool = False,
                  batch: int = 8, page_size: int = 16, ranks: int = 40,
                  seed: int = 0) -> dict:
    """Pallas kernels vs their XLA references at each arch's widths.
    Returns {name: max abs error}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.kernels.entropy_probe.kernel import entropy_probe_pallas
    from repro.kernels.entropy_probe.ops import _xla_entropy, pick_block_v
    from repro.kernels.paged_attention.kernel import paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_xla

    errs = {}

    def compare(name, kernel, ref, args, tol):
        kern, ref = jax.jit(kernel), jax.jit(ref)
        t0 = time.perf_counter()
        kern.lower(*args).compile()
        t_compile = time.perf_counter() - t0
        out, _ = _timed(lambda: kern(*args))
        out, t_warm = _timed(lambda: kern(*args))
        want = np.asarray(ref(*args), np.float32)
        got = np.asarray(out, np.float32)
        err = float(np.max(np.abs(got - want)))
        atol, rtol = tol
        print(f"phase a {name}: compile {t_compile:.3f} s")
        print(f"phase a {name}: warm {t_warm * 1e3:.3f} ms")
        print(f"phase a {name}: max abs error {err:.3e} "
              f"(tolerance |err| <= {atol:g} + {rtol:g}*|ref|)")
        _check(bool(np.all(np.isfinite(got))), f"{name} output is finite")
        _check(bool(np.all(np.abs(got - want) <= atol + rtol * np.abs(want))),
               f"{name} within tolerance")
        errs[name] = err

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    for arch in archs:
        cfg = get_config(arch)
        dt = jnp.dtype(cfg.dtype)
        d, Vp, vocab = cfg.d_model, cfg.padded_vocab, cfg.vocab
        k1, k2, k3, k4, k5, key = jax.random.split(key, 6)

        h = jax.random.normal(k1, (batch, d), dt)
        w = (jax.random.normal(k2, (d, Vp)) * d ** -0.5).astype(dt)
        block_v = pick_block_v(batch, d, Vp, dt.itemsize)
        compare(f"{arch} entropy_probe B={batch}",
                lambda h, w: entropy_probe_pallas(
                    h, w, vocab, block_v=block_v, interpret=interpret),
                lambda h, w: _xla_entropy(h, w, vocab), (h, w), ENTROPY_TOL)

        Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        n_pages = batch * ranks + 1                   # + the trash page 0
        kp = jax.random.normal(k3, (n_pages, page_size, Hkv, hd), dt)
        vp = jax.random.normal(k4, (n_pages, page_size, Hkv, hd), dt)
        counts = rng.integers(ranks // 4, ranks + 1, batch).astype(np.int32)
        perm = rng.permutation(np.arange(1, n_pages)).astype(np.int32)
        pages = np.zeros((batch, ranks), np.int32)
        bpos = np.full((batch, ranks, page_size), -1, np.int32)
        for b in range(batch):
            c = int(counts[b])
            pages[b, :c] = perm[b * ranks:b * ranks + c]
            pos = np.arange(c * page_size).reshape(c, page_size)
            pos[-1, page_size // 2 + 1:] = -1         # a partial last page
            bpos[b, :c] = pos
        filled = (counts - 1) * page_size + page_size // 2 + 1
        for m in (1, 2):                              # decode step, probe
            q = jax.random.normal(jax.random.fold_in(k5, m),
                                  (batch, m, Hq, hd), dt)
            q_pos = (filled[:, None] + np.arange(m)[None]).astype(np.int32)
            args = (q, kp, vp, jnp.asarray(pages), jnp.asarray(counts),
                    jnp.asarray(bpos), jnp.asarray(q_pos))
            compare(f"{arch} paged_attention m={m}",
                    lambda q, kp, vp, pg, ct, bp, qp: paged_attention_pallas(
                        q, kp, vp, pg, ct, bp, qp, scale=hd ** -0.5,
                        interpret=interpret),
                    lambda q, kp, vp, pg, ct, bp, qp: paged_attention_xla(
                        q, kp, vp, pg, bp, qp, scale=hd ** -0.5),
                    args, ATTENTION_TOL)
    return errs


# ------------------------------------------------------- phases b and c
def serve_argv(arch: str, *, requests: int, slots: int, budget: int,
               chunk: int, eval_every: int, mesh: str = "1x1",
               attn_impl: str = "auto", interpret: bool = False,
               overlap: bool = False, proxy: str | None = None,
               greedy: bool = False, delta: float = 1e9) -> list[str]:
    """The launcher command line of a serve phase.  ``delta=1e9`` stops a
    request at its second EAT evaluation, so exits land well below the
    budget and slots are refilled."""
    argv = ["--arch", arch, "--mesh", mesh, "--requests", str(requests),
            "--batch", str(slots), "--budget", str(budget),
            "--chunk", str(chunk), "--eval-every", str(eval_every),
            "--delta", repr(delta), "--cache", "paged",
            "--attn-impl", attn_impl, "--overlap", "on" if overlap else "off"]
    if proxy:
        argv += ["--monitor", "proxy", "--proxy-config", proxy]
    if interpret:
        argv.append("--interpret")
    if greedy:
        argv.append("--greedy")
    return argv


def check_served(results: list[dict], n_requests: int, budget: int) -> dict:
    """The serve-phase checks: every request terminal, within budget, at
    least one below it, and every EAT value finite (a non-finite EAT makes
    the EMA variance the trace records non-finite)."""
    import math

    _check(len(results) == n_requests, f"{n_requests} requests served")
    for r in results:
        _check(r["status"] in ("exited", "exhausted"),
               f"request {r['request']} terminal (status {r['status']})")
        _check(r["n_reasoning"] <= budget, "n_reasoning <= budget")
    n = [r["n_reasoning"] for r in results]
    _check(min(n) < budget, "an exit below budget (slots refilled)")
    evals = [v for r in results for (_, n_ev, v) in r["eat_trace"] if n_ev > 0]
    _check(len(evals) > 0, "EAT evaluated")
    _check(all(math.isfinite(v) for v in evals), "every EAT value finite")
    return {"n_reasoning": n,
            "exits": sorted({r["exit_reason"] for r in results}),
            "evals": len(evals)}


def _serve_twice(name: str, argv: list[str], prepare=None):
    """Build the engine the launcher builds for ``argv`` and serve its
    queue twice: cold (compilation included), then warm.  ``prepare(engine,
    batch)`` runs between the build and the first serve."""
    import numpy as np

    from repro.data.synthetic import ChainTask
    from repro.launch import serve

    args = serve.parse_args(argv)
    batch = ChainTask().serve_batch(np.random.default_rng(0), args.requests)
    t0 = time.perf_counter()
    engine = serve.build_engine(args, batch["prompts"].shape[1])
    print(f"phase {name}: engine build (weights init) "
          f"{time.perf_counter() - t0:.3f} s")
    if prepare is not None:
        prepare(engine, batch)
    t0 = time.perf_counter()
    serve.serve_requests(engine, args, batch, record_trace=True)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = serve.serve_requests(engine, args, batch, record_trace=True)
    t_warm = time.perf_counter() - t0
    print(f"phase {name}: compile (first run - warm run) "
          f"{t_cold - t_warm:.3f} s")
    print(f"phase {name}: first run (compilation included) {t_cold:.3f} s")
    print(f"phase {name}: warm run {t_warm:.3f} s")
    return engine, results


def phase_self_eat(arch: str = GEN, *, requests: int = 16, slots: int = 8,
                   budget: int = 512, chunk: int = 32, eval_every: int = 64,
                   attn_impl: str = "auto", interpret: bool = False) -> dict:
    """Phase b: self-EAT serving on the synchronous loop."""
    argv = serve_argv(arch, requests=requests, slots=slots, budget=budget,
                      chunk=chunk, eval_every=eval_every,
                      attn_impl=attn_impl, interpret=interpret)
    engine, results = _serve_twice("b", argv)
    _check(engine.monitor_mode == "self", "self-EAT monitor")
    out = check_served(results, requests, budget)
    print(f"phase b: n_reasoning {out['n_reasoning']} exits {out['exits']}")
    return out


def phase_proxy(arch: str = GEN, proxy: str = PROXY, *, requests: int = 8,
                slots: int = 4, budget: int = 512, chunk: int = 32,
                eval_every: int = 64, attn_impl: str = "auto",
                interpret: bool = False) -> dict:
    """Phase c: the black-box proxy tier on the overlapped loop."""
    argv = serve_argv(arch, requests=requests, slots=slots, budget=budget,
                      chunk=chunk, eval_every=eval_every,
                      attn_impl=attn_impl, interpret=interpret,
                      overlap=True, proxy=proxy)
    engine, results = _serve_twice("c", argv)
    _check(engine.monitor_mode == "proxy", "proxy monitor")
    gen_keys = list(engine.executor._programs)
    _check(not [k for k in gen_keys if k[0] == "probe"
                or (k[0] == "chunk" and k[2])],
           f"the generator built no probe program ({gen_keys})")
    out = check_served(results, requests, budget)
    print(f"phase c: n_reasoning {out['n_reasoning']} exits {out['exits']}")
    return out


# ------------------------------------------------------------ four chips
def _layout(tree) -> list[tuple]:
    """(shape, first shard's shape, bytes, device count) of every array —
    read at once, since a donated array cannot be inspected later."""
    import jax

    return [(a.shape, a.addressable_shards[0].data.shape, a.nbytes,
             len(a.sharding.device_set))
            for a in jax.tree_util.tree_leaves(tree)]


def _spread(layout: list[tuple], n_devices: int, what: str) -> None:
    """Every array spans ``n_devices`` devices, and the bulk of the bytes
    is split across them (shard shape smaller than the array's)."""
    total = sum(nbytes for _, _, nbytes, _ in layout)
    split = sum(nbytes for shape, shard, nbytes, _ in layout if shard != shape)
    _check(all(n == n_devices for *_, n in layout),
           f"{what} on {n_devices} devices")
    print(f"phase mesh: {what}: {split / total:.3f} of {total} bytes "
          f"split across {n_devices} devices")
    _check(split / total > 0.9, f"{what} sharded, not replicated")


def _prefill_logits_and_eat(engine, batch, rows: int):
    """Prefill ``rows`` prompts through the executor's prefill program
    (as ``ReasoningEngine.start`` does) and return the last-position
    logits over the vocabulary and the first EAT values."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.cache import alloc_cache, page_align
    from repro.serving.executor import positions_for

    cfg = engine.model.cfg
    prompts = jnp.asarray(batch["prompts"][:rows])
    plen = jnp.asarray(batch["prompt_len"][:rows])
    S = prompts.shape[1]
    pos1d = jnp.arange(S, dtype=jnp.int32)[None, :] - (S - plen)[:, None]
    pos1d = jnp.where(pos1d >= 0, pos1d, -1)
    cache = alloc_cache(cfg, rows, page_align(S + 16, 16))
    hidden, cache = engine.executor.prefill(
        engine.params, prompts, positions_for(cfg, pos1d), pos1d, cache)
    logits = engine.model.logits(engine.params, hidden[:, -1])[:, :cfg.vocab]
    eat = engine.executor.probe(engine.params, cache, plen.astype(jnp.int32))
    return np.asarray(logits, np.float32), np.asarray(eat, np.float32)


def phase_mesh(arch: str = PAPER_8B, meshes=("1x4", "2x2"), *,
               requests: int = 16, slots: int = 8, budget: int = 512,
               chunk: int = 32, eval_every: int = 64,
               attn_impl: str = "auto") -> dict:
    """The sharded model served greedily on two meshes of four devices."""
    import numpy as np

    runs = {}
    for mesh in meshes:
        argv = serve_argv(arch, requests=requests, slots=slots,
                          budget=budget, chunk=chunk, eval_every=eval_every,
                          mesh=mesh, attn_impl=attn_impl, greedy=True)
        n_dev = int(np.prod([int(x) for x in mesh.split("x")]))
        pools, run = [], {}

        def prepare(engine, batch, mesh=mesh, n_dev=n_dev, pools=pools,
                    run=run):
            _spread(_layout(engine.params), n_dev, f"{mesh} parameters")
            chunk_fn = engine.executor.decode_chunk

            def spy(params, state, *a, **k):
                # the serve loop's own state: its page pools, as placed
                out = chunk_fn(params, state, *a, **k)
                if not pools:
                    pools.extend(_layout(out.cache["layers"]))
                return out

            engine.executor.decode_chunk = spy
            run["logits"], run["eat"] = _prefill_logits_and_eat(
                engine, batch, slots)

        engine, run["results"] = _serve_twice(f"mesh {mesh}", argv, prepare)
        _spread(pools, n_dev, f"{mesh} KV page pools")
        summary = check_served(run["results"], requests, budget)
        print(f"phase mesh {mesh}: n_reasoning {summary['n_reasoning']} "
              f"exits {summary['exits']}")
        runs[mesh] = run
        del engine               # free this mesh's weights before the next
        gc.collect()

    a, b = (runs[m] for m in meshes)
    scale = float(np.max(np.abs(a["logits"])))
    d_logit = float(np.max(np.abs(a["logits"] - b["logits"]))) / scale
    d_eat = float(np.max(np.abs(a["eat"] - b["eat"])))
    print(f"phase mesh: prefill logits {meshes[0]} vs {meshes[1]}: max |d| "
          f"/ max |logit| = {d_logit:.3e} (tolerance {LOGIT_TOL:g})")
    print(f"phase mesh: first EAT {meshes[0]} vs {meshes[1]}: max |d| = "
          f"{d_eat:.3e} nats (tolerance {EAT_TOL:g}); "
          f"EAT {np.round(a['eat'], 4).tolist()}")
    _check(d_logit <= LOGIT_TOL, "prefill logits agree across meshes")
    _check(d_eat <= EAT_TOL, "first EAT values agree across meshes")
    agree, same = [], 0
    for ra, rb in zip(a["results"], b["results"]):
        ta, tb = ra["reasoning_tokens"], rb["reasoning_tokens"]
        n = min(len(ta), len(tb))
        diff = np.nonzero(ta[:n] != tb[:n])[0]
        agree.append(int(diff[0]) if diff.size else n)
        same += int(len(ta) == len(tb) and not diff.size)
    print(f"phase mesh: token streams identical for {same}/{len(agree)} "
          f"requests; agreeing prefix lengths {agree}")
    return dict(d_logit=d_logit, d_eat=d_eat, identical=same,
                prefix=agree)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: only the sharded "
                         "eat-paper-8b phase, on four")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    if args.chips == 4:
        _check(len(devices) >= 4, "four devices visible")
        phase_mesh()
    else:
        # each phase's engine is dropped before the next one is built
        for phase in (phase_kernels, phase_self_eat, phase_proxy):
            phase()
            gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
