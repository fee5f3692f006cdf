"""Production serving launcher: batched EAT-monitored reasoning serving.

  python -m repro.launch.serve --arch tiny-reasoner --local \
      --ckpt artifacts/tiny_reasoner.ckpt --batch 8 --delta 1e-3

On TPU the same launcher builds the production mesh and shards the serve
state (the dry-run proves every assigned architecture lowers; this is the
runtime equivalent).  On CPU it serves the synthetic-task models.

``parse_args`` / ``build_engine`` / ``serve_requests`` are the launcher's
construction, shared with ``chip_smoke.py``: the smoke drives exactly the
engine a user's command line builds.

With ``--requests`` the last line printed is the serve call's telemetry
summary as one JSON object: per span its count, total and self seconds,
and every counter (``serving/telemetry.py``, docs/serving.md).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.eat import make_probe
from repro.core.monitor import ReasoningMonitor
from repro.core.stopping import EATStopper
from repro.data.synthetic import ChainTask, Tokens
from repro.launch.mesh import local_ctx, make_ctx, make_device_ctx
from repro.models import Model
from repro.serving.cache import CacheConfig
from repro.serving.engine import EngineConfig, ReasoningEngine
from repro.serving.executor import init_params
from repro.serving.sampler import SamplerConfig
from repro.serving.telemetry import ServeResults
from repro.training.checkpoint import load_checkpoint
from repro.utils.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-reasoner")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--delta", type=float, default=1e-3)
    ap.add_argument("--alpha", type=float, default=0.2)
    ap.add_argument("--budget", type=int, default=96)
    ap.add_argument("--eval-every", type=int, default=0, metavar="N",
                    help="EAT evaluation schedule: every N reasoning tokens "
                         "(0 = at each paragraph-break token, the paper's "
                         "schedule)")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve on a (data x model) mesh over the visible "
                         "devices, e.g. --mesh 4x2 (overrides --local / the "
                         "production mesh)")
    ap.add_argument("--greedy", action="store_true",
                    help="greedy decoding (default: temperature 0.6, "
                         "top-p 0.95)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling cutoff (0 = off)")
    ap.add_argument("--typical-p", type=float, default=1.0,
                    help="locally-typical sampling mass (1 = off)")
    ap.add_argument("--min-p", type=float, default=0.0,
                    help="min-p sampling cutoff relative to the max-prob "
                         "token (0 = off)")
    ap.add_argument("--attn-impl",
                    choices=["gather", "auto", "xla", "pallas"],
                    default="gather",
                    help="decode/probe attention implementation: gather "
                         "(materialize the paged cache's logical view) or "
                         "the page-native path (auto/xla/pallas — K/V read "
                         "straight off the page pools through the mapped-"
                         "page list, O(mapped pages) per token; auto = the "
                         "Pallas kernel on one TPU device, xla elsewhere; "
                         "'pallas' compiles the TPU kernel, and off the TPU "
                         "needs --interpret — docs/serving.md)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode (CPU "
                         "runs of --attn-impl pallas)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="decode steps per jitted dispatch")
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N queued requests through --batch slots "
                         "with continuous batching (0 = single batch)")
    ap.add_argument("--cache", choices=["ring", "paged"], default="ring",
                    help="KV-cache backend for --requests serving: ring "
                         "(dense, batch-lifetime capacity) or paged (block "
                         "pool, per-block admission — docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged backend: logical slots per physical page")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged backend: physical page-pool size "
                         "(0 = ring-equivalent auto sizing)")
    ap.add_argument("--overlap", choices=["off", "on"], default="off",
                    help="--requests serving loop: off = synchronous chunk "
                         "boundaries (dispatch, block, harvest), on = the "
                         "double-buffered pipeline (chunk N+1 dispatched "
                         "while chunk N is harvested; bit-identical token "
                         "streams under greedy sampling, proxy exits land "
                         "at most one chunk later — docs/serving.md "
                         "§Overlapped serving)")
    ap.add_argument("--monitor", choices=["self", "proxy"], default="self",
                    help="EAT monitor tier: self (white-box, probe inlined "
                         "in the decode chunk) or proxy (black-box, a "
                         "second model shadows the emitted stream — "
                         "docs/serving.md §Black-box monitoring)")
    ap.add_argument("--proxy-config", default=None, metavar="ARCH",
                    help="monitor=proxy: proxy model architecture "
                         "(default: --arch, i.e. a same-family twin)")
    ap.add_argument("--proxy-ckpt", default=None,
                    help="monitor=proxy: proxy checkpoint (default: random "
                         "weights, seeded differently from the generator)")
    ap.add_argument("--proxy-mesh", default=None, metavar="DATAxMODEL",
                    help="monitor=proxy: give the proxy its own (smaller) "
                         "mesh over the visible devices, e.g. 1x2 "
                         "(default: share the generator's context)")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.monitor == "proxy" and not args.requests:
        ap.error("--monitor proxy serves through the scheduler: pass "
                 "--requests N")
    if args.overlap == "on" and not args.requests:
        ap.error("--overlap on applies to the --requests serving loop: "
                 "pass --requests N")
    if args.monitor != "proxy" and (args.proxy_config or args.proxy_ckpt
                                    or args.proxy_mesh):
        ap.error("--proxy-config/--proxy-ckpt/--proxy-mesh only apply with "
                 "--monitor proxy (default monitor is 'self')")
    return args


def _mesh_ctx(spec: str):
    d, m = (int(x) for x in spec.lower().split("x"))
    return make_device_ctx(d, m)


def _params(model: Model, ckpt: str | None, seed: int, what: str):
    if ckpt:
        like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        return load_checkpoint(ckpt, like)
    print(f"WARNING: no {what}checkpoint — random {what}weights")
    return init_params(model, jax.random.PRNGKey(seed))


def build_engine(args: argparse.Namespace,
                 prompt_width: int) -> ReasoningEngine:
    """The engine ``args`` describe.  ``prompt_width`` (the left-padded
    prompt length) sizes the batch-lifetime cache of ``--requests``
    serving."""
    cfg = get_config(args.arch)
    if args.mesh:
        ctx = _mesh_ctx(args.mesh)
    elif args.local:
        ctx = local_ctx()
    else:
        ctx = make_ctx(multi_pod=args.multipod)
    model = Model(cfg, ctx, attn_impl="xla", interpret=args.interpret)
    params = _params(model, args.ckpt, 0, "")

    sampler = (SamplerConfig(greedy=True) if args.greedy else
               SamplerConfig(temperature=0.6, top_p=0.95, top_k=args.top_k,
                             typical_p=args.typical_p, min_p=args.min_p))
    ecfg = EngineConfig(
        max_reasoning_tokens=args.budget, capacity=args.budget + 128,
        pad_id=Tokens.PAD, end_think_id=Tokens.END_THINK,
        newline_id=Tokens.NEWLINE, eos_id=Tokens.EOS,
        sampler=sampler, cache=CacheConfig(attn_impl=args.attn_impl),
        chunk_len=args.chunk,
    )
    schedule = (dict(schedule="every_n", every_n=args.eval_every)
                if args.eval_every else {})
    monitor = ReasoningMonitor(
        stopper=EATStopper(alpha=args.alpha, delta=args.delta),
        probe=make_probe(Tokens.END_THINK, (Tokens.ANS,)),
        newline_id=Tokens.NEWLINE, **schedule,
    )
    if args.requests:
        # continuous batching: args.batch slots over a longer request
        # queue; early-exiting sequences free their slot for the next
        # prompt.  The shared ring pointer advances for the whole run, so
        # (logical) capacity must cover the batch-lifetime worst case, not
        # one budget; with --cache paged that capacity is int32 metadata
        # and the PHYSICAL footprint is --num-pages pages of live KV.  The
        # cache config must be final BEFORE the engine is built — the
        # engine bakes --attn-impl/--page-size into its model.
        from repro.serving.scheduler import SlotScheduler

        ecfg.capacity = SlotScheduler.required_capacity(
            prompt_width, args.requests, args.batch, args.budget)
        if args.overlap == "on":
            # the overlapped loop's ring guard adds one in-flight chunk to
            # its (host-mirror) pointer estimate — give it that headroom
            ecfg.capacity += args.chunk
        ecfg.cache = CacheConfig(kind=args.cache, page_size=args.page_size,
                                 num_pages=args.num_pages,
                                 attn_impl=args.attn_impl)

    proxy = None
    if args.monitor == "proxy":
        from repro.serving.proxy import ProxyConfig

        proxy_cfg = get_config(args.proxy_config or args.arch)
        if proxy_cfg.vocab != cfg.vocab:
            raise SystemExit(f"proxy arch {proxy_cfg.name} must share the "
                             f"generator's tokenizer (vocab {cfg.vocab}, "
                             f"got {proxy_cfg.vocab})")
        proxy_ctx = _mesh_ctx(args.proxy_mesh) if args.proxy_mesh else ctx
        proxy_model = Model(proxy_cfg, proxy_ctx, attn_impl="xla",
                            interpret=args.interpret)
        proxy_params = _params(proxy_model, args.proxy_ckpt, 1, "proxy ")
        proxy = ProxyConfig(model=proxy_model, params=proxy_params)

    return ReasoningEngine(model, params, ecfg, monitor, proxy=proxy)


def serve_requests(engine: ReasoningEngine, args: argparse.Namespace,
                   batch: dict, *, record_trace: bool = False
                   ) -> ServeResults:
    """``--requests`` serving: the whole queue through ``--batch`` slots,
    with a 4-token forced answer harvested from each request."""
    return engine.serve(batch["prompts"], batch["prompt_len"],
                        jax.random.PRNGKey(0), batch_size=args.batch,
                        answer_len=4, overlap=args.overlap == "on",
                        record_trace=record_trace)


def main():
    enable_compile_cache()
    args = parse_args()
    task = ChainTask()
    batch = task.serve_batch(np.random.default_rng(0),
                             args.requests or args.batch)
    engine = build_engine(args, batch["prompts"].shape[1])

    if args.requests:
        results = serve_requests(engine, args, batch)
        ans = np.array([ChainTask.extract_answer(r["answer_tokens"][None])[0]
                        for r in results])
        n = np.array([r["n_reasoning"] for r in results])
        print(f"served {args.requests} requests through {args.batch} slots "
              f"(monitor={engine.monitor_mode})")
        print(f"answers: {ans}  truth: {batch['answers']}")
        print(f"correct: {(ans == batch['answers']).mean():.2f}  "
              f"reasoning tokens: total={n.sum()} per-q={n}")
        print(json.dumps(results.telemetry.summary()))
        return

    st = engine.start(jnp.asarray(batch["prompts"]), jnp.asarray(batch["prompt_len"]),
                      jax.random.PRNGKey(0))
    st = engine.reason(st)
    toks, _ = engine.force_answer(st, 4)
    ans = ChainTask.extract_answer(np.asarray(toks))
    n = np.asarray(st.n_reasoning)
    print(f"answers: {ans}  truth: {batch['answers']}")
    print(f"correct: {(ans == batch['answers']).mean():.2f}  "
          f"reasoning tokens: total={n.sum()} per-q={n}")
    print(f"exit via EAT: {np.asarray(st.monitor.stop_flag)}")


if __name__ == "__main__":
    main()
