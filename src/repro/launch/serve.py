"""Serving driver: request-streaming server (default) or lockstep batches.

The default path runs the :class:`repro.serving.ServingEngine`: requests
arrive on a Poisson clock, stream token deltas as they decode, share prompt
KV through the radix prefix cache, and (when a weight store is wired in)
keep decoding across live weight hot-swaps. ``--lockstep`` keeps the old
fixed-batch driver — requests grouped into one-shape batches through
``generate()`` — as the fallback for archs the streaming engine gates out
(SSM mixers, SWA rings, int8 KV, enc-dec) and as the goodput baseline
``benchmarks/serving.py`` measures against.

Usage:
  python -m repro.launch.serve --arch qwen2.5-7b --smoke --num-requests 16
  python -m repro.launch.serve --arch gemma-2b --smoke --lockstep --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ServingConfig, get_config, reduced
from repro.data.tokenizer import ByteTokenizer
from repro.launch.mesh import make_local_mesh
from repro.models import get_model
from repro.rl.rollout import generate
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.jax_compat import use_mesh


def run_lockstep(model, params, tok, args) -> None:
    """Fixed-shape batched serving. One untimed warmup batch absorbs the
    compile, then per-batch wall latencies feed a registry histogram for
    the p50/p99 report."""
    from repro.obs import MetricsRegistry

    cfg = model.cfg
    texts = [f"{i:02d}+{i + 1:02d}=" for i in range(args.batch)]
    prompt = jnp.asarray(np.stack([tok.encode(t) for t in texts]))
    kw = {}
    if cfg.is_encoder_decoder:
        kw["frames"] = jnp.zeros((args.batch, cfg.encoder_len, cfg.d_model),
                                 jnp.bfloat16)
    if cfg.num_prefix_embeds > 1:
        kw["prefix_embeds"] = jnp.zeros(
            (args.batch, cfg.num_prefix_embeds, cfg.d_model), jnp.bfloat16)

    def one_batch(r: int) -> int:
        key = jax.random.PRNGKey(args.seed + r + 1)
        res = generate(model, params, prompt, key, max_new=args.max_new,
                       temperature=args.temperature, eos_id=tok.eos_id, **kw)
        return int(jnp.sum(res.lengths)), res

    _, res = one_batch(-1)  # warmup: compile + first execution, untimed
    for text, row in zip(texts, np.asarray(res.tokens)):
        print(f"[serve] {text!r} -> {tok.decode(row[len(text):])!r}")

    served = 0
    hist = MetricsRegistry().histogram("serve/batch_latency_s")
    t0 = time.perf_counter()
    for r in range(args.requests):
        tb = time.perf_counter()
        n, _ = one_batch(r)
        hist.record(time.perf_counter() - tb)
        served += n
    dt = time.perf_counter() - t0
    p = hist.percentiles((50, 99))
    print(f"[serve] {served} tokens in {dt:.2f}s ({served / dt:.1f} tok/s, "
          f"compile excluded; batch latency p50 {p['p50'] * 1e3:.1f}ms "
          f"p99 {p['p99'] * 1e3:.1f}ms)")


def run_streaming(model, params, args):
    """Request-streaming serving over a synthetic Poisson arrival stream.
    Returns the finished streams; exits non-zero if any stream is left
    unfinished."""
    from repro.obs import MetricsRegistry
    from repro.serving import ServingEngine, synthetic_requests

    scfg = ServingConfig(
        num_slots=args.slots, max_len=args.max_len, max_new=args.max_new,
        page_size=args.page_size, prefix_cache=not args.no_prefix_cache,
        decode_burst=args.burst, yield_quota=args.yield_quota)
    eng = ServingEngine(model, scfg, params=params, eos_id=args.eos_id,
                        key=jax.random.PRNGKey(args.seed),
                        registry=MetricsRegistry())
    reqs = synthetic_requests(
        args.num_requests, arrival_rate=args.rate, page_size=args.page_size,
        max_new=args.max_new, temperature=args.temperature, seed=args.seed)
    # warmup: replay the identical workload once, untimed, so every
    # per-shape executable is compiled; then reset (cache cleared) and time
    warm = synthetic_requests(
        args.num_requests, arrival_rate=args.rate, page_size=args.page_size,
        max_new=args.max_new, temperature=args.temperature, seed=args.seed)
    for w in warm:
        w.rid -= args.num_requests
    eng.serve(warm, realtime=False)
    eng.reset_stats()
    eng.registry = MetricsRegistry()  # drop warmup latencies too

    streams = eng.serve(reqs, realtime=not args.no_realtime)
    st = eng.stats()
    ttft = eng.registry.histogram("serving/ttft_s").percentiles((50, 99))
    tpot = eng.registry.histogram("serving/tpot_s").percentiles((50, 99))
    print(f"[serve] {int(st['requests_finished'])} requests, "
          f"{int(st['tokens'])} tokens, "
          f"goodput {st['goodput_tokens_per_s']:.1f} tok/s")
    print(f"[serve] TTFT p50 {ttft['p50'] * 1e3:.1f}ms "
          f"p99 {ttft['p99'] * 1e3:.1f}ms | per-token p50 "
          f"{tpot['p50'] * 1e3:.1f}ms p99 {tpot['p99'] * 1e3:.1f}ms")
    print(f"[serve] prefix-cache hit rate {st['prefix_hit_rate']:.0%} "
          f"({int(st['prefix_hit_tokens'])} of {int(st['prompt_tokens'])} "
          f"prompt tokens), occupancy {st['slot_occupancy']:.0%}, "
          f"parks {int(st['parks'])}, pool pages {int(st['pool_pages_used'])}")
    unfinished = sum(not s.finished for s in streams)
    if unfinished:
        raise SystemExit(f"[serve] {unfinished} of {len(streams)} streams "
                         "unfinished")
    return streams


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--max-new", type=int, default=16)
    # streaming knobs
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--yield-quota", type=int, default=0)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--no-realtime", action="store_true",
                    help="enqueue all arrivals up front (max pressure)")
    # lockstep fallback knobs
    ap.add_argument("--lockstep", action="store_true",
                    help="fixed-batch fallback driver")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=3,
                    help="batches to serve (lockstep)")
    args = ap.parse_args(argv)
    args.eos_id = ByteTokenizer().eos_id
    return args


def main(argv=None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, vocab_size=260, num_layers=2)
    tok = ByteTokenizer()
    model = get_model(cfg)
    mesh = make_local_mesh()
    with use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(args.seed))
        if args.lockstep:
            run_lockstep(model, params, tok, args)
        else:
            run_streaming(model, params, args)


if __name__ == "__main__":
    main()
