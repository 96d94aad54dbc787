#!/usr/bin/env python
"""Benchmark: online serving latency/throughput (doc/serving.md).

Prints ONE JSON line per run so future PRs get a serving perf trajectory
next to the training BENCH_*.json ledger.  Two modes:

``predict`` (default) — the PR 2 fixed-shape path::

  {"metric": "serve_p99_latency_ms", "value": P99, "unit": "ms",
   "p50_ms": P50, "mean_ms": M, "requests_per_sec": R,
   "rows_per_sec": RW, "compile_count": C, "buckets": [...],
   "clients": N, "duration_sec": D}

``decode`` — the continuous-batching decode engine (serve/decode.py)::

  {"metric": "decode_tokens_per_sec", "value": TPS, "unit": "tokens/sec",
   "token_p50_ms": P50, "token_p99_ms": P99, "streams": N,
   "shed": {"expired": E, "pages": P, "rejected": R},
   "gen_cache": {"hit": H, "miss": M}, "slots": S, "pages": PG, ...}

``decode_matrix`` — the serve.dtype grid over ONE fixed seeded workload
(doc/serving.md "Quantized inference").  Every leg's streams are
twin-asserted in-bench against offline ``generate`` over that leg's own
stored tree (the BENCH_SCAN_r01 discipline: a receipt is only emitted
for outputs proven correct)::

  {"metric": "decode_int8_resident_reduction", "value": X, "unit": "x",
   "legs": [{"dtype": "f32|bf16|int8",
             "tokens_per_sec": T, "token_p50_ms": P50,
             "token_p99_ms": P99, "resident_bytes": B,
             "twin_checked": N}, ...], "model": {...}}

``prefix`` — prefix-share ON vs OFF at 90% shared-prefix traffic
(doc/serving.md "Prefix sharing"): prefill-amortized tokens/sec (wall
includes every prefill) + time-to-first-token per leg, every stream
twin-asserted.  ``spec`` — greedy speculative decoding legs (draft off /
cold small draft / self-speculation twin): tokens/sec + acceptance rate,
every stream twin-asserted token-equal.  ``prefix_spec`` — both in one
receipt (the BENCH_SERVE_r04 shape)::

  {"metric": "prefix_share_speedup", "value": X, "unit": "x",
   "prefix": {"on": {...}, "off": {...}}, "spec": {"legs": [...]}}

``kv_tiers`` — graftcache (doc/serving.md "Tiered KV cache"): a prefix
working set larger than the HBM page pool served via host/disk tiers vs
cold prefill over identical round-robin traffic, every stream in both
legs twin-asserted (the BENCH_KV_r01 shape)::

  {"metric": "kv_tier_speedup", "value": X, "unit": "x",
   "warm": {"tokens_per_sec": T, "streams": N, "twin_checked": N,
            "kv_promoted_pages": P, "kv": {"hits": H, "spills": S,
            "disk_promote_pages": D, ...}},
   "cold": {"tokens_per_sec": T, "streams": N, "twin_checked": N},
   "cache_pages": CP, "hbm_pages": HP}   # guard re-checks CP > HP

``sharded`` — graftshard (doc/serving.md "Sharded serving"): decode
tokens/sec at ``tp:1/2/4`` under a fixed per-device page budget (the
mesh scales pool capacity, so the slot count riding it scales too) +
the prefill-disaggregation A/B (``prefill_workers=0`` vs ``2`` with a
long prompt at the head of the queue; the metric is the short crowd's
time-to-first-token p99 — what the knob buys is admission past the
head-of-line blocker), every leg's streams twin-asserted against a
HOST copy of the leg's tree (the BENCH_SHARD_r01 shape)::

  {"metric": "decode_shard_scaling", "value": X, "unit": "x",
   "legs": [{"tp": N, "tokens_per_sec": T, "streams": S,
             "twin_checked": S, "resident_bytes_per_device": [...]},
            ...],
   "disagg": {"off": {...}, "on": {...}, "short_ttft_improvement": I},
   "twin_violations": 0}

Method: a tiny model (random init — serving cost is shape-bound, not
value-bound) behind the real engine + DynamicBatcher stack;
``--clients`` in-process threads submit mixed-size requests (seeded)
back-to-back for ``--duration`` seconds after a warmup.  Decode clients
send mixed prompt lengths with staggered arrivals; per-token latency is
the gap between consecutive emissions of one stream.

Backend: the run checks in this process that JAX is on a TPU and
otherwise exits non-zero with one JSON ``error`` line; a caller that pins
``JAX_PLATFORMS=cpu`` gets a correctness run stamped ``"platform":
"cpu"`` whose timings are not device numbers.
Env: CXXNET_SERVE_BENCH_* override the defaults below.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

from cxxnet_tpu.utils.backend import enable_compile_cache, require_chip

NET_CFG = """
netconfig=start
layer[+1] = fullc:fc1
  nhidden = 64
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 16
layer[+0] = softmax
netconfig=end
input_shape = 1,1,32
batch_size = 32
eta = 0.1
"""


def bench_predict(args) -> dict:
    from cxxnet_tpu import wrapper
    from cxxnet_tpu.serve import DynamicBatcher, PredictEngine
    from cxxnet_tpu.utils.bucketing import parse_buckets

    net = wrapper.Net(dev='', cfg=NET_CFG)
    net.set_param('inference_only', '1')
    net.init_model()
    buckets = parse_buckets(args.buckets)
    engine = PredictEngine(net._trainer, buckets)
    engine.warm()
    batcher = DynamicBatcher(engine, max_queue=4 * args.clients,
                             max_wait=args.max_wait, deadline=30.0)

    lat_ms = []
    rows_done = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client(cid: int) -> None:
        rng = np.random.RandomState(cid)
        while not stop.is_set():
            n = int(rng.randint(1, max(2, buckets[-1] // 2)))
            d = rng.randn(n, 1, 1, 32).astype(np.float32)
            t0 = time.monotonic()
            batcher.submit(d)
            dt = (time.monotonic() - t0) * 1e3
            with lock:
                lat_ms.append(dt)
                rows_done[0] += n

    threads = [threading.Thread(target=client, args=(cid,), daemon=True)
               for cid in range(args.clients)]
    warmup = min(0.5, args.duration / 4)
    for t in threads:
        t.start()
    time.sleep(warmup)
    with lock:          # measure steady state only
        lat_ms.clear()
        rows_done[0] = 0
    t_start = time.monotonic()
    time.sleep(args.duration)
    elapsed = time.monotonic() - t_start
    stop.set()
    for t in threads:
        t.join(10)
    batcher.close(timeout=10)

    arr = np.asarray(lat_ms)
    return {
        'metric': 'serve_p99_latency_ms',
        'value': round(float(np.quantile(arr, 0.99)), 4),
        'unit': 'ms',
        'p50_ms': round(float(np.quantile(arr, 0.5)), 4),
        'mean_ms': round(float(arr.mean()), 4),
        'requests_per_sec': round(arr.size / elapsed, 2),
        'rows_per_sec': round(rows_done[0] / elapsed, 2),
        'compile_count': engine.compile_count,
        'buckets': list(buckets),
        'clients': args.clients,
        'duration_sec': round(elapsed, 3),
        'platform': __import__('jax').default_backend(),
    }


def bench_decode(args) -> dict:
    """Continuous-batching decode: mixed prompt lengths, staggered
    arrivals, tokens/sec + per-token p50/p99 + shed counts."""
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.serve import ServeError
    from cxxnet_tpu.serve.decode import DecodeService

    cfg = T.TransformerConfig(vocab_size=256, d_model=64, num_heads=4,
                              d_ff=128, num_stages=2, seq_len=64,
                              attn='local')
    params = T.init_params(np.random.RandomState(0), cfg)
    svc = DecodeService(params, cfg, slots=args.slots, pages=args.pages,
                        page_size=args.page_size, max_prompt=32,
                        max_new_bound=args.max_new,
                        max_queue=4 * args.clients, deadline=60.0)
    stats = svc.engine.stats
    T.gen_cache_stats(reset=True)

    tok_gaps = []
    streams = [0]
    toks_done = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client(cid: int) -> None:
        rng = np.random.RandomState(1000 + cid)
        while not stop.is_set():
            s0 = int(rng.randint(1, 32))
            prompt = rng.randint(0, cfg.vocab_size, (1, s0)).astype(np.int32)
            try:
                req = svc.submit_async(prompt, args.max_new)
                svc.batcher.wait(req)
            except ServeError:
                continue           # shed: counted by the engine stats
            with lock:
                streams[0] += 1
                toks_done[0] += len(req.tokens)
                tt = req.token_times
                tok_gaps.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]))
            time.sleep(rng.uniform(0, 0.01))   # staggered arrivals

    threads = [threading.Thread(target=client, args=(cid,), daemon=True)
               for cid in range(args.clients)]
    for t in threads:
        t.start()
    time.sleep(min(1.0, args.duration / 3))    # warmup: compile + fill
    with lock:
        tok_gaps.clear()
        streams[0] = toks_done[0] = 0
    t_start = time.monotonic()
    time.sleep(args.duration)
    elapsed = time.monotonic() - t_start
    stop.set()
    for t in threads:
        t.join(30)
    svc.close(30)

    gaps = np.asarray(tok_gaps) if tok_gaps else np.asarray([float('nan')])
    gs = T.gen_cache_stats()
    return {
        'metric': 'decode_tokens_per_sec',
        'value': round(toks_done[0] / elapsed, 2),
        'unit': 'tokens/sec',
        'token_p50_ms': round(float(np.quantile(gaps, 0.5)), 4),
        'token_p99_ms': round(float(np.quantile(gaps, 0.99)), 4),
        'streams': streams[0],
        'streams_per_sec': round(streams[0] / elapsed, 2),
        'shed': {'expired': int(stats.get('expired')),
                 'pages': int(stats.get('shed_pages')),
                 'rejected': int(stats.get('rejected'))},
        'step_occupancy_p50': round(
            float(stats.quantile('step_occupancy', 0.5)), 3),
        # retrace visibility: the engine's own compiled programs (the
        # decode path never consults generate()'s cache; gen_cache is
        # here for surfaces that do — e.g. the CLI drive's twin check)
        'prefill_programs': int(stats.get('prefill_programs')),
        'gen_cache': {'hit': gs['hit'], 'miss': gs['miss']},
        'slots': args.slots, 'pages': args.pages,
        'page_size': args.page_size, 'max_new': args.max_new,
        'clients': args.clients,
        'duration_sec': round(elapsed, 3),
        'platform': __import__('jax').default_backend(),
    }


def bench_decode_matrix(args) -> dict:
    """The f32/bf16/int8 serving tiers,
    ONE fixed seeded workload per leg so tokens/sec, per-token quantiles
    and resident_bytes compare like for like.  Twin-asserted in-bench."""
    import jax
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.serve.decode import DecodeService

    # params-heavy model (vocab dominates): the int8 tier's >=3x
    # resident claim is about real serving models, not toy trees whose
    # KV pool drowns the weights
    cfg = T.TransformerConfig(vocab_size=8192, d_model=256, num_heads=8,
                              d_ff=512, num_stages=2, seq_len=64,
                              attn='local')
    params = T.init_params(np.random.RandomState(0), cfg)
    rng = np.random.RandomState(args.seed)
    n_req = args.requests
    prompts = [rng.randint(0, cfg.vocab_size,
                           (1, int(rng.randint(1, args.max_prompt))))
               .astype(np.int32) for _ in range(n_req)]

    def run_leg(dtype: str) -> dict:
        svc = DecodeService(
            params, cfg, slots=args.slots, pages=args.pages,
            page_size=args.page_size, max_prompt=args.max_prompt,
            max_new_bound=args.max_new, max_queue=2 * n_req,
            deadline=600.0, dtype=dtype)
        try:
            warm = svc.submit_async(prompts[0], args.max_new)
            svc.batcher.wait(warm)            # compile outside the clock
            t0 = time.monotonic()
            reqs = [svc.submit_async(p, args.max_new) for p in prompts]
            toks, gaps = 0, []
            for r in reqs:
                svc.batcher.wait(r)
                toks += len(r.tokens)
                tt = r.token_times
                gaps.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]))
            wall = time.monotonic() - t0
            # twin gate (BENCH_SCAN_r01 discipline): every tier's oracle
            # is generate() over the ENGINE's stored tree + compute cfg
            checked = 0
            for i in range(min(args.twin_checks, n_req)):
                off = np.asarray(T.generate(
                    svc.engine.params, prompts[i], args.max_new,
                    svc.engine.cfg))[0]
                got = np.asarray(reqs[i].result)
                assert (got == off[:len(got)]).all(), (
                    f'{dtype} stream {i} diverged from its '
                    f'offline twin')
                checked += 1
            def q(p):
                # null, not NaN, when a leg produced no inter-token gaps
                # (e.g. --max-new 1): the receipt is strict JSON
                if not gaps:
                    return None
                return round(float(np.quantile(np.asarray(gaps), p)), 4)

            return {
                'dtype': dtype,
                'tokens_per_sec': round(toks / wall, 2),
                'token_p50_ms': q(0.5),
                'token_p99_ms': q(0.99),
                'resident_bytes': int(svc.engine.resident_bytes()),
                'streams': n_req, 'twin_checked': checked,
                'wall_sec': round(wall, 3),
            }
        finally:
            svc.close(60)

    legs = [run_leg(dtype) for dtype in ('f32', 'bf16', 'int8')]
    by = {l['dtype']: l for l in legs}
    reduction = by['f32']['resident_bytes'] / by['int8']['resident_bytes']
    return {
        'metric': 'decode_int8_resident_reduction',
        'value': round(reduction, 2),
        'unit': 'x',
        'legs': legs,
        'model': {'vocab': cfg.vocab_size, 'd_model': cfg.d_model,
                  'heads': cfg.num_heads, 'd_ff': cfg.d_ff,
                  'stages': cfg.num_stages},
        'slots': args.slots, 'pages': args.pages,
        'page_size': args.page_size, 'max_new': args.max_new,
        'requests': n_req,
        'platform': jax.default_backend(),
    }


def _decode_model():
    """The shared decode-bench model (random init — serving cost is
    shape-bound, not value-bound)."""
    from cxxnet_tpu.models import transformer as T
    cfg = T.TransformerConfig(vocab_size=256, d_model=64, num_heads=4,
                              d_ff=128, num_stages=2, seq_len=64,
                              attn='local')
    return T.init_params(np.random.RandomState(0), cfg), cfg


def _drive_leg(svc, prompts, max_new, twin_all=True):
    """Submit every prompt, wait, twin-assert EVERY stream against its
    offline generate (BENCH_SCAN_r01 discipline: a receipt is only
    emitted for outputs proven correct).  Returns (tokens, wall_sec,
    ttft_ms list)."""
    from cxxnet_tpu.models import transformer as T
    t0 = time.monotonic()
    reqs = [svc.submit_async(p, max_new) for p in prompts]
    toks, ttft = 0, []
    for r in reqs:
        svc.batcher.wait(r)
        toks += len(r.tokens)
        ttft.append((r.token_times[0] - r.t_submit) * 1e3)
    wall = time.monotonic() - t0
    checked = 0
    # sharded engines oracle against a HOST copy of the params — the
    # offline reference must never itself compile SPMD
    oracle = getattr(svc.engine, 'oracle_params',
                     lambda: svc.engine.params)()
    for p, r in zip(prompts, reqs):
        off = np.asarray(T.generate(oracle, p, max_new,
                                    svc.engine.cfg))[0]
        got = np.asarray(r.result)
        assert (got == off[:len(got)]).all(), (
            f'stream {checked} diverged from its offline twin')
        checked += 1
        if not twin_all and checked >= 3:
            break
    return toks, wall, ttft, checked


def bench_prefix(args) -> dict:
    """Prefix-share ON vs OFF over identical 90%-shared traffic:
    prefill-amortized tokens/sec (the wall clock includes every
    prefill) and time-to-first-token, every stream twin-asserted.

    The workload is the shape the amortization thesis targets: a long
    PAGE-ALIGNED system prefix (31 of 32 pages) + a one-page unique
    tail per request, short generations — sharing requires the same
    prompt bucket and pad width (doc/serving.md "Prefix sharing"), so
    90% of requests splice 31 pages and prefill one."""
    import jax
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.serve.decode import DecodeService

    cfg = T.TransformerConfig(vocab_size=512, d_model=128, num_heads=8,
                              d_ff=512, num_stages=2, seq_len=512,
                              attn='local')
    params = T.init_params(np.random.RandomState(0), cfg)
    ps = args.page_size
    plen = 31 * ps
    total = plen + ps
    max_new = int(os.environ.get('CXXNET_SERVE_BENCH_PREFIX_MAX_NEW', 2))
    pages = max(args.pages, 384)
    rng = np.random.RandomState(args.seed)
    prefix = rng.randint(0, cfg.vocab_size, (1, plen)).astype(np.int32)
    prompts = []
    for i in range(args.requests):
        if i % 10 == 9:                            # the 10% cold minority
            prompts.append(rng.randint(0, cfg.vocab_size,
                                       (1, total)).astype(np.int32))
        else:
            tail = rng.randint(0, cfg.vocab_size, (1, ps)).astype(np.int32)
            prompts.append(np.concatenate([prefix, tail], axis=1))

    def leg(share: bool) -> dict:
        svc = DecodeService(
            params, cfg, slots=args.slots, pages=pages,
            page_size=ps, max_prompt=total,
            max_new_bound=max_new, max_queue=2 * args.requests,
            deadline=600.0, prefix_share=pages // 2 if share else 0)
        try:
            # warmup outside the clock: compiles prefill + tail-prefill
            # + the step (and, with sharing on, publishes the prefix —
            # the pay-once half of the amortization thesis)
            for p in prompts[:2]:
                svc.batcher.wait(svc.submit_async(p, max_new))
            toks, wall, ttft, checked = _drive_leg(svc, prompts, max_new)
            st = svc.engine.stats
            return {
                'prefix_share': bool(share),
                'tokens_per_sec': round(toks / wall, 2),
                'ttft_p50_ms': round(float(np.quantile(ttft, 0.5)), 3),
                'ttft_p99_ms': round(float(np.quantile(ttft, 0.99)), 3),
                'wall_sec': round(wall, 3),
                'streams': len(prompts), 'twin_checked': checked,
                'prefix_hits': int(st.get('prefix_hits')),
                'prefix_misses': int(st.get('prefix_misses')),
                'cow_copies': int(st.get('cow_copies')),
                'shared_page_splices': int(st.get('prefix_hit_pages')),
                'free_pages_min': int(svc.engine._free_min),
            }
        finally:
            svc.close(60)

    on, off = leg(True), leg(False)
    return {
        'metric': 'prefix_share_speedup',
        'value': round(on['tokens_per_sec'] / off['tokens_per_sec'], 2),
        'unit': 'x',
        'on': on, 'off': off,
        'shared_fraction': 0.9, 'prefix_pages': 31,
        'prompt_tokens': total,
        'model': {'vocab': cfg.vocab_size, 'd_model': cfg.d_model,
                  'heads': cfg.num_heads, 'd_ff': cfg.d_ff,
                  'stages': cfg.num_stages},
        'requests': args.requests, 'max_new': max_new,
        'page_size': ps, 'slots': args.slots,
        'platform': jax.default_backend(),
    }


def bench_spec(args) -> dict:
    """Greedy speculative decoding: draft-off baseline vs a cold small
    draft vs the self-speculation twin draft (acceptance upper bound),
    one seeded workload, every stream twin-asserted token-equal."""
    import jax
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.serve.decode import DecodeService

    params, cfg = _decode_model()
    dcfg = T.TransformerConfig(vocab_size=cfg.vocab_size, d_model=16,
                               num_heads=2, d_ff=32, num_stages=1,
                               seq_len=cfg.seq_len, attn='local')
    dparams = T.init_params(np.random.RandomState(1), dcfg)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (1, int(rng.randint(2, args.max_prompt))))
               .astype(np.int32) for _ in range(args.requests)]

    def leg(name: str, draft, spec_k: int) -> dict:
        svc = DecodeService(
            params, cfg, slots=args.slots, pages=args.pages,
            page_size=args.page_size, max_prompt=args.max_prompt,
            max_new_bound=args.max_new, max_queue=2 * args.requests,
            deadline=600.0, spec_k=spec_k, draft=draft)
        try:
            svc.batcher.wait(svc.submit_async(prompts[0], args.max_new))
            toks, wall, _, checked = _drive_leg(svc, prompts,
                                                args.max_new)
            st = svc.engine.stats
            proposed = st.get('spec_proposed')
            return {
                'draft': name,
                'tokens_per_sec': round(toks / wall, 2),
                'wall_sec': round(wall, 3),
                'streams': len(prompts), 'twin_checked': checked,
                'spec_k': spec_k,
                'spec_proposed': int(proposed),
                'spec_accepted': int(st.get('spec_accepted')),
                'acceptance_rate': round(
                    st.get('spec_accepted') / proposed, 3)
                if proposed else None,
                'decode_steps': int(st.get('decode_steps')),
            }
        finally:
            svc.close(60)

    legs = [leg('off', None, 0),
            leg('small', (dparams, dcfg), args.spec_k),
            leg('twin', (params, cfg), args.spec_k)]
    base = legs[0]['tokens_per_sec']
    best = max(legs[1:], key=lambda leg_: leg_['tokens_per_sec'])
    out = {
        'metric': 'spec_decode_speedup',
        'value': round(best['tokens_per_sec'] / base, 2),
        'unit': 'x',
        'best_draft': best['draft'],
        'legs': legs,
        'requests': args.requests, 'max_new': args.max_new,
        'spec_k': args.spec_k, 'slots': args.slots,
        'platform': jax.default_backend(),
    }
    if out['platform'] == 'cpu':
        # random-init models make any CHEAPER draft disagree with the
        # target (acceptance ~0), and on compute-bound CPU the verify
        # window saves no arithmetic: cpu legs prove token-equality
        # and report acceptance; the speed claim is the on-chip one
        # (one K-window pass costs ~one step of HBM weight traffic)
        out['note'] = ('cpu legs prove correctness + acceptance '
                       'accounting, not speed; see doc/benchmarks.md')
    return out


def bench_prefix_spec(args) -> dict:
    """The BENCH_SERVE_r04 receipt: both multipliers over one config —
    the prefix-share A/B (headline) plus the spec-decode legs."""
    prefix = bench_prefix(args)
    spec = bench_spec(args)
    return {
        'metric': 'prefix_share_speedup',
        'value': prefix['value'],
        'unit': 'x',
        'prefix': prefix,
        'spec': spec,
        'platform': prefix['platform'],
    }


def bench_kv_tiers(args) -> dict:
    """graftcache: a prefix working set LARGER than the HBM page pool
    served through the host/disk tiers vs cold prefill (doc/serving.md
    "Tiered KV cache").

    The workload is N distinct long page-aligned prefixes (each 31
    pages) + one-page unique tails, all prompts exactly one 512-token
    size class (sharing requires the same prompt bucket and pad
    width).  The pool
    is capped TIGHT — the full prefix working set cannot fit in HBM —
    and the index cap holds barely one prefix, so round-robin traffic
    forces the demote -> spill -> prefetch -> promote cycle on nearly
    every arrival instead of riding tier-0 index hits.  The COLD leg
    serves the identical scored traffic with no cache at all (pure
    prefill — the re-prefill cost a promote avoids).  Every stream in
    BOTH legs is twin-asserted in-bench against offline ``generate``
    (the BENCH_SCAN_r01 discipline), and the receipt carries the
    cache-vs-HBM page accounting the guard re-checks."""
    import shutil
    import tempfile

    import jax
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.serve.decode import DecodeService

    # a fat MLP (d_ff 16x d_model): prefill FLOPs per token dwarf the
    # promote path's per-token record bytes, which is exactly the regime
    # the tier thesis targets — repaying cached K/V beats recomputing it
    cfg = T.TransformerConfig(vocab_size=512, d_model=128, num_heads=8,
                              d_ff=2048, num_stages=2, seq_len=1024,
                              attn='local')
    params = T.init_params(np.random.RandomState(0), cfg)
    ps = args.page_size
    prefix_pages = 31
    plen = prefix_pages * ps
    total = plen + ps        # 512 — exactly one prompt size class (w=0)
    max_new = int(os.environ.get('CXXNET_SERVE_BENCH_KV_MAX_NEW', 2))
    n_prefixes = int(os.environ.get('CXXNET_SERVE_BENCH_KV_PREFIXES', 6))
    # tight HBM: barely one stream + one indexed prefix; the cached
    # working set (n_prefixes * prefix_pages pages) cannot fit
    pages = 48
    slots = 2
    # publish covers prefix AND tail page (total // ps pages), so the
    # cap needs one page of slack past that to accept a whole prompt
    share_cap = prefix_pages + 2
    rng = np.random.RandomState(args.seed)
    prefixes = [rng.randint(0, cfg.vocab_size, (1, plen)).astype(np.int32)
                for _ in range(n_prefixes)]

    def tailed(pfx):
        tail = rng.randint(0, cfg.vocab_size, (1, ps)).astype(np.int32)
        return np.concatenate([pfx, tail], axis=1)

    prime = [tailed(p) for p in prefixes]
    # scored: four visits per prefix, round-robin — consecutive
    # arrivals never share a prefix, so the one-prefix index cap forces
    # a promote (not a tier-0 hit) on nearly every request; enough
    # streams that per-arrival scheduling noise averages out of the
    # ratio
    scored = [tailed(prefixes[i % n_prefixes])
              for i in range(4 * n_prefixes)]

    def drive_serial(svc, prompts, reps=3):
        """Pipelined submit, in-order wait: the admit thread drains the
        queue FIFO (round-robin prefix order — the tier churn — is
        preserved), but the next admission overlaps the previous
        stream's decode instead of paying a submit->admit handoff per
        request.  The pass repeats ``reps`` times and the BEST wall
        scores (the tier state is cyclic — every pass promotes the same
        chains — so min-of-N removes scheduler noise, not work).  Every
        stream twin-asserted."""
        walls = []
        for _ in range(reps):
            t0 = time.monotonic()
            reqs = [svc.submit_async(p, max_new) for p in prompts]
            for r in reqs:
                svc.batcher.wait(r)
            walls.append(time.monotonic() - t0)
        toks = sum(len(r.tokens) for r in reqs)
        wall = min(walls)
        checked = 0
        for p, r in zip(prompts, reqs):
            off = np.asarray(T.generate(svc.engine.params, p, max_new,
                                        svc.engine.cfg))[0]
            got = np.asarray(r.result)
            assert (got == off[:len(got)]).all(), (
                f'stream {checked} diverged from its offline twin')
            checked += 1
        return toks, wall, checked

    kv_root = tempfile.mkdtemp(prefix='cxxnet-bench-kv-')
    try:
        warm_svc = DecodeService(
            params, cfg, slots=slots, pages=pages, page_size=ps,
            max_prompt=total, max_new_bound=max_new,
            max_queue=4 * len(scored), deadline=600.0,
            prefix_share=share_cap, kv_host_mb=4, kv_disk_mb=64,
            kv_dir=os.path.join(kv_root, 'records'))
        try:
            eng = warm_svc.engine
            # priming pass: prefill each prefix once; the one-prefix
            # index cap demotes every earlier prefix down-tier (host
            # overflows to disk records)
            for p in prime:
                warm_svc.batcher.wait(warm_svc.submit_async(p, max_new))
            assert eng._kv.flush(60.0), 'spill queue never drained'
            # warmup outside the clock: TWO concurrent promote-shaped
            # arrivals compile the tail prefill, the batched upload
            # scatter AND the occupancy-2 step program (prime arrivals
            # were serial full-prefill misses, so all of those are
            # still cold — a first compile inside the clock would be
            # the artifact, not the tiers).  prefixes[0]/[1] — the
            # COLDEST prefixes, disk-only by now — so the warmup walks
            # the full disk -> host -> HBM promote path, not a tier-0
            # index hit that would leave those programs uncompiled
            wreqs = [warm_svc.submit_async(tailed(prefixes[i]), max_new)
                     for i in range(2)]
            for r in wreqs:
                warm_svc.batcher.wait(r)
            toks, wall, checked = drive_serial(warm_svc, scored)
            eng.kv_occupancy()               # fold tier gauges
            ks = eng.kv_stats
            cache_bytes = int(ks.get('host_bytes') + ks.get('disk_bytes'))
            pool_bytes = int(eng._kpool.nbytes + eng._vpool.nbytes)
            page_bytes = pool_bytes // eng.n_pages   # K+V, all stages
            cache_pages = cache_bytes // page_bytes
            warm = {
                'tokens_per_sec': round(toks / wall, 2),
                'wall_sec': round(wall, 3),
                'streams': len(scored), 'twin_checked': checked,
                'kv_promoted_pages': int(
                    eng.stats.get('kv_promoted_pages')),
                'kv_uploads': int(eng.stats.get('kv_uploads')),
                'prefix_hits': int(eng.stats.get('prefix_hits')),
                'kv': {k: int(ks.get(k)) for k in
                       ('hits', 'misses', 'demote_pages',
                        'promote_pages', 'disk_promote_pages', 'spills',
                        'host_bytes', 'disk_bytes',
                        'corrupt_quarantined')},
                'promote_ms_p50': round(ks.quantile('promote_ms', 0.5),
                                        3),
                'promote_ms_p99': round(ks.quantile('promote_ms', 0.99),
                                        3),
            }
        finally:
            warm_svc.close(60)

        cold_svc = DecodeService(
            params, cfg, slots=slots, pages=pages, page_size=ps,
            max_prompt=total, max_new_bound=max_new,
            max_queue=4 * len(scored), deadline=600.0, prefix_share=0)
        try:
            # warmup compiles only (two concurrent throwaway streams —
            # the occupancy-2 step program must be warm here too)
            creqs = [cold_svc.submit_async(prime[i], max_new)
                     for i in range(2)]
            for r in creqs:
                cold_svc.batcher.wait(r)
            ctoks, cwall, cchecked = drive_serial(cold_svc, scored)
            cold = {
                'tokens_per_sec': round(ctoks / cwall, 2),
                'wall_sec': round(cwall, 3),
                'streams': len(scored), 'twin_checked': cchecked,
            }
        finally:
            cold_svc.close(60)
    finally:
        shutil.rmtree(kv_root, ignore_errors=True)

    hbm_pages = pages - 1                    # page 0 is scratch
    assert cache_pages > hbm_pages, (
        f'the tiered cache holds {cache_pages} pages — not larger than '
        f'the {hbm_pages}-page HBM pool; the bench proves nothing')
    assert warm['kv_promoted_pages'] > 0 and \
        warm['kv']['disk_promote_pages'] > 0, (
        'warm leg never promoted through the tiers')
    return {
        'metric': 'kv_tier_speedup',
        'value': round(warm['tokens_per_sec'] / cold['tokens_per_sec'],
                       2),
        'unit': 'x',
        'warm': warm, 'cold': cold,
        'cache_pages': int(cache_pages), 'hbm_pages': int(hbm_pages),
        'cache_bytes': cache_bytes, 'pool_bytes': pool_bytes,
        'prefixes': n_prefixes, 'prefix_pages': prefix_pages,
        'prompt_tokens': total, 'page_size': ps, 'slots': slots,
        'reps': 3, 'kv_host_mb': 4, 'kv_disk_mb': 64,
        'max_new': max_new,
        'model': {'vocab': cfg.vocab_size, 'd_model': cfg.d_model,
                  'heads': cfg.num_heads, 'd_ff': cfg.d_ff,
                  'stages': cfg.num_stages},
        'platform': jax.default_backend(),
    }


def bench_scenarios(args) -> dict:
    """graftstorm: adversarial traffic scenarios scored static vs
    autoscale-on (doc/serving.md "Scenarios and autoscaling").

    ONE physical engine serves every leg (the compiled step, params and
    page pool are identical); the STATIC leg pins the live admission
    caps at a tight baseline, the AUTOSCALE leg starts at the same
    baseline and lets the SLO-driven autoscaler grow toward the
    physical ceiling under queue-pressure verdicts.  Same seeded storm
    both legs, so the delta is the autoscaler and nothing else.  Every
    leg's served streams are twin-asserted against offline ``generate``
    (the BENCH_SCAN_r01 discipline), and the ledger must reconcile
    exactly against the service counters — a shed percentage here
    cannot be a silently-dropped request.  The last leg composes a
    ``slow_step@every`` FaultPlan with a flash crowd in one run: zero
    twin violations, typed sheds only."""
    import jax
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.runtime import faults
    from cxxnet_tpu.serve.autoscale import AutoscalePolicy, Autoscaler
    from cxxnet_tpu.serve.decode import DecodeService
    from cxxnet_tpu.serve.scenario import ScenarioLedger, ScenarioSpec, drive

    params, cfg = _decode_model()
    svc = DecodeService(params, cfg, slots=args.slots, pages=args.pages,
                        page_size=8, max_prompt=24, max_new_bound=8,
                        eos_id=None, max_queue=32,
                        max_wait=args.max_wait, deadline=8.0)
    eng = svc.engine
    tight = {'max_slots': 1, 'max_pages': 6}
    # hysteresis=3 + cooldown=0.05 damp trough-shrinking under periodic
    # (diurnal) load — with faster shrink the knobs sag in every trough
    # and the next peak lands on shrunk capacity
    policy = AutoscalePolicy.parse(
        'min_slots=1;min_pages=2;min_queue=4;'
        'cooldown=0.05;hysteresis=3;step=2')

    def verdicts():
        # queue-pressure verdict, the SLO engine's stand-in: the bench
        # must stay deterministic-ish and self-contained, and the hub
        # path is proven by pytest -m scenario.  BREACHED means the
        # queue is about to overflow (28 of 32) — classing a drainable
        # burst as BREACHED trips the degrade rung and mass-sheds
        depth = svc.batcher.depth()
        cv = eng.capacity_view()
        if depth >= 28:
            state = 'BREACHED'
        elif depth >= 2 or cv['occupied'] >= cv['live_slot_cap']:
            state = 'AT_RISK'
        else:
            state = 'OK'
        return {'queue': {'state': state}}

    scenarios = [
        ('steady', 'shape=steady;seed=101;requests=60;qps=400;'
                   'max_prompt=16;max_new=8'),
        ('flash', 'shape=flash;seed=102;requests=64;qps=300;burst=16;'
                  'max_prompt=16;max_new=8'),
        ('heavy_tail', 'shape=heavy_tail;seed=103;requests=60;qps=400;'
                       'tail=1.1;max_prompt=24;max_new=8'),
        ('diurnal_abandon', 'shape=diurnal;seed=104;requests=60;qps=400;'
                            'abandon=0.35;patience=0.04;'
                            'max_prompt=16;max_new=8'),
    ]

    def twin_check(spec, led):
        sched = spec.schedule()
        for idx, stream in led.streams.items():
            prompt = spec.prompt_for(idx, sched[idx].prompt_len,
                                     cfg.vocab_size)
            off = np.asarray(T.generate(eng.params, prompt,
                                        sched[idx].max_new, eng.cfg))[0]
            got = np.asarray(stream)
            assert (got == off[:len(got)]).all(), \
                f'stream {idx} diverged from its offline twin'
        return len(led.streams)

    def run_leg(spec, autoscale):
        eng.set_live_limits(**tight)
        svc.batcher.set_max_queue(32)
        scaler, on_tick = None, None
        if autoscale:
            scaler = Autoscaler(policy, verdicts=verdicts,
                                gauges=lambda: {})
            scaler.bind_engine(eng)      # tight caps ARE the baseline
            scaler.bind_batcher(svc.batcher)
            on_tick = lambda _t: scaler.evaluate()
        base = ScenarioLedger.stat_snapshot(eng.stats)
        t0 = time.monotonic()
        led = drive(svc, spec, vocab=cfg.vocab_size, on_tick=on_tick)
        wall = time.monotonic() - t0
        led.reconcile(eng.stats, base=base)
        checked = twin_check(spec, led)
        s = led.summary()
        row = {
            'served': s['served'], 'shed': led.shed(),
            'abandoned': s['abandoned'],
            'loss': led.shed() + s['abandoned'],
            'p50_ms': None if s['p50_s'] is None else s['p50_s'] * 1e3,
            'p99_ms': None if s['p99_s'] is None else s['p99_s'] * 1e3,
            'wall_sec': wall, 'twin_checked': checked,
        }
        if scaler is not None:
            hist = scaler.history()
            row['actions'] = len(hist)
            row['degraded'] = scaler.degraded
            # sustained OK drifts knobs back to baseline, so final caps
            # alone hide the storm response — record the peak too
            row['peak_slots'] = max(
                [a['to'] for a in hist if a['knob'] == 'slots'],
                default=tight['max_slots'])
            row['peak_pages'] = max(
                [a['to'] for a in hist if a['knob'] == 'pages'],
                default=tight['max_pages'])
            row['final_caps'] = list(eng.live_limits())
            scaler.close()
        return row

    def warm(spec):
        # an unscored throwaway drive at physical caps: pre-pays the
        # per-prompt-length XLA compiles AND first-use batcher-path
        # state so the FIRST scored leg isn't charged costs the second
        # leg then gets for free (A/B fairness — serial ``generate``
        # warmup demonstrably does not cover the submit_async path)
        eng.set_live_limits(max_slots=args.slots,
                            max_pages=args.pages - 1)
        drive(svc, spec, vocab=cfg.vocab_size)

    rows, wins = [], 0
    try:
        for name, spec_text in scenarios:
            spec = ScenarioSpec.parse(spec_text)
            warm(spec)
            static = run_leg(spec, autoscale=False)
            scaled = run_leg(spec, autoscale=True)
            # the autoscaler wins a scenario by losing strictly fewer
            # requests (typed sheds + client abandons), or losing the
            # same with p99 no worse than 110% of static
            if scaled['loss'] < static['loss']:
                win = True
            elif scaled['loss'] == static['loss']:
                sp, tp = scaled['p99_ms'], static['p99_ms']
                win = sp is not None and tp is not None and sp <= tp * 1.1
            else:
                win = False
            wins += bool(win)
            rows.append({'name': name, 'spec': spec.describe(),
                         'static': static, 'autoscale': scaled,
                         'win': bool(win)})

        # the composed chaos drill: slow_step@every faults + flash crowd
        # + autoscaler in ONE run — zero twin violations, typed-only sheds
        plan = faults.FaultPlan.parse('seed=1;slow_step@every=4:0.004')
        chaos_spec = ScenarioSpec.parse(
            'shape=flash;seed=105;requests=32;qps=120;burst=8;'
            'max_prompt=16;max_new=6')
        warm(chaos_spec)
        eng.set_live_limits(**tight)
        scaler = Autoscaler(policy, verdicts=verdicts, gauges=lambda: {})
        scaler.bind_engine(eng)
        scaler.bind_batcher(svc.batcher)
        base = ScenarioLedger.stat_snapshot(eng.stats)
        prev = faults.install_plan(plan)
        try:
            led = drive(svc, chaos_spec, vocab=cfg.vocab_size,
                        on_tick=lambda _t: scaler.evaluate())
        finally:
            faults.install_plan(prev)
        led.reconcile(eng.stats, base=base)
        checked = twin_check(chaos_spec, led)
        fired = [t for t in plan.fired() if t.startswith('slow_step')]
        assert fired, 'the chaos plan never fired'
        # typed-only: engine_errors is the one bucket that could hide an
        # untyped failure; reconcile already proved nothing fell outside
        assert led.counts['engine_errors'] == 0, led.summary()
        s = led.summary()
        chaos = {'spec': chaos_spec.describe(),
                 'fault_plan': plan.describe(),
                 'slow_steps_fired': len(fired),
                 'twin_checked': checked, 'twin_violations': 0,
                 'untyped_sheds': 0, **s}
        for k in ('p50_s', 'p99_s'):
            v = chaos.pop(k)
            chaos[k.replace('_s', '_ms')] = None if v is None else v * 1e3
        scaler.close()
    finally:
        svc.close(30.0)

    return {
        'metric': 'scenario_autoscale_wins', 'value': wins,
        'unit': 'scenarios', 'total_scenarios': len(rows),
        'policy': policy.describe(),
        'tight_caps': tight, 'scenarios': rows, 'chaos': chaos,
        'engine': {'slots': args.slots, 'pages': args.pages,
                   'vocab': cfg.vocab_size, 'd_model': cfg.d_model},
        'platform': jax.default_backend(),
    }


def bench_sharded(args) -> dict:
    """graftshard ledger (doc/serving.md "Sharded serving"): decode
    tokens/sec at tp:1/2/4 under a FIXED PER-DEVICE page budget — the
    mesh is a capacity lever: the pool (and the slot count feeding it)
    scales with the shard width while each device's slice stays one
    chip's share, so at tp:1 a crowd round-robining over shared prompt
    stems thrashes the prefix index (full stem prefill per stream)
    while the tp:4 pool keeps every stem resident (page splices) —
    plus the prefill-disaggregation A/B (``prefill_workers=0`` vs
    ``2``) reading the short crowd's TTFT p99 past a long head-of-line
    prompt.  Every leg's streams twin-asserted in-bench against
    offline ``generate`` over a host copy of the leg's own tree."""
    import jax
    from cxxnet_tpu.serve.decode import DecodeService

    ndev = len(jax.devices())
    widths = [tp for tp in (1, 2, 4) if tp <= ndev]
    from cxxnet_tpu.models import transformer as T
    # a wider body than the shared decode-bench model: the quantity
    # under test is AVOIDED stem-prefill compute, so the stem prefill
    # must dwarf per-call dispatch overhead or the ledger reads noise
    cfg = T.TransformerConfig(vocab_size=256, d_model=256, num_heads=4,
                              d_ff=1024, num_stages=2, seq_len=64,
                              attn='local')
    params = T.init_params(np.random.RandomState(1), cfg)
    ps = args.page_size
    max_new = int(os.environ.get('CXXNET_SERVE_BENCH_SHARD_MAX_NEW', 8))
    rng = np.random.RandomState(args.seed)
    # Residency workload: the crowd round-robins over a few long shared
    # prompt stems.  The per-device page budget is ONE stream's worth,
    # so the tp:1 pool cannot keep a stem's prefix pages resident past
    # the next stem's admission (reclaim evicts them) and every stream
    # pays the full stem prefill again; the tp:4 pool holds every stem
    # and streams splice cached pages instead — HBM capacity scaling
    # the mesh buys, read out as aggregate tokens/sec.
    stem_len = 60 * ps                     # prefills the 1024 bucket
    n_stems = int(os.environ.get('CXXNET_SERVE_BENCH_SHARD_STEMS', 3))
    reps = 8
    stems = [rng.randint(0, cfg.vocab_size,
                         (1, stem_len)).astype(np.int32)
             for _ in range(n_stems)]
    prompts = [stems[i % n_stems] for i in range(n_stems * reps)]
    s0b = T._size_class(stem_len, floor=8)
    # exactly one stream's pages per device: prompt pages + decode tail
    pages_per_dev = int(os.environ.get(
        'CXXNET_SERVE_BENCH_SHARD_PAGES',
        (s0b + max_new - 2) // ps + 1))

    legs = []
    violations = 0
    for tp in widths:
        svc = DecodeService(
            params, cfg, slots=2 * tp, pages=1 + pages_per_dev * tp,
            page_size=ps, max_prompt=stem_len, max_new_bound=max_new,
            max_queue=4 * len(prompts), deadline=600.0,
            prefix_share=n_stems * (s0b // ps),
            shard='' if tp == 1 else f'tp:{tp}')
        try:
            for p in stems:        # warmup: compile + publish off-clock
                svc.batcher.wait(svc.submit_async(p, max_new))
            toks, wall, _, checked = _drive_leg(svc, prompts, max_new)
            hits = svc.engine.stats.get('prefix_hits')
            misses = svc.engine.stats.get('prefix_misses')
            hitp = svc.engine.stats.get('prefix_hit_pages')
            legs.append({
                'tp': tp, 'slots': 2 * tp,
                'pages': 1 + pages_per_dev * tp,
                'tokens_per_sec': round(toks / wall, 2),
                'wall_sec': round(wall, 3),
                'prefix_hits': int(hits), 'prefix_misses': int(misses),
                'prefix_hit_pages': int(hitp),
                'streams': len(prompts), 'twin_checked': checked,
                'resident_bytes_per_device':
                    [int(b) for b in svc.engine.resident_bytes_per_device()],
            })
        except AssertionError:
            violations += 1
            raise
        finally:
            svc.close(60)

    # --- prefill disaggregation A/B: a LONG prompt at the head of the
    # admission queue must not block the short streams behind it.  With
    # workers=0, admission runs serially on the batcher worker, so
    # every short waits out the long prefill; with workers=2, one
    # worker chews the long prompt while the other drains the shorts —
    # their time-to-first-token is the head-of-line claim.
    long_len = 60 * ps                     # the same 1024-bucket weight
    d_max_new = 24                         # longs: slot-holding streams
    n_short = 12
    longs = [rng.randint(0, cfg.vocab_size,
                         (1, long_len)).astype(np.int32)
             for _ in range(3)]
    shorts = [rng.randint(0, cfg.vocab_size,
                          (1, int(rng.randint(1, 8)))).astype(np.int32)
              for _ in range(n_short)]
    # longs INTERLEAVED with the short crowd: with workers=0 every
    # mid-queue long prefill blocks all shorts behind it (serial
    # admission), with workers=2 the second worker keeps draining
    # shorts through it — the short crowd's TTFT p99 is the claim
    order = ([(longs[0], False)]
             + [(s, True) for s in shorts[:n_short // 2]]
             + [(longs[1], False)]
             + [(s, True) for s in shorts[n_short // 2:]]
             + [(longs[2], False)])
    dcfg_prompts = [p for p, _ in order]
    short_idx = {i for i, (_, sh) in enumerate(order) if sh}
    short_new = 4                          # shorts: TTFT-bound streams

    def disagg_leg(workers: int) -> dict:
        svc = DecodeService(
            params, cfg, slots=6, pages=256, page_size=ps,
            max_prompt=long_len, max_new_bound=d_max_new,
            max_queue=64, deadline=600.0, prefill_workers=workers)
        try:
            # warmup compiles BOTH prompt buckets off the clock
            svc.batcher.wait(svc.submit_async(longs[0], 2))
            svc.batcher.wait(svc.submit_async(shorts[0], 2))
            t0 = time.monotonic()
            reqs = [svc.submit_async(
                p, short_new if i in short_idx else d_max_new)
                for i, p in enumerate(dcfg_prompts)]
            ttft = []
            for i, r in enumerate(reqs):
                svc.batcher.wait(r)
                if i in short_idx:
                    ttft.append((r.token_times[0] - r.t_submit) * 1e3)
            wall = time.monotonic() - t0
            toks = sum(len(r.tokens) for r in reqs)
            from cxxnet_tpu.models import transformer as T
            checked = 0
            for i, (p, r) in enumerate(zip(dcfg_prompts, reqs)):
                mn = short_new if i in short_idx else d_max_new
                off = np.asarray(T.generate(params, p, mn, cfg))[0]
                got = np.asarray(r.result)
                assert (got == off[:len(got)]).all(), \
                    'disagg stream diverged from its offline twin'
                checked += 1
            tt = np.asarray(ttft)
            return {
                'prefill_workers': workers,
                'tokens_per_sec': round(toks / wall, 2),
                'short_ttft_p50_ms': round(float(np.quantile(tt, 0.5)), 3),
                'short_ttft_p99_ms': round(float(np.quantile(tt, 0.99)), 3),
                'streams': len(dcfg_prompts), 'twin_checked': checked,
            }
        finally:
            svc.close(60)

    d_off, d_on = disagg_leg(0), disagg_leg(2)
    tp1 = legs[0]['tokens_per_sec']
    tpN = legs[-1]['tokens_per_sec']
    return {
        'metric': 'decode_shard_scaling',
        'value': round(tpN / tp1, 2),
        'unit': 'x',
        'legs': legs,
        'pages_per_device': pages_per_dev,
        'disagg': {
            'off': d_off, 'on': d_on,
            'short_ttft_improvement': round(
                d_off['short_ttft_p99_ms']
                / max(d_on['short_ttft_p99_ms'], 1e-9), 2),
        },
        'twin_violations': violations,
        'max_new': max_new, 'page_size': ps,
        'devices': ndev,
        'model': {'vocab': cfg.vocab_size, 'd_model': cfg.d_model,
                  'heads': cfg.num_heads, 'd_ff': cfg.d_ff,
                  'stages': cfg.num_stages},
        'platform': jax.default_backend(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('mode', nargs='?', default='predict',
                    choices=('predict', 'decode', 'decode_matrix',
                             'prefix', 'spec', 'prefix_spec',
                             'scenarios', 'kv_tiers', 'sharded'))
    ap.add_argument('--clients', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_CLIENTS', 8)))
    ap.add_argument('--duration', type=float, default=float(
        os.environ.get('CXXNET_SERVE_BENCH_DURATION', 3.0)))
    ap.add_argument('--buckets', default=os.environ.get(
        'CXXNET_SERVE_BENCH_BUCKETS', '1,8,32'))
    ap.add_argument('--max-wait', type=float, default=0.001)
    ap.add_argument('--slots', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_SLOTS', 8)))
    ap.add_argument('--pages', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_PAGES', 96)))
    ap.add_argument('--page-size', type=int, default=16)
    ap.add_argument('--max-new', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_MAX_NEW', 32)))
    ap.add_argument('--max-prompt', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_MAX_PROMPT', 24)))
    ap.add_argument('--requests', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_REQUESTS', 12)))
    ap.add_argument('--twin-checks', type=int, default=2)
    ap.add_argument('--spec-k', type=int, default=int(
        os.environ.get('CXXNET_SERVE_BENCH_SPEC_K', 4)))
    ap.add_argument('--seed', type=int, default=7)
    args = ap.parse_args(argv)

    if args.mode == 'sharded':
        # the sharded legs need a mesh: on CPU, widen the virtual
        # device set BEFORE jax initializes (the conftest pattern)
        plats = os.environ.get('JAX_PLATFORMS', '')
        flags = os.environ.get('XLA_FLAGS', '')
        if (not plats or plats == 'cpu') and \
                'xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=8'
            ).strip()

    modes = {'predict': bench_predict, 'decode': bench_decode,
             'decode_matrix': bench_decode_matrix,
             'prefix': bench_prefix, 'spec': bench_spec,
             'prefix_spec': bench_prefix_spec,
             'scenarios': bench_scenarios,
             'kv_tiers': bench_kv_tiers,
             'sharded': bench_sharded}
    metrics = {'predict': 'serve_p99_latency_ms',
               'decode': 'decode_tokens_per_sec',
               'decode_matrix': 'decode_int8_resident_reduction',
               'prefix': 'prefix_share_speedup',
               'spec': 'spec_decode_speedup',
               'prefix_spec': 'prefix_share_speedup',
               'scenarios': 'scenario_autoscale_wins',
               'kv_tiers': 'kv_tier_speedup',
               'sharded': 'decode_shard_scaling'}
    try:
        enable_compile_cache()
        require_chip()
        out = modes[args.mode](args)
    except Exception as e:  # structured failure, never a bare traceback
        out = {'metric': metrics[args.mode],
               'value': None, 'unit': None, 'error': repr(e)}
    print(json.dumps(out))
    return 0 if 'error' not in out else 1


if __name__ == '__main__':
    sys.exit(main())
