#!/usr/bin/env python
"""Per-op microbenchmark: Pallas kernels vs their XLA lowerings on the
real chip, at the shapes the framework actually runs (AlexNet LRN/fullc,
transformer attention).

    python tools/pallas_microbench.py [--json out.json]

Each op is timed fwd-only and fwd+bwd (grad through the op), looped
on-device inside one jit with the dispatch cost cancelled (see
chiptime.py — per-dispatch timing cannot rank sub-millisecond kernels).  Results feed BASELINE.md's kernel table and decide
the default `use_pallas` state (ops/pallas_kernels.py: pallas wins ->
enabled by default).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chiptime import atomic_receipt_dump, grad_probe, time_op  # noqa: E402

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from cxxnet_tpu.utils.backend import enable_compile_cache    # noqa: E402


_PASS_WRAPS = {'fwd': lambda f: f, 'fwd+bwd': None, 'bwd-op': lambda f: f}


def bench_pair(name, xla_fn, pallas_fn, args, results, flops=None,
               passes=('fwd', 'fwd+bwd')):
    # loop length is adaptive (chiptime.time_op auto-sizes iterations).
    # 'bwd-op' times a raw backward building block as-is (no grad wrap —
    # the raw impls aren't differentiable themselves).
    for tag in passes:
        wrap = _PASS_WRAPS[tag] or grad_probe
        t_x = time_op(wrap(xla_fn), args)
        t_p = time_op(wrap(pallas_fn), args)
        speedup = t_x / max(t_p, 1e-9)
        row = {'op': name, 'pass': tag,
               'xla_us': round(t_x * 1e6, 1),
               'pallas_us': round(t_p * 1e6, 1),
               'pallas_speedup': round(speedup, 3)}
        note = ''
        if flops is not None:
            # physically-impossible sanity column: >peak means the timing
            # (or a compiler simplification) is lying
            fl = flops * (3.0 if tag == 'fwd+bwd' else 1.0)
            row['xla_tflops'] = round(fl / max(t_x, 1e-9) / 1e12, 1)
            row['pallas_tflops'] = round(fl / max(t_p, 1e-9) / 1e12, 1)
            note = (f"  [{row['xla_tflops']:6.1f} vs "
                    f"{row['pallas_tflops']:6.1f} TF/s]")
        results.append(row)
        print(f'{name:28s} {tag:8s} xla {t_x * 1e6:9.1f}us  '
              f'pallas {t_p * 1e6:9.1f}us  speedup {speedup:6.3f}x{note}',
              flush=True)


def lrn_xla(x, nsize, alpha, beta, knorm):
    """The layer's default XLA path (layers/norm.py math)."""
    sq = (x * x).astype(jnp.float32)
    half_lo = (nsize - 1) // 2
    half_hi = nsize - 1 - half_lo
    win = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, 1, 1, nsize), (1, 1, 1, 1),
        [(0, 0), (0, 0), (0, 0), (half_lo, half_hi)])
    norm = knorm + (alpha / nsize) * win
    return (x.astype(jnp.float32) * norm ** (-beta)).astype(x.dtype)


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--json', default=None)
    ap.add_argument('--dtype', default='bfloat16',
                    choices=['bfloat16', 'float32'])
    ap.add_argument('--only', default='',
                    help='comma list of op groups: lrn,matmul,attn,'
                         'matmul_bwd,matmul_tiles')
    args = ap.parse_args()
    only = set(args.only.split(',')) if args.only else None

    def want(group):
        return only is None or group in only

    from cxxnet_tpu.ops.pallas_kernels import (flash_attention, lrn_pallas,
                                               pallas_matmul)
    from cxxnet_tpu.parallel.sequence import attention_reference

    dev = jax.devices()[0]
    print(f'device: {dev.device_kind} ({dev.platform})', flush=True)
    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32
    rng = np.random.RandomState(0)

    def dump(rows, partial: bool) -> None:
        atomic_receipt_dump(args.json,
                            {'device': dev.device_kind,
                             'dtype': args.dtype, 'results': list(rows)},
                            partial)

    class _DumpingList(list):
        def append(self, row):
            super().append(row)
            dump(self, partial=True)

    results = _DumpingList()

    # --- LRN at AlexNet shapes (NHWC) ---------------------------------
    for b, h, w, c in (((256, 27, 27, 96), (256, 13, 13, 256))
                       if want('lrn') else ()):
        x = jnp.asarray(rng.randn(b, h, w, c), dtype)
        bench_pair(f'lrn {b}x{h}x{w}x{c}',
                   functools.partial(lrn_xla, nsize=5, alpha=1e-4,
                                     beta=0.75, knorm=1.0),
                   lambda y: lrn_pallas(y, 5, 1e-4, 0.75, 1.0),
                   (x,), results)

    # --- fullc matmuls at AlexNet shapes ------------------------------
    for m, k, n in (((256, 9216, 4096), (256, 4096, 4096),
                     (256, 4096, 1000)) if want('matmul') else ()):
        a = jnp.asarray(rng.randn(m, k) * 0.05, dtype)
        bmat = jnp.asarray(rng.randn(k, n) * 0.05, dtype)
        bench_pair(f'matmul {m}x{k}x{n}',
                   lambda p, q: jnp.dot(p, q), pallas_matmul,
                   (a, bmat), results, flops=2.0 * m * k * n)

    # --- backward-matmul kernels (da = g@b^T, db = a^T@g) -------------
    # A/Bs the dedicated transpose-free NT/TN kernels against XLA's own
    # contraction of the stored layouts — the r3 fwd+bwd ratio (0.33x)
    # bundled a physical 75MB weight transpose into the pallas side
    if only is not None and 'matmul_bwd' in only:   # opt-in, like tiles
        from cxxnet_tpu.ops.pallas_kernels import (_matmul_nt_impl,
                                                   _matmul_tn_impl)
        for m, k, n in ((256, 9216, 4096), (256, 4096, 4096)):
            g = jnp.asarray(rng.randn(m, n) * 0.05, dtype)
            a = jnp.asarray(rng.randn(m, k) * 0.05, dtype)
            bmat = jnp.asarray(rng.randn(k, n) * 0.05, dtype)
            fl = 2.0 * m * k * n
            bench_pair(f'da=g@bT {m}x{k}x{n}',
                       lambda p, q: jax.lax.dot_general(
                           p, q, (((1,), (1,)), ((), ()))),
                       _matmul_nt_impl, (g, bmat), results, flops=fl,
                       passes=('bwd-op',))
            bench_pair(f'db=aT@g {m}x{k}x{n}',
                       lambda p, q: jax.lax.dot_general(
                           p, q, (((0,), (0,)), ((), ()))),
                       _matmul_tn_impl, (a, g), results, flops=fl,
                       passes=('bwd-op',))

    # --- matmul tile-size sweep (kernel tuning, fwd only) -------------
    # answers "is the 45% matmul gap a tiling problem?" in one run:
    # every (tm, tn, tk) variant of the K-blocked kernel vs XLA's dot
    # at the two big fullc shapes.  Opt-in only (--only matmul_tiles):
    # ~16 fresh kernel compiles would bloat the standard receipt run.
    if only is not None and 'matmul_tiles' in only:
        from cxxnet_tpu.ops.pallas_kernels import _matmul_impl
        for m, k, n in ((256, 9216, 4096), (256, 4096, 4096)):
            a = jnp.asarray(rng.randn(m, k) * 0.05, dtype)
            bmat = jnp.asarray(rng.randn(k, n) * 0.05, dtype)
            t_x = time_op(lambda p, q: jnp.dot(p, q), (a, bmat))
            fl = 2.0 * m * k * n
            print(f'matmul {m}x{k}x{n} XLA {t_x * 1e6:9.1f}us '
                  f'[{fl / t_x / 1e12:6.1f} TF/s]', flush=True)
            results.append({'op': f'matmul {m}x{k}x{n}', 'pass': 'fwd',
                            'tiles': 'xla', 'us': round(t_x * 1e6, 1),
                            'tflops': round(fl / t_x / 1e12, 1)})
            for tm, tn, tk in ((256, 256, 512), (128, 256, 512),
                               (256, 512, 512), (512, 512, 512),
                               (256, 256, 1024), (128, 512, 1024),
                               (256, 1024, 512), (512, 256, 1024)):
                f = functools.partial(_matmul_impl, tile_m=tm, tile_n=tn,
                                      tile_k=tk)
                try:
                    t_p = time_op(f, (a, bmat))
                except Exception as e:   # VMEM OOM at big tiles: record
                    print(f'  tiles {tm}x{tn}x{tk}: FAILED '
                          f'{type(e).__name__}', flush=True)
                    results.append({'op': f'matmul {m}x{k}x{n}',
                                    'pass': 'fwd',
                                    'tiles': f'{tm}x{tn}x{tk}',
                                    'error': type(e).__name__})
                    continue
                print(f'  tiles {tm}x{tn}x{tk}: {t_p * 1e6:9.1f}us '
                      f'[{fl / t_p / 1e12:6.1f} TF/s] '
                      f'{t_x / t_p:5.3f}x of XLA', flush=True)
                results.append({'op': f'matmul {m}x{k}x{n}',
                                'pass': 'fwd', 'tiles': f'{tm}x{tn}x{tk}',
                                'us': round(t_p * 1e6, 1),
                                'tflops': round(fl / t_p / 1e12, 1),
                                'vs_xla': round(t_x / t_p, 3)})

    # --- attention at transformer shapes ------------------------------
    for b, s, heads, d in (((4, 1024, 8, 64), (2, 4096, 8, 64))
                           if want('attn') else ()):
        q = jnp.asarray(rng.randn(b, s, heads, d) * 0.1, dtype)
        k = jnp.asarray(rng.randn(b, s, heads, d) * 0.1, dtype)
        v = jnp.asarray(rng.randn(b, s, heads, d) * 0.1, dtype)
        for causal in (False, True):
            bench_pair(
                f'attn b{b} s{s} h{heads} d{d}'
                f'{" causal" if causal else ""}',
                functools.partial(attention_reference, causal=causal),
                functools.partial(flash_attention, causal=causal),
                (q, k, v), results)

    dump(results, partial=False)
    if args.json:
        print(f'wrote {args.json}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
