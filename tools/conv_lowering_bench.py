"""On-chip A/B of conv lowerings (native fgc vs im2col-GEMM vs per-group
split) at the AlexNet shapes BASELINE.md names as the MFU ceiling-setters:
conv1 (11x11 s4 on a 3-deep input — MXU lane underfill) and the ngroup=2
conv2/4/5 (feature_group_count halves contraction depth per pass).

Timing: chiptime.time_op quotient loops (dispatch-cancelled, scatter-add
perturbation); fwd and fwd+bwd (grad_probe) per lowering.  Receipt feeds
the conv_lowering 'auto' policy (layers/conv.py) — a lowering only
becomes an auto default with a win recorded here.

Usage: python tools/conv_lowering_bench.py [--json receipts/conv_lowering.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from chiptime import atomic_receipt_dump, grad_probe, time_op  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# time the SHIPPED lowerings — the receipt decides conv.py's auto policy,
# so it must measure the code that policy gates, not a copy
from cxxnet_tpu.layers.conv import (conv_im2col, conv_native,  # noqa: E402
                                    conv_s2d, conv_split)
from cxxnet_tpu.utils.backend import enable_compile_cache  # noqa: E402

# (name, batch, in_y/x, cin, cout, kernel, stride, pad, ngroup)
SHAPES = [
    ('conv1 b256 227x227x3->96 k11s4', 256, 227, 3, 96, 11, 4, 0, 1),
    ('conv2 b256 27x27x96->256 k5 g2', 256, 27, 96, 256, 5, 1, 2, 2),
    ('conv4 b256 13x13x384->384 k3 g2', 256, 13, 384, 384, 3, 1, 1, 2),
    ('conv5 b256 13x13x384->256 k3 g2', 256, 13, 384, 256, 3, 1, 1, 2),
]


def lowering_fns(k, stride, pad, g):
    strides = (stride, stride)
    padding = ((pad, pad), (pad, pad))
    out = {'native': lambda x, w: conv_native(x, w, strides, padding, g)}
    if g == 1:
        out['im2col'] = lambda x, w: conv_im2col(x, w, strides, padding)
        if stride > 1 and pad % stride == 0:
            out['s2d'] = lambda x, w: conv_s2d(x, w, strides, padding)
    else:
        out['split'] = lambda x, w: conv_split(x, w, strides, padding, g)
    return out


def flops(b, y, cin, cout, k, stride, pad, g):
    o = (y + 2 * pad - k) // stride + 1
    return 2 * b * o * o * (cin // g) * k * k * cout


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--json', default=None)
    ap.add_argument('--only', default=None, help='substring filter on name')
    ap.add_argument('--smoke', action='store_true',
                    help='batch 4 (CPU pipe-clean, numbers meaningless)')
    args = ap.parse_args()
    if args.smoke:
        global SHAPES
        SHAPES = [(n, 4, y, ci, co, k, s, p, g)
                  for (n, _, y, ci, co, k, s, p, g) in SHAPES]

    dev = jax.devices()[0]
    print(f'device: {dev.device_kind} ({dev.platform})', flush=True)
    rng = np.random.RandomState(0)
    results = []
    for (name, b, y, cin, cout, k, stride, pad, g) in SHAPES:
        if args.only and args.only not in name:
            continue
        x = jnp.asarray(rng.randn(b, y, y, cin), jnp.bfloat16)
        w = jnp.asarray(0.01 * rng.randn(k, k, cin // g, cout), jnp.bfloat16)
        fns = lowering_fns(k, stride, pad, g)
        gf = flops(b, y, cin, cout, k, stride, pad, g)
        base = {}
        for passname, wrap in (('fwd', lambda f: f), ('fwd+bwd', grad_probe)):
            mult = 1 if passname == 'fwd' else 3   # bwd ~2x fwd FLOPs
            for lname, fn in fns.items():
                t = time_op(wrap(fn), (x, w))
                tf = gf * mult / t / 1e12
                r = {'op': name, 'pass': passname, 'lowering': lname,
                     'us': round(t * 1e6, 1), 'tflops': round(tf, 1)}
                if lname == 'native':
                    base[passname] = t
                elif base.get(passname):
                    r['speedup_vs_native'] = round(base[passname] / t, 3)
                results.append(r)
                extra = ('  %.3fx vs native' % (base[passname] / t)
                         if lname != 'native' and base.get(passname) else '')
                print(f'{name:34s} {passname:7s} {lname:7s} '
                      f'{t * 1e6:9.1f}us  {tf:6.1f} TF/s{extra}',
                      flush=True)
                # durability: dump partial results as each row lands;
                # atomic replace so a mid-write kill can't leave a
                # truncated (non-empty but unparseable) receipt.  The
                # 'partial' flag comes off only in the final dump below,
                # so a relaunch that skips finished receipts re-runs
                # an interrupted sweep instead of skipping it forever.
                if args.json:
                    _dump_json(args.json, dev, results, partial=True)
    if args.json and results:
        _dump_json(args.json, dev, results, partial=False)
        print(f'wrote {args.json}')
    elif args.json:
        print(f'NOTHING matched --only={args.only}: {args.json} NOT written')
    return 0


def _dump_json(path, dev, results, partial):
    atomic_receipt_dump(path, {'device': dev.device_kind,
                               'dtype': 'bfloat16', 'results': results},
                        partial)


if __name__ == '__main__':
    raise SystemExit(main())
