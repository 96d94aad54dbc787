#!/usr/bin/env python
"""Validate the flash-attention auto-engage gate against reality.

    python tools/flash_engage_probe.py [--json out.json]

The ``attn_use_flash`` gate (ops/pallas_kernels.py) is a MEMORY
feasibility bound: dense attention materializes a b*h*s^2 f32 score
matrix, so past ~4 GiB the Pallas flash kernel is the only way to run
the shape at all.  Every SPEED-measured shape fit in HBM (dense won,
receipts/micro_attn.json) — so until this probe, the gate's engage side
had never been exercised on the real chip.  Three facts land in the
receipt:

1. at a dense-INFEASIBLE length (b1 h8 s32768: 34 GiB of scores) the
   gate engages and the flash forward completes with finite output;
2. its on-device time (K-vs-1 quotient, tools/chiptime.py);
3. at a dense-feasible length the same kernel matches the dense
   reference numerically (the correctness half, checkable only where
   dense fits).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chiptime import atomic_receipt_dump, time_op              # noqa: E402

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from cxxnet_tpu.utils.backend import enable_compile_cache    # noqa: E402


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--json', default=None)
    ap.add_argument('--seq', type=int, default=32768)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--dim', type=int, default=64)
    args = ap.parse_args()

    from cxxnet_tpu.ops.pallas_kernels import (attn_use_flash,
                                               flash_attention)
    from cxxnet_tpu.parallel.sequence import attention_reference

    payload = {'metric': 'flash_engage_probe', 'seq': args.seq,
               'heads': args.heads, 'head_dim': args.dim, 'value': None}

    def dump(partial=True):
        atomic_receipt_dump(args.json, payload, partial)

    # 1. the gate must engage at the dense-infeasible shape and stay off
    #    at the measured dense-feasible ones
    engaged = attn_use_flash(args.seq, batch=1, heads=args.heads)
    payload['gate_engages_at_infeasible'] = bool(engaged)
    payload['gate_off_at_4096'] = not attn_use_flash(4096, batch=2, heads=8)
    dump()
    if not engaged:
        payload['error'] = ('attn_use_flash did not engage at the '
                            'dense-infeasible length — gate broken or '
                            'not on a real TPU')
        dump(partial=False)
        print(json.dumps(payload))
        return 1

    # 2. correctness where dense still fits (bf16 tolerance)
    rng = jax.random.PRNGKey(0)
    small = 2048
    qs, ks, vs = (jax.random.normal(jax.random.fold_in(rng, i),
                                    (1, small, args.heads, args.dim),
                                    jnp.bfloat16) for i in range(3))
    ref = attention_reference(qs, ks, vs, causal=True)
    got = flash_attention(qs, ks, vs, causal=True)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    payload['small_check_max_abs_err'] = round(err, 5)
    payload['small_check_ok'] = err < 0.05
    dump()

    # 3. the engaged forward at the infeasible length: completes, finite,
    #    timed
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, 10 + i),
                                 (1, args.seq, args.heads, args.dim),
                                 jnp.bfloat16) for i in range(3))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    out = jax.jit(fwd)(q, k, v)
    finite = bool(np.isfinite(
        float(jnp.sum(out.astype(jnp.float32)))))
    payload['infeasible_fwd_finite'] = finite
    dump()
    t = time_op(fwd, (q, k, v), iters=5, reps=3)
    payload['infeasible_fwd_ms'] = round(t * 1e3, 2)
    payload['value'] = round(t * 1e3, 2)
    payload['unit'] = 'ms (b1 h8 s%d causal flash fwd)' % args.seq
    ok = (finite and payload['small_check_ok']
          and payload['gate_off_at_4096'])
    if not ok:
        # a failed validation must never pass receipt_ok as a landed
        # measurement: mark it so the idempotent runner re-runs the step
        payload['error'] = 'probe checks failed: ' + ', '.join(
            k for k, v in (('finite', finite),
                           ('small_check_ok', payload['small_check_ok']),
                           ('gate_off_at_4096',
                            payload['gate_off_at_4096'])) if not v)
    dump(partial=False)
    print(json.dumps(payload))
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
