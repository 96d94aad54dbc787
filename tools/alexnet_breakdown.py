#!/usr/bin/env python
"""Per-layer time breakdown of a model-zoo train step on the real chip.

    python tools/alexnet_breakdown.py [--model alexnet] [--batch 256]
                                      [--json out.json]

``--model googlenet`` attributes the inception towers (the MFU-0.12
question); ``alexnet`` is the default and the historical name.

This tool derives the MFU breakdown without the profiler (it was written
for a remote chip the profiler could not trace; ``profile_dir=`` works on
a local one, and ROADMAP S1 decides which of the two the benchmark
keeps): it times the full optimizer step
(trainer.compile_multi_step — the whole K-step loop in one dispatch), the
forward pass, and each parameterized/pooling/LRN layer in isolation
(jitted at its exact activation shape, fwd and fwd+bwd).  All timings
loop on-device inside one jit with the dispatch cost cancelled (see
chiptime.py).
Layer times are lower bounds (isolated kernels skip fusion
opportunities) but name where the step's time goes — the evidence the
MFU question needs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chiptime import atomic_receipt_dump, time_op              # noqa: E402

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from cxxnet_tpu.utils.backend import enable_compile_cache    # noqa: E402


def _time_step_scan(tr, dstack, lstack, iters=10, reps=3):
    """Per-step seconds of the full optimizer step via the trainer's
    scanned multi-step path (iters-vs-1 difference quotient)."""
    m1 = tr.compile_multi_step(1)
    mk = tr.compile_multi_step(iters)

    def run(fn, n):
        return float(np.asarray(tr.update_n_on_device(fn, dstack, lstack, n)))

    run(m1, 1)
    run(mk, iters)
    t1s, tks = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(m1, 1)
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run(mk, iters)
        tks.append(time.perf_counter() - t0)
    # min at each endpoint rejects dispatch jitter spikes (see chiptime.py)
    return (min(tks) - min(t1s)) / (iters - 1)


_MODELS = {  # name -> (conf fn name, default batch, input shape)
    'alexnet': ('alexnet_conf', 256, (3, 227, 227)),
    'inception_bn': ('inception_bn_conf', 128, (3, 224, 224)),
    'googlenet': ('googlenet_conf', 128, (3, 224, 224)),
    'vgg16': ('vgg16_conf', 64, (3, 224, 224)),
}


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--model', default='alexnet', choices=sorted(_MODELS))
    ap.add_argument('--batch', type=int, default=None)
    ap.add_argument('--json', default=None)
    ap.add_argument('--dtype', default='bfloat16',
                    choices=('bfloat16', 'float32'),
                    help='float32 for CPU pipe-clean runs — CPU bf16 is '
                         'emulated and minutes-slow per conv')
    args = ap.parse_args()

    from cxxnet_tpu import models
    from cxxnet_tpu.layers import ForwardContext
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    conf_fn, default_bs, shape = _MODELS[args.model]
    bs = args.batch or default_bs
    conf = getattr(models, conf_fn)() + f"""
batch_size = {bs}
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
compute_type = {args.dtype}
"""
    cdtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32
    tr = NetTrainer(parse_config_string(conf))
    tr.init_model()
    rng = np.random.RandomState(0)
    dstack = tr.shard_batch_stack(
        rng.randint(0, 256, (2, bs) + shape, dtype=np.uint8))
    lstack = tr.shard_batch_stack(
        rng.randint(0, 1000, (2, bs, 1)).astype(np.float32), cast=False)
    data, label = dstack[0], lstack[0]

    # Ordering: per-layer rows FIRST (cheap compiles, the attribution
    # value unique to this tool), whole-step anchor LAST — three runs in
    # a row were killed inside the expensive multi-step-scan compile
    # before a single layer row existed.  pct_of_step is filled in once
    # (if) the step time lands; the known-good step time from the
    # bench_alexnet receipt anchors a partial file.
    t_step = t_fwd = step_flops = None
    net = tr.net
    host = jax.device_get(tr.params)
    rows = []

    def dump(partial: bool) -> None:
        # after EVERY layer: a killed/timed-out run must still leave the
        # rows it produced — losing a finished measurement to a
        # round-end kill is the round-3 failure mode the receipts
        # discipline exists to prevent
        atomic_receipt_dump(args.json, {
            'model': args.model, 'batch': bs,
            'step_ms': round(t_step * 1e3, 2) if t_step else None,
            'fwd_ms': round(t_fwd * 1e3, 2) if t_fwd else None,
            'achieved_tflops': round(step_flops / t_step / 1e12, 2)
                               if t_step and step_flops else None,
            'layers': rows}, partial)

    dump(partial=True)
    for i, info in enumerate(net.cfg.layers):
        layer = net.layers[i]
        if layer.type_name in ('relu', 'flatten', 'dropout', 'softmax'):
            continue                      # elementwise: fused in practice
        spec_in = [net.node_specs[j] for j in info.nindex_in]
        xs = []
        for sp in spec_in:
            shape = ((bs, sp.flat_size) if sp.is_mat
                     else (bs, sp.y, sp.x, sp.c))
            xs.append(jnp.asarray(rng.randn(*shape) * 0.1, cdtype))
        lp = {k: jnp.asarray(v) for k, v in
              host.get(str(net.layer_primary[i]), {}).items()}
        ctx = ForwardContext(is_train=True, rng=jax.random.PRNGKey(0),
                             layer_index=i, compute_dtype=cdtype)

        def f(*inputs, _layer=layer, _lp=lp, _ctx=ctx):
            return _layer.forward(_lp, list(inputs), _ctx)[0]

        is_input_layer = 0 in info.nindex_in

        def g(*inputs, _layer=layer, _lp=lp, _ctx=ctx,
              _input_layer=is_input_layer):
            def loss(lp_, ins):
                out = _layer.forward(lp_, list(ins), _ctx)[0]
                return jnp.sum(out.astype(jnp.float32))
            # interior layers: differentiate wrt params AND inputs —
            # training computes both dW and dX there (skipping dX would
            # let XLA dead-code-eliminate ~1/3 of a conv/fullc layer's
            # backward FLOPs).  The input layer gets params-only, like
            # the real step (no dX wrt the data batch).
            if _lp and _input_layer:
                return jax.grad(loss)(_lp, inputs)
            if _lp:
                return jax.grad(loss, argnums=(0, 1))(_lp, inputs)
            return jax.grad(lambda ins: loss(_lp, ins))(inputs)

        name = f'{i:2d} {layer.type_name}:{info.name or ""}'
        print(f'... timing {name.strip()} fwd', flush=True)
        t_f = time_op(f, tuple(xs))
        print(f'... timing {name.strip()} fwd+bwd', flush=True)
        t_g = time_op(g, tuple(xs))
        rows.append({'layer': name.strip(), 'fwd_us': round(t_f * 1e6, 1),
                     'fwd_bwd_us': round(t_g * 1e6, 1)})
        print(f'{name:26s} fwd {t_f * 1e6:9.1f}us   '
              f'fwd+bwd {t_g * 1e6:9.1f}us', flush=True)
        dump(partial=True)

    # --- whole step & forward-only (the expensive compiles) -----------
    print('timing full train step (multi-step scan compile)...',
          flush=True)
    t_step = _time_step_scan(tr, dstack, lstack)
    for r in rows:
        r['pct_of_step'] = round(100 * r['fwd_bwd_us'] / 1e6 / t_step, 1)
    dump(partial=True)      # t_step is the costliest number: persist NOW
    fwd = tr._forward_fn
    params = tr.params
    t_fwd = time_op(lambda d: fwd(params, d, (), 0), (data,))
    step_flops = tr.train_step_flops(data, label)
    print(f'full train step: {t_step * 1e3:8.2f} ms   '
          f'({step_flops / t_step / 1e12:.1f} TFLOP/s achieved)')
    print(f'forward only:    {t_fwd * 1e3:8.2f} ms')
    covered = sum(r['fwd_bwd_us'] for r in rows) / 1e6
    print(f'sum of isolated layers (fwd+bwd): {covered * 1e3:.2f} ms '
          f'of {t_step * 1e3:.2f} ms step '
          f'({100 * covered / t_step:.0f}% — rest is fusion overlap, '
          f'elementwise, optimizer, dispatch)')
    dump(partial=False)
    if args.json:
        print(f'wrote {args.json}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
