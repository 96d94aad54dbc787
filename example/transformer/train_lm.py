#!/usr/bin/env python
"""Train a tiny causal LM with composed 4D parallelism (pp x dp x sp x tp).

The long-context / distributed side of the framework (beyond the
reference's CNN scope): pipeline stages over the ``pipe`` mesh axis, data
parallelism over ``data``, ring-attention sequence parallelism over
``seq``, tensor-parallel heads/FFN over ``model``, optional switch-MoE
experts over the data axis.  Runs anywhere — on a laptop it uses 8 virtual
CPU devices; on a TPU slice the same code spans the real chips.

  python example/transformer/train_lm.py                # pp2 dp2 sp2 tp1
  python example/transformer/train_lm.py --pp 1 --dp 4 --sp 2 --tp 1
  python example/transformer/train_lm.py --experts 4    # switch-MoE FFN
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--pp', type=int, default=2)
    ap.add_argument('--dp', type=int, default=2)
    ap.add_argument('--sp', type=int, default=2)
    ap.add_argument('--tp', type=int, default=1)
    ap.add_argument('--experts', type=int, default=0)
    ap.add_argument('--remat', action='store_true',
                    help='rematerialize blocks in backward (long-context HBM saver)')
    ap.add_argument('--steps', type=int, default=30)
    ap.add_argument('--seq', type=int, default=128)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--ckpt_dir', default='',
                    help='sharded orbax checkpoint dir; resumes from the '
                         'newest step when one exists')
    ap.add_argument('--save_every', type=int, default=10)
    ap.add_argument('--generate', type=int, default=0, metavar='N',
                    help='after training, greedy-decode N tokens from a '
                         'training prompt (KV-cached transformer.generate '
                         '— the LM analog of task=pred)')
    ap.add_argument('--temperature', type=float, default=0.0,
                    help='sampling temperature for --generate (0=greedy)')
    args = ap.parse_args()
    if args.save_every <= 0:
        ap.error('--save_every must be >= 1')
    n = args.pp * args.dp * args.sp * args.tp

    import jax
    if len(jax.devices()) < n:
        # virtual CPU mesh for development machines
        from jax.extend import backend as jexb
        jexb.clear_backends()
        jax.config.update('jax_platforms', 'cpu')
        jax.config.update('jax_num_cpu_devices', n)

    import numpy as np
    from cxxnet_tpu.models.transformer import (TransformerConfig,
                                               abstract_params,
                                               build_transformer_mesh,
                                               init_params, make_train_step)

    if args.batch % args.dp:
        ap.error(f'--batch {args.batch} must be divisible by --dp {args.dp}')
    # GPipe microbatches must divide the per-data-rank batch; use the most
    # the local batch allows, capped at the default of 4.  Stage count must
    # equal the pipe axis size (each pipe rank owns exactly one stage).
    local_batch = args.batch // args.dp
    micro = max(m for m in (4, 3, 2, 1) if local_batch % m == 0)
    cfg = TransformerConfig(seq_len=args.seq, num_experts=args.experts,
                            num_stages=args.pp,
                            num_microbatches=micro, remat=args.remat)
    mesh = build_transformer_mesh(n, args.pp, args.dp, args.sp, args.tp)
    print(f'mesh: {dict(mesh.shape)}  experts={args.experts}')
    step = make_train_step(cfg, mesh)
    params, start_step = None, 0
    if args.ckpt_dir:
        from cxxnet_tpu.nnet.sharded_ckpt import (latest_step,
                                                  restore_sharded,
                                                  save_sharded,
                                                  wait_for_saves)
        if latest_step(args.ckpt_dir) is not None:
            # shapes-only restore target: resume never materializes a
            # throwaway full replica
            params, start_step = restore_sharded(
                args.ckpt_dir, abstract_params(None, cfg, mesh))
            start_step += 1
            print(f'resumed from step {start_step - 1}')
    if params is None:
        params = init_params(np.random.RandomState(0), cfg)

    # synthetic copy-task data: predict the previous token
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, cfg.vocab_size,
                         (args.batch, cfg.seq_len)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1).astype(np.int32)

    t0 = time.time()
    for i in range(start_step, args.steps):
        params, loss, aux = step(params, tokens, labels)
        if i % 10 == 0 or i == args.steps - 1:
            moe = (f'  balance {float(aux["balance_loss"]):.3f}'
                   f'  drop {float(aux["drop_frac"]):.3f}'
                   if args.experts else '')
            print(f'step {i:4d}  loss {float(loss):.4f}{moe}  '
                  f'({time.time() - t0:.1f}s)')
        if args.ckpt_dir and ((i + 1) % args.save_every == 0
                              or i == args.steps - 1):
            # async: the commit overlaps the next training steps
            save_sharded(args.ckpt_dir, i, params, block=False)
    if args.ckpt_dir:
        wait_for_saves()
    if args.generate:
        import jax
        from cxxnet_tpu.models.transformer import generate

        # decode happens on replicated single-logical-device params: pull
        # the (tiny example) params off the mesh once
        host_params = jax.tree.map(lambda a: np.asarray(a), params)
        prompt = tokens[:2, :8]
        out = np.asarray(generate(
            host_params, prompt, args.generate, cfg,
            temperature=args.temperature,
            rng=jax.random.PRNGKey(0) if args.temperature > 0 else None))
        for b in range(out.shape[0]):
            print(f'prompt {list(map(int, prompt[b]))} -> '
                  f'decoded {list(map(int, out[b]))}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
