#!/usr/bin/env python
"""Benchmark: training throughput (images/sec/chip) on real hardware.

Default (what the driver runs) — AlexNet batch 256, prints ONE JSON line:
  {"metric": "alexnet_images_per_sec_per_chip", "value": N,
   "unit": "images/sec", "vs_baseline": N, "mfu": F, "tflops": T}

Extra modes for the BASELINE.md ledger (same JSON shape):
  python bench.py inception_bn     # Inception-BN batch 128 throughput
  python bench.py googlenet        # GoogLeNet v1 batch 128 throughput
  python bench.py vgg16            # VGG-16 batch 64 throughput
  python bench.py e2e_alexnet      # AlexNet through the FULL data path
                                   #   (imgbin+decode+augment+H2D included)
  python bench.py mnist_tta        # MNIST conv time-to-2%-test-error (sec)
  python bench.py eval_alexnet     # AlexNet EVAL (forward-only) img/s —
                                   #   fc8 Pallas gate A/B in one receipt
  python bench.py transformer      # TransformerLM tokens/sec (GPT-2-small
                                   #   class; beyond-reference family)
  python bench.py decode           # LM inference tokens/sec (KV-cached
                                   #   autoregressive generate)
  python bench.py io               # host input pipeline only (no chip):
                                   #   imgbinx chain + nworker pool sweep
                                   #   (alias: bench_io; BENCH_IO_r01.json)
  python bench.py scan             # SUPERVISED steps/sec A/B: K=4 scanned
                                   #   dispatch vs per-step with the
                                   #   supervisor on (BENCH_SCAN_r01.json)
  python bench.py online           # train-while-serve: steps/sec under
                                   #   live traffic + freshness p50/p99 +
                                   #   swap count (BENCH_ONLINE_r01.json)

``CXXNET_BENCH_CONF_EXTRA`` appends config lines (';'-separated) to every
model bench conf — the execution-plan A/B hook (e.g.
``fuse_blockdiag = auto``, ``conv_lowering = s2d``).

Backend: a mode that measures the device checks, in this process, that
JAX runs on a TPU and otherwise exits non-zero with ONE JSON line
carrying an "error" field — there is no rerun on the CPU.  A caller that
pins JAX_PLATFORMS=cpu gets a correctness run stamped
``"platform": "cpu"``, whose timings are not device numbers.  Any other
failure is likewise one structured JSON line, never a bare traceback.

MFU: flops per optimizer step come from the compiled executable's own
cost analysis (trainer.train_step_flops); peak chip flops from the device
kind (override with $CXXNET_PEAK_TFLOPS).

Baseline: the reference repo publishes no numbers (BASELINE.md).  We use
500 images/sec as the stand-in for cxxnet-CUDA AlexNet on a 2015-era
high-end GPU (Titan X class, cuDNN-era full fwd+bwd+update; see BASELINE.md
ledger) until a measured reference figure exists.
"""

from __future__ import annotations

import gzip
import json
import os
import struct
import sys
import time
from typing import Optional

import numpy as np

BASELINE_IMAGES_PER_SEC = 500.0          # AlexNet stand-in (see docstring)
BASELINE_INCEPTION_IMAGES_PER_SEC = 130.0  # Inception-BN stand-in, same era
BASELINE_GOOGLENET_IMAGES_PER_SEC = 150.0  # GoogLeNet v1 stand-in, same era
BASELINE_VGG16_IMAGES_PER_SEC = 50.0       # VGG-16 stand-in, same era
BASELINE_MNIST_TTA_SEC = 30.0            # reference MNIST.conf CPU run
BASELINE_TRANSFORMER_TOKENS_PER_SEC = 25000.0  # stand-in: GPT-2-small-class
# fp16 training on a 2019 V100 (no reference number exists — the
# reference framework has no attention; generous like the other stand-ins)

# bf16 peak TFLOP/s by TPU generation — THE table lives in
# cxxnet_tpu/obs/programs.py (the MFU gauge on the train eval line
# divides by the same numbers; _peak_flops below delegates to it)


#: the backend main() found for a device mode; host-only modes keep 'host'
_platform = 'host'


def _emit(obj: dict) -> None:
    """One JSON line; every payload says which backend produced it."""
    obj.setdefault('platform', _platform)
    print(json.dumps(obj))


def _peak_flops() -> float:
    """Peak bf16 FLOP/s of one chip, for the MFU denominator — ONE
    table (``obs/programs.py``) shared with the train eval line's MFU
    gauge, ``CXXNET_PEAK_TFLOPS`` override included."""
    from cxxnet_tpu.obs.programs import peak_flops
    return peak_flops()


def _program_summary() -> Optional[dict]:
    """The ledger's compile summary for the receipt (programs /
    compiles / compile-ms / recompiles) — None when nothing compiled
    in-process (subprocess-driven modes)."""
    from cxxnet_tpu.obs.programs import get_ledger
    led = get_ledger()
    led.entries()                 # force the lazy AOT analysis so the
                                  # receipt's compile_ms_total is real
    s = led.summary()
    return s if s['compiles_total'] else None


def _bench_steps(default: int) -> int:
    """K for the K-vs-1 quotient; floor 2 (K=1 has no quotient)."""
    return max(2, int(os.environ.get('CXXNET_BENCH_STEPS', str(default))))


def _quotient_per_step(run_1, run_k, steps: int):
    """The ledger timing method, in ONE place: warm both compiled loops,
    then 4 reps of each endpoint; per-step seconds is the K-vs-1
    difference quotient of the min wall times.  min over reps because the
    link cost is a constant floor plus positive jitter spikes, so min
    rejects the spikes where a median-of-noisy-quotients cannot.
    Returns (per_step_seconds, t1s)."""
    run_1()                              # compile + warm
    run_k()
    t1s, tks = [], []
    for _ in range(4):
        t0 = time.perf_counter()
        run_1()
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_k()
        tks.append(time.perf_counter() - t0)
    return (min(tks) - min(t1s)) / (steps - 1), t1s


def _emit_throughput(metric: str, work_per_step: float, unit: str,
                     baseline: float, step_flops: float, per_step: float,
                     t1s) -> None:
    """The shared ledger JSON payload (value/tflops/mfu/step_ms/
    dispatch_ms/timing keys) — one schema for every model family.

    The A/B experiment knobs ride in the receipt itself (``batch`` from
    ``CXXNET_BENCH_BATCH``, ``conf_extra`` from
    ``CXXNET_BENCH_CONF_EXTRA``; both None on a baseline run), so a
    ledger entry is self-describing — an override run can never be
    mistaken for the default configuration it is measured against.
    ``save_stall_ms_per_step`` is 0.0 here by construction (these loops
    never touch a checkpoint); ``bench_ckpt.py`` measures the nonzero
    sync-vs-async story on the same schema key."""
    import statistics

    rate = work_per_step / per_step
    achieved = step_flops / per_step
    peak = _peak_flops()
    measured = step_flops > 0            # 0 = backend has no cost model
    env_batch = os.environ.get('CXXNET_BENCH_BATCH')
    conf_extra = os.environ.get('CXXNET_BENCH_CONF_EXTRA', '').strip()
    _emit({
        'metric': metric,
        'value': round(rate, 1),
        'unit': unit,
        'vs_baseline': round(rate / baseline, 3),
        'tflops': round(achieved / 1e12, 2) if measured else None,
        'mfu': round(achieved / peak, 4) if measured and peak else None,
        # compiler truth (obs/programs.py): the HLO flops the mfu/tflops
        # figures divide, plus the run's compile ledger — a receipt now
        # says what was compiled, how long compiles took, and whether
        # the recompile sentinel fired during the measurement
        'flops_per_step': round(step_flops) if measured else None,
        'programs': _program_summary(),
        'step_ms': round(per_step * 1e3, 3),
        # wall time of a 1-step dispatch minus the step itself = the pure
        # link/dispatch overhead one un-pipelined update() pays per call
        'dispatch_ms': round(statistics.median(t1s) * 1e3 - per_step * 1e3,
                             1),
        'batch': int(env_batch) if env_batch else None,
        'conf_extra': conf_extra or None,
        'save_stall_ms_per_step': 0.0,
        'timing': 'scan-in-jit K-vs-1 quotient',
    })


def _throughput(conf: str, batch_size: int, shape, metric: str,
                baseline: float) -> int:
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    trainer = NetTrainer(parse_config_string(conf))
    trainer.init_model()

    # raw uint8 pixels pre-staged on device: measures the full training
    # step (device-side cast/normalize + fwd + bwd + optimizer) per chip.
    # Host-to-device input transfer is excluded — in production the input
    # pipeline double-buffers H2D behind compute (utils/thread_buffer +
    # trainer.update's async staging); e2e_alexnet measures it.
    #
    # Timing method: the whole K-step loop runs on device in ONE dispatch
    # (trainer.compile_multi_step: lax.scan over the params carry), and
    # the per-step time is the K-vs-1 difference quotient, which cancels
    # the constant per-dispatch cost exactly.  It was chosen when every
    # dispatch paid a ~7 ms round trip to a remote chip; ROADMAP S1
    # re-tests it against plain per-step timing on a local one.
    rng = np.random.RandomState(0)
    nstack = 4
    dstack = trainer.shard_batch_stack(
        rng.randint(0, 256, (nstack, batch_size) + shape, dtype=np.uint8))
    lstack = trainer.shard_batch_stack(
        rng.randint(0, 1000, (nstack, batch_size, 1)).astype(np.float32),
        cast=False)

    steps = _bench_steps(30)
    multi_1 = trainer.compile_multi_step(1)
    multi_k = trainer.compile_multi_step(steps)

    def run(fn, n) -> float:
        # fetching the returned device scalar is the completion barrier
        return float(np.asarray(
            trainer.update_n_on_device(fn, dstack, lstack, n)))

    per_step, t1s = _quotient_per_step(
        lambda: run(multi_1, 1), lambda: run(multi_k, steps), steps)
    # AFTER the warm runs: the flops read the ledger entries the loops
    # above just compiled — no throwaway probe program
    step_flops = trainer.train_step_flops(dstack[0], lstack[0])
    _emit_throughput(metric, batch_size, 'images/sec', baseline,
                     step_flops, per_step, t1s)
    return 0


def _bench_batch(default: int) -> int:
    """``CXXNET_BENCH_BATCH`` overrides a bench's default batch size
    (batch-scaling experiments, e.g. GoogLeNet 128 vs 256)."""
    return int(os.environ.get('CXXNET_BENCH_BATCH', default))


def _extra_conf() -> str:
    """``CXXNET_BENCH_CONF_EXTRA`` appends config lines (';'-separated)
    to every model bench conf — the A/B hook for execution-plan knobs
    (e.g. ``fuse_blockdiag = auto`` for the GoogLeNet tower-fusion
    receipt) without a bench.py edit per experiment."""
    extra = os.environ.get('CXXNET_BENCH_CONF_EXTRA', '').strip()
    return (extra.replace(';', '\n') + '\n') if extra else ''


def bench_alexnet() -> int:
    from cxxnet_tpu.models import alexnet_conf
    batch_size = _bench_batch(256)
    conf = alexnet_conf() + f"""
batch_size = {batch_size}
eta = 0.01
momentum = 0.9
wmat:wd = 0.0005
bias:wd = 0.0
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
    return _throughput(conf, batch_size, (3, 227, 227),
                       'alexnet_images_per_sec_per_chip',
                       BASELINE_IMAGES_PER_SEC)


def bench_eval_alexnet() -> int:
    """Net-level EVAL (forward-only) throughput on AlexNet, A/B over the
    fc8-class Pallas forward gate in ONE receipt.

    The micro receipt (micro_matmul.json) shows the Pallas forward 4.28x
    over XLA at fc8's non-lane-aligned 256x4096x1000 — this measures
    whether that survives at net level (fc8 is a sub-ms slice of the
    step), which decides if the ``fullc_use_pallas`` auto gate stays.
    ``value`` is the gated (auto) img/s; ``gate_off_images_per_sec`` and
    ``gate_speedup`` carry the A/B."""
    from cxxnet_tpu.models import alexnet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    batch_size = _bench_batch(256)
    conf = alexnet_conf() + f"""
batch_size = {batch_size}
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
    trainer = NetTrainer(parse_config_string(conf))
    trainer.init_model()
    rng = np.random.RandomState(0)
    dstack = trainer.shard_batch_stack(
        rng.randint(0, 256, (4, batch_size, 3, 227, 227), dtype=np.uint8))
    steps = _bench_steps(30)

    # the off leg uses the fullc-only kill switch: CXXNET_PALLAS=0 would
    # also disable the LRN auto winners and credit their delta to this
    # gate
    prev = os.environ.get('CXXNET_FULLC_PALLAS')
    rates = {}
    try:
        for gate, env in (('auto', None), ('off', '0')):
            if env is None:
                os.environ.pop('CXXNET_FULLC_PALLAS', None)
            else:
                os.environ['CXXNET_FULLC_PALLAS'] = env
            # fresh jit objects per gate setting: the env is read at trace
            # time, so reusing a compiled fn would ignore the toggle
            fwd_1 = trainer.compile_multi_forward(1)
            fwd_k = trainer.compile_multi_forward(steps)

            def run(fn):
                return float(np.asarray(fn(trainer.params, dstack)))

            per_step, t1s = _quotient_per_step(
                lambda: run(fwd_1), lambda: run(fwd_k), steps)
            rates[gate] = batch_size / per_step
    finally:
        if prev is None:
            os.environ.pop('CXXNET_FULLC_PALLAS', None)
        else:
            os.environ['CXXNET_FULLC_PALLAS'] = prev
    _emit({
        'metric': 'alexnet_eval_images_per_sec_per_chip',
        'value': round(rates['auto'], 1),
        'unit': 'images/sec',
        'vs_baseline': None,
        'gate_off_images_per_sec': round(rates['off'], 1),
        'gate_speedup': round(rates['auto'] / rates['off'], 4),
        'timing': 'scan-in-jit K-vs-1 quotient, fwd-only',
    })
    return 0


def bench_inception_bn() -> int:
    from cxxnet_tpu.models import inception_bn_conf
    batch_size = _bench_batch(128)
    conf = inception_bn_conf() + f"""
batch_size = {batch_size}
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
    return _throughput(conf, batch_size, (3, 224, 224),
                       'inception_bn_images_per_sec_per_chip',
                       BASELINE_INCEPTION_IMAGES_PER_SEC)


def bench_googlenet() -> int:
    from cxxnet_tpu.models import googlenet_conf
    batch_size = _bench_batch(128)
    conf = googlenet_conf() + f"""
batch_size = {batch_size}
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
    return _throughput(conf, batch_size, (3, 224, 224),
                       'googlenet_images_per_sec_per_chip',
                       BASELINE_GOOGLENET_IMAGES_PER_SEC)


def bench_vgg16() -> int:
    from cxxnet_tpu.models import vgg16_conf
    batch_size = _bench_batch(64)
    conf = vgg16_conf() + f"""
batch_size = {batch_size}
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
    return _throughput(conf, batch_size, (3, 224, 224),
                       'vgg16_images_per_sec_per_chip',
                       BASELINE_VGG16_IMAGES_PER_SEC)


def _transformer_throughput(cfg, batch: int, metric: str,
                            baseline: float) -> int:
    """Tokens/sec of a TransformerLM train step on the current backend,
    timed like _throughput: the whole K-step loop runs on device in one
    dispatch (lax.scan over the params carry, cycling a stacked token
    stack) and the per-step time is the K-vs-1 difference quotient."""
    import jax.numpy as jnp

    from cxxnet_tpu.models import transformer as T

    rng = np.random.RandomState(0)
    params = T.init_params(rng, cfg)
    nstack = 4
    toks = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (nstack, batch, cfg.seq_len)), jnp.int32)
    labs = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (nstack, batch, cfg.seq_len)), jnp.int32)

    steps = _bench_steps(20)
    multi_1 = T.make_multi_train_step(cfg, 1, lr=0.01)
    multi_k = T.make_multi_train_step(cfg, steps, lr=0.01)

    try:
        cost = multi_1.lower(params, toks, labs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        step_flops = float(cost.get('flops', 0.0)) if cost else 0.0
    except Exception:
        step_flops = 0.0

    def run(fn):
        nonlocal params
        params, loss = fn(params, toks, labs)
        # the device_get of the loss is the completion barrier
        return float(np.asarray(loss))

    per_step, t1s = _quotient_per_step(
        lambda: run(multi_1), lambda: run(multi_k), steps)
    _emit_throughput(metric, batch * cfg.seq_len, 'tokens/sec', baseline,
                     step_flops, per_step, t1s)
    return 0


def bench_transformer() -> int:
    """TransformerLM tokens/sec on one chip — the beyond-reference
    flagship family (the reference has no attention anywhere, SURVEY.md
    §5 'long-context: N/A for parity').  GPT-2-small-class decoder:
    8 blocks, d_model 1024, 16 heads, d_ff 4096, causal, bf16.  Times
    the single-device path (``reference_loss`` + scanned SGD) — the
    exact math the 4-axis shard_map step is oracle-tested against
    (tests/test_transformer_parallel.py), but NOT the shard_map program
    itself, which needs a multi-chip mesh to mean anything."""
    import jax.numpy as jnp

    from cxxnet_tpu.models import transformer as T

    batch = _bench_batch(16)
    seq = int(os.environ.get('CXXNET_BENCH_SEQ', '1024'))
    cfg = T.TransformerConfig(
        vocab_size=32768, d_model=1024, num_heads=16, d_ff=4096,
        num_stages=8, seq_len=seq, attn='local', causal=True,
        num_microbatches=1, dtype=jnp.bfloat16)
    return _transformer_throughput(
        cfg, batch, 'transformer_tokens_per_sec_per_chip',
        BASELINE_TRANSFORMER_TOKENS_PER_SEC)


def bench_decode() -> int:
    """Autoregressive decode throughput (tokens/sec/chip) on the
    GPT-2-small-class LM — the inference-side counterpart of
    ``transformer`` (training tok/s).  KV-cached ``transformer.generate``
    runs prefill + the whole decode scan in ONE dispatch; per-token time
    is the K-vs-1 difference quotient over the number of NEW tokens, so
    the dispatch/link cost and the shared prefill cancel."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.models import transformer as T

    batch = _bench_batch(8)
    seq0 = int(os.environ.get('CXXNET_BENCH_SEQ', '128'))
    new_k = _bench_steps(256)
    # exact decode shapes: the K-vs-1 quotient needs each request to cost
    # exactly its own step count — opt out of the generate() size-class
    # bucketing (models/transformer._size_class) so no run is ever
    # rounded up to a larger compiled horizon
    os.environ['CXXNET_GEN_BUCKETS'] = '0'
    cfg = T.TransformerConfig(
        vocab_size=32768, d_model=1024, num_heads=16, d_ff=4096,
        num_stages=8, seq_len=seq0 + new_k, attn='local', causal=True,
        num_microbatches=1, dtype=jnp.bfloat16)
    params = T.init_params(np.random.RandomState(0), cfg)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab_size, (batch, seq0)).astype(np.int32)

    def run(n):
        return np.asarray(T.generate(params, prompt, n, cfg))

    per_tok, t1s = _quotient_per_step(lambda: run(1), lambda: run(new_k),
                                      new_k)
    import statistics
    _emit({
        'metric': 'decode_tokens_per_sec_per_chip',
        'value': round(batch / per_tok, 1),
        'unit': 'tokens/sec',
        'vs_baseline': None,
        'batch': batch,
        'prompt_len': seq0,
        'new_tokens': new_k,
        'per_token_ms': round(per_tok * 1e3, 3),
        'dispatch_ms': round(statistics.median(t1s) * 1e3
                             - per_tok * 1e3, 1),
        'timing': 'KV-cached scan, K-vs-1 new-token quotient',
    })
    return 0


def _pack_synthetic_imgbin(tmp: str, n_images: int):
    """Pack a synthetic JPEG imgbin dataset (the smoke's seeded recipe and
    the in-tree packer); returns (list_path, bin_path)."""
    from chip_smoke import pack_synthetic
    return pack_synthetic(tmp, 'train', n_images, 0)


def _imgbinx_chain(lst: str, binpath: str, batch_size: int,
                   device_normalize: bool = False):
    """The production input chain: two-stage imgbinx reader -> augment
    (rand crop+mirror) -> batch -> background threadbuffer.
    ``device_normalize`` keeps the decoded uint8 on the wire (half the
    H2D bytes, no host-side cast) and defers (x-mean)*scale to the
    jitted step — the TPU-recommended configuration."""
    chain = [('iter', 'imgbinx'),
             ('image_list', lst),
             ('image_bin', binpath),
             ('shuffle', '1'), ('rand_crop', '1'), ('rand_mirror', '1'),
             ('input_shape', '3,227,227'),
             ('batch_size', str(batch_size)),
             ('round_batch', '1'), ('silent', '1')]
    if device_normalize:
        chain.append(('device_normalize', '1'))
    chain.append(('iter', 'threadbuffer'))
    return chain


def _imgbin_aug_chain(lst: str, binpath: str, batch_size: int,
                      nworker: int):
    """The nworker-sweep chain: imgbin + REAL augmentation (affine warp
    via rotation, random crop, mirror) behind a pooled threadbuffer —
    the per-instance work the ``nworker`` pool (utils/parallel_pool.py)
    exists to parallelize."""
    return [('iter', 'imgbin'),
            ('image_list', lst), ('image_bin', binpath),
            ('shuffle', '1'), ('rand_crop', '1'), ('rand_mirror', '1'),
            ('max_rotate_angle', '15'),
            ('input_shape', '3,224,224'),
            ('batch_size', str(batch_size)),
            ('round_batch', '1'), ('silent', '1'),
            ('iter', 'threadbuffer'),
            ('nworker', str(nworker))]


def bench_io() -> int:
    """HOST-side input-pipeline throughput: imgbin pages -> JPEG decode
    -> augment -> batch -> threadbuffer, no device involved (runs
    anywhere, chip or not).  This is the supply side of the e2e number:
    if bench_io < bench_alexnet img/s, the host pipeline is the e2e
    bottleneck (the reference's iter_thread_imbin_x exists for exactly
    that reason).  Counterpart of the reference's ``test_io=1`` harness
    (cxxnet_main.cpp test_io loop).

    Also sweeps ``nworker`` over an AUGMENTED imgbin stream (affine +
    crop + mirror — the decode+augment cost a real training conf pays)
    and reports batches/sec per worker count plus the n=4 pool
    occupancy: the receipt that justifies (or indicts) the parallel
    decode/augment pool on this host."""
    import tempfile

    from cxxnet_tpu.io.data import create_iterator

    batch_size = _bench_batch(256)
    n_images = int(os.environ.get('CXXNET_E2E_IMAGES', '1024'))
    sweep_images = int(os.environ.get('CXXNET_IO_SWEEP_IMAGES', '256'))
    sweep_batch = int(os.environ.get('CXXNET_IO_SWEEP_BATCH', '32'))

    def rate(it, rounds=2):
        it.init()
        for b in it:                 # warm: page cache, buffers, threads
            pass
        n_done, n_batch, t0 = 0, 0, time.perf_counter()
        for _round in range(rounds):
            for b in it:
                n_done += b.batch_size - b.num_batch_padd
                n_batch += 1
        dt = time.perf_counter() - t0
        return n_done, n_done / dt, n_batch / dt

    with tempfile.TemporaryDirectory() as tmp:
        lst, binpath = _pack_synthetic_imgbin(tmp, n_images)
        n_done, ips, _ = rate(
            create_iterator(_imgbinx_chain(lst, binpath, batch_size)))
        # B-side: uint8 wire (device_normalize) — the host skips the
        # f32 convert + normalize, quantifying that stage's share.  A
        # B-side failure must not discard the completed A-side number.
        try:
            _, ips_u8, _ = rate(
                create_iterator(_imgbinx_chain(lst, binpath, batch_size,
                                               device_normalize=True)))
        except Exception as e:              # noqa: BLE001
            ips_u8 = None
            print(f'uint8-wire side failed: {e!r}', file=sys.stderr)

        # nworker sweep on its own (smaller) augmented dataset: the
        # affine warp makes per-instance cost realistic, so the sweep
        # stays minutes-not-hours on the serial leg
        if sweep_images == n_images:
            slst, sbin = lst, binpath
        else:
            sdir = os.path.join(tmp, 'sweep')
            os.makedirs(sdir, exist_ok=True)
            slst, sbin = _pack_synthetic_imgbin(sdir, sweep_images)
        sweep, occupancy = {}, None
        for nw in (1, 2, 4, 8):
            it = create_iterator(_imgbin_aug_chain(slst, sbin,
                                                   sweep_batch, nw))
            _, sips, bps = rate(it)
            sweep[str(nw)] = {'images_per_sec': round(sips, 1),
                              'batches_per_sec': round(bps, 2)}
            stats = it.pipeline_stats()
            if nw == 4 and stats is not None:
                occupancy = round(stats.get('pool.occupancy'), 3)
    speedup = (sweep['4']['batches_per_sec']
               / max(sweep['1']['batches_per_sec'], 1e-9))
    _emit({
        'metric': 'host_io_images_per_sec',
        'value': round(ips, 1),
        'unit': 'images/sec',
        'vs_baseline': None,
        'images': n_done,
        'uint8_wire_images_per_sec':
            round(ips_u8, 1) if ips_u8 else None,
        'nworker_sweep': sweep,
        'sweep_batch': sweep_batch,
        'speedup_4v1': round(speedup, 2),
        'pool_occupancy_nworker4': occupancy,
        'note': 'imgbinx+decode+augment+threadbuffer, host only; '
                'uint8_wire = same chain under device_normalize=1; '
                'nworker_sweep = augmented (affine+crop+mirror) imgbin '
                'through the parallel decode/augment pool',
    })
    return 0


_SCAN_MLP = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 512
  init_sigma = 0.05
layer[+1:ac1] = relu
layer[+1:do1] = dropout
  threshold = 0.3
layer[+1:fc2] = fullc:fc2
  nhidden = 512
  init_sigma = 0.05
layer[+1:ac2] = relu
layer[+1:fc3] = fullc:fc3
  nhidden = 16
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,256
dev = cpu
eta = 0.05
momentum = 0.9
metric[label] = error
eval_train = 0
"""


def bench_scan() -> int:
    """SUPERVISED steps/sec, scanned K-dispatch vs per-step — the receipt
    that the ExecutionPlan refactor (doc/trainer.md) keeps the
    steps_per_dispatch win under production constraints: both legs run
    the REAL supervised loop (TrainSupervisor watchdog ThreadBuffer,
    anchor + final exact-resume checkpoints, divergence gate armed via
    nan_breaker), differing ONLY in the plan's K.  Final params of the
    two legs are bitwise-asserted in-bench, so the speedup can never be
    bought with a semantics drift.  What K recovers is the per-dispatch
    cost; on a CPU correctness run that is a host call only, so a speedup
    of ~1x is expected there and the number is not a chip number."""
    import tempfile

    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.execution import ExecutionPlan
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.runtime.supervisor import (SupervisorConfig,
                                               TrainSupervisor)
    from cxxnet_tpu.utils.config import parse_config_string

    batch_size = _bench_batch(64)
    scan_k = int(os.environ.get('CXXNET_SCAN_K', '4'))
    n_batches = int(os.environ.get('CXXNET_SCAN_BATCHES', '96'))
    # whole windows for a clean A/B, floor of one window (a sub-K request
    # would otherwise round to zero batches and a 0/0 speedup)
    n_batches = max(scan_k, n_batches - n_batches % scan_k)
    conf = _SCAN_MLP + f'batch_size = {batch_size}\n' + _extra_conf()

    rng = np.random.RandomState(0)
    centers = rng.randn(16, 256).astype(np.float32) * 2
    batches = []
    for _ in range(n_batches):
        y = rng.randint(0, 16, batch_size)
        x = centers[y] + 0.3 * rng.randn(batch_size, 256).astype(np.float32)
        batches.append(DataBatch(x.reshape(batch_size, 1, 1, 256),
                                 y[:, None].astype(np.float32)))

    def leg(k, tmp):
        trainer = NetTrainer(parse_config_string(conf))
        trainer.init_model()
        plan = ExecutionPlan.resolve(requested_k=k, strict=True,
                                     silent=True)
        sup = TrainSupervisor(
            trainer, os.path.join(tmp, f'sup_k{k}'),
            SupervisorConfig(batch_deadline=120.0, nan_breaker=3,
                             save_every=0))
        stepper = lambda: plan.round_stepper(trainer, lookahead=0)  # noqa: E731
        factory = lambda s: iter(batches[s % n_batches:])           # noqa: E731
        sup.run(factory, before_step=None, make_stepper=stepper)  # warm
        # min over reps, like _quotient_per_step: scheduler spikes only
        # ever ADD time, so min is the honest steady-state epoch
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            n = sup.run(factory, make_stepper=stepper)
            times.append(time.perf_counter() - t0)
        return n / min(times), trainer

    with tempfile.TemporaryDirectory() as tmp:
        rate_1, t1 = leg(1, tmp)
        rate_k, tk = leg(scan_k, tmp)
    bitwise = all(
        np.array_equal(np.asarray(t1.params[lk][fk]),
                       np.asarray(tk.params[lk][fk]))
        for lk, fields in t1.params.items() for fk in fields)
    if not bitwise:
        raise AssertionError(
            'supervised scanned leg diverged from the per-step leg — '
            'the speedup number would be meaningless')
    import jax
    _emit({
        'metric': 'supervised_scan_steps_per_sec',
        'value': round(rate_k, 1),
        'unit': 'steps/sec',
        'platform': jax.devices()[0].platform,
        'vs_baseline': None,
        'per_step_steps_per_sec': round(rate_1, 1),
        'speedup': round(rate_k / rate_1, 3),
        'k': scan_k,
        'batch': batch_size,
        'steps': n_batches,
        'supervise': 1,
        'bitwise_equal': True,
        'timing': 'min wall over 3 supervised epochs, warm leg discarded',
    })
    return 0


def bench_obs() -> int:
    """Always-on telemetry tax (doc/observability.md): the graftscope
    flight recorder + span instrumentation runs on EVERY production
    path, so its cost must be provably negligible.  Two A/B legs with
    the recorder disabled vs enabled (the only difference — the hub
    object, StatSets, and trace-id counters exist either way):

    * supervised train steps/sec — the real TrainSupervisor loop with
      dispatch/save spans and io.produce events riding each batch,
    * decode tokens/sec — the DecodeService continuous-batching stack
      with per-request lifecycle spans and per-step decode spans.

    Each leg runs back-to-back off/on PAIRS and reports the median of
    per-pair overhead ratios: host noise between bursts spans ±5-10%,
    far above the recorder's true cost, and only the paired ratio
    cancels it.  The decode model is mid-sized (d_model 128) like the
    ``decode`` mode's, not a toy: the span cost is constant per step,
    so a micro model would overstate the relative tax ~10x against any
    production step time.  Acceptance: overhead < 2% on both.  The
    receipt also lands in BENCH_OBS_r01.json.

    A second pass measures graftwatch on top of an enabled recorder:
    sampler-off vs sampler-on (the ``obs.sample_every`` history thread
    at its production-default 0.25s cadence plus two live SLO specs
    evaluated per tick, one plain and one windowed-rate reduction).
    Same paired-ratio discipline, same legs; receipt BENCH_OBS_r02.json,
    acceptance: the history/SLO tax stays below the recorder acceptance
    bar (< 2% on both legs).  ``CXXNET_OBS_SAMPLE_EVERY=0.05`` stresses
    a 5x cadence — measured ~2% on the host decode leg (each 20 Hz tick
    costs ~1ms of GIL against the pure-host token loop; the bounded
    ``tail_view`` read keeps it flat no matter how large the serving
    distributions grow)."""
    import tempfile

    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.models import transformer as T
    from cxxnet_tpu.nnet.execution import ExecutionPlan
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.obs import get_hub
    from cxxnet_tpu.runtime.supervisor import (SupervisorConfig,
                                               TrainSupervisor)
    from cxxnet_tpu.serve.decode import DecodeService
    from cxxnet_tpu.utils.config import parse_config_string

    hub = get_hub()
    batch_size = _bench_batch(64)
    n_batches = int(os.environ.get('CXXNET_OBS_BATCHES', '192'))
    n_req = int(os.environ.get('CXXNET_OBS_REQUESTS', '32'))
    max_new = int(os.environ.get('CXXNET_OBS_MAX_NEW', '48'))
    reps = int(os.environ.get('CXXNET_OBS_REPS', '6'))
    conf = _SCAN_MLP + f'batch_size = {batch_size}\n' + _extra_conf()

    rng = np.random.RandomState(0)
    centers = rng.randn(16, 256).astype(np.float32) * 2
    batches = []
    for _ in range(n_batches):
        y = rng.randint(0, 16, batch_size)
        x = centers[y] + 0.3 * rng.randn(batch_size, 256).astype(np.float32)
        batches.append(DataBatch(x.reshape(batch_size, 1, 1, 256),
                                 y[:, None].astype(np.float32)))

    def make_train(tmp):
        trainer = NetTrainer(parse_config_string(conf))
        trainer.init_model()
        plan = ExecutionPlan.resolve(requested_k=1, silent=True)
        sup = TrainSupervisor(
            trainer, os.path.join(tmp, 'sup'),
            SupervisorConfig(batch_deadline=120.0, nan_breaker=3,
                             save_every=0))
        stepper = lambda: plan.round_stepper(trainer, lookahead=0)  # noqa: E731
        factory = lambda s: iter(batches[s % n_batches:])           # noqa: E731
        sup.run(factory, make_stepper=stepper)            # warm/compile

        def epoch():
            t0 = time.perf_counter()
            n = sup.run(factory, make_stepper=stepper)
            return n / (time.perf_counter() - t0)
        return epoch, sup

    lm_cfg = T.TransformerConfig(vocab_size=512, d_model=128, num_heads=8,
                                 d_ff=256, num_stages=2, seq_len=64,
                                 attn='local')
    lm_params = T.init_params(np.random.RandomState(0), lm_cfg)
    prompt_rng = np.random.RandomState(7)
    prompts = [prompt_rng.randint(
        0, lm_cfg.vocab_size,
        (1, int(prompt_rng.randint(1, 12)))).astype(np.int32)
        for _ in range(n_req)]

    def make_decode():
        svc = DecodeService(lm_params, lm_cfg, slots=4, pages=96,
                            page_size=8, max_prompt=16,
                            max_new_bound=max_new, deadline=240.0)
        svc.generate(prompts[0], max_new)                 # warm/compile

        def burst():
            t0 = time.perf_counter()
            reqs = [svc.submit_async(p, max_new) for p in prompts]
            toks = 0
            for r in reqs:
                svc.batcher.wait(r)
                toks += len(r.tokens)
            return toks / (time.perf_counter() - t0)
        return burst, svc

    import statistics
    samples = {'train': {False: [], True: []},
               'decode': {False: [], True: []}}
    pair_tax = {'train': [], 'decode': []}
    with tempfile.TemporaryDirectory() as tmp:
        train_epoch, sup = make_train(tmp)
        decode_burst, svc = make_decode()
        try:
            # per-leg back-to-back off/on pairs: only the paired ratio
            # cancels slow host drift, so nothing runs inside a pair.
            # Decode measures first and a full collection precedes each
            # leg: the recorder's only indirect cost is extra gc
            # triggers, and their price scales with how much garbage
            # the OTHER leg left behind — that cross-talk is bench
            # artifact, not recorder tax
            import gc
            for leg, run in (('decode', decode_burst),
                             ('train', train_epoch)):
                gc.collect()
                for i in range(reps):
                    # alternate which state runs first within the pair:
                    # the second slot of a pair is systematically a bit
                    # different (heap growth, cache state), and a fixed
                    # order would book that bias to one state
                    order = (False, True) if i % 2 == 0 else (True, False)
                    rate = {}
                    for state in order:
                        hub.enabled = state
                        # max rate of two runs per slot: scheduler
                        # spikes only ever ADD time, so the better of
                        # two is the honest steady-state sample
                        rate[state] = max(run(), run())
                    samples[leg][False].append(rate[False])
                    samples[leg][True].append(rate[True])
                    pair_tax[leg].append(1.0 - rate[True] / rate[False])
        finally:
            hub.enabled = True
            svc.close(30.0)
            sup.close()

    # --- graftwatch leg: sampler+SLO tax over the enabled recorder ---
    from cxxnet_tpu.obs.history import GaugeSampler, hub_source
    from cxxnet_tpu.obs.slo import SLOEngine, SLOSpec
    sample_every = float(os.environ.get('CXXNET_OBS_SAMPLE_EVERY',
                                        '0.25'))
    s_samples = {'train': {False: [], True: []},
                 'decode': {False: [], True: []}}
    s_pair_tax = {'train': [], 'decode': []}
    with tempfile.TemporaryDirectory() as tmp:
        train_epoch, sup = make_train(tmp)
        decode_burst, svc = make_decode()
        hub.enabled = True
        # real gauges for the sampler to chew on each tick
        hub.register_stats('decode', svc.engine.stats)
        try:
            import gc
            for leg, run in (('decode', decode_burst),
                             ('train', train_epoch)):
                gc.collect()
                for i in range(reps):
                    order = (False, True) if i % 2 == 0 else (True, False)
                    rate = {}
                    for state in order:
                        sampler = None
                        if state:
                            sampler = GaugeSampler(hub_source(hub),
                                                   period=sample_every)
                            eng = SLOEngine(sampler.history)
                            eng.add(SLOSpec.parse(
                                'load', 'decode.requests>=0@1'))
                            eng.add(SLOSpec.parse(
                                'ramp', 'decode.requests.rate>=0@1'))
                            sampler.add_listener(eng.on_tick)
                            sampler.start()
                        try:
                            rate[state] = max(run(), run())
                        finally:
                            if sampler is not None:
                                sampler.close(10.0)
                    s_samples[leg][False].append(rate[False])
                    s_samples[leg][True].append(rate[True])
                    s_pair_tax[leg].append(1.0 - rate[True] / rate[False])
        finally:
            hub.unregister_stats('decode')
            svc.close(30.0)
            sup.close()

    # --- graftprof leg: program-ledger + sentinel tax ----------------
    # off = the ledger's trace-time hook suppressed (set_raw_jit — the
    # dispatch is the plain jit C++ fast path either way), on = the
    # shipped wrap.  Both paths are warmed before pairing so neither
    # leg ever measures a compile.
    from cxxnet_tpu.obs.programs import set_raw_jit
    l_samples = {'train': {False: [], True: []},
                 'decode': {False: [], True: []}}
    l_pair_tax = {'train': [], 'decode': []}
    with tempfile.TemporaryDirectory() as tmp:
        train_epoch, sup = make_train(tmp)
        decode_burst, svc = make_decode()
        hub.enabled = True
        try:
            import gc
            for leg, run in (('decode', decode_burst),
                             ('train', train_epoch)):
                set_raw_jit(True)        # warm the plain-jit twin cache
                run()
                set_raw_jit(False)
                gc.collect()
                for i in range(reps):
                    order = (False, True) if i % 2 == 0 else (True, False)
                    rate = {}
                    for state in order:
                        # state True = ledger wrap ON (the shipped path).
                        # best-of-3 per slot (vs the other passes'
                        # best-of-2): the ledger's true per-dispatch
                        # cost is ~µs against a multi-ms step — an
                        # order of magnitude under the recorder/sampler
                        # taxes — so only the min-wall discipline of
                        # _quotient_per_step keeps scheduler spikes
                        # from swamping it
                        set_raw_jit(not state)
                        try:
                            rate[state] = max(run(), run(), run())
                        finally:
                            set_raw_jit(False)
                    l_samples[leg][False].append(rate[False])
                    l_samples[leg][True].append(rate[True])
                    l_pair_tax[leg].append(1.0 - rate[True] / rate[False])
        finally:
            set_raw_jit(False)
            svc.close(30.0)
            sup.close()

    # direct per-dispatch wrapper cost: the A/B above runs minute-long
    # loops whose run-to-run spread on a shared host is ±5-15% — it can
    # corroborate "no systemic tax rides along" but cannot RESOLVE a
    # µs-scale dispatch delta.  So measure the delta directly: a tiny
    # program behind a conservatively deep pytree (the signature walk
    # is the wrapper's only per-call work and scales with leaf count),
    # wrapped vs raw, median of trials, then convert through each
    # leg's measured step/token wall into the implied steady-state tax.
    # A throwaway ledger keeps the micro program out of /programs.
    import jax.numpy as jnp
    from cxxnet_tpu.obs.programs import (ProgramLedger, get_ledger,
                                         install_ledger)
    micro_led = ProgramLedger()
    prev_led = install_ledger(micro_led)
    try:
        mprog = micro_led.program('bench.micro')
    finally:
        install_ledger(prev_led)
    mtree = {f'l{i}': {'w': jnp.ones((64, 64)), 'b': jnp.ones((64,))}
             for i in range(50)}         # 100 leaves: deeper than any
                                         # real step's dispatch tree
    mwrap = mprog.jit(lambda tree, x: x + tree['l0']['b'][0])
    set_raw_jit(True)
    mwrap(mtree, 0.0).block_until_ready()
    set_raw_jit(False)
    mwrap(mtree, 0.0).block_until_ready()

    def _per_call_us(raw: bool, n: int = 3000) -> float:
        set_raw_jit(raw)
        try:
            t0 = time.perf_counter()
            r = None
            for _ in range(n):
                r = mwrap(mtree, 0.0)
            r.block_until_ready()
            return (time.perf_counter() - t0) / n * 1e6
        finally:
            set_raw_jit(False)
    deltas = sorted(_per_call_us(False) - _per_call_us(True)
                    for _ in range(7))
    wrap_delta_us = max(0.0, deltas[len(deltas) // 2])

    rates = {leg: {st: statistics.median(v) for st, v in legs.items()}
             for leg, legs in samples.items()}
    s_rates = {leg: {st: statistics.median(v) for st, v in legs.items()}
               for leg, legs in s_samples.items()}
    l_rates = {leg: {st: statistics.median(v) for st, v in legs.items()}
               for leg, legs in l_samples.items()}

    def tax(leg):
        return round(statistics.median(pair_tax[leg]), 4)

    def s_tax(leg):
        return round(statistics.median(s_pair_tax[leg]), 4)

    def l_tax(leg):
        return round(statistics.median(l_pair_tax[leg]), 4)

    import jax
    plat = jax.devices()[0].platform
    # implied steady-state tax per leg: measured per-dispatch delta
    # over each leg's measured per-step / per-token wall.  One dispatch
    # per train step and per decode token is CONSERVATIVE (a K-scanned
    # window dispatches once per K steps; one decode step emits up to
    # `slots` tokens), so the true tax is at or below these
    train_ms = 1e3 / max(l_rates['train'][True], 1e-9)
    tok_ms = 1e3 / max(l_rates['decode'][True], 1e-9)
    implied_train = wrap_delta_us / 1e3 / train_ms
    implied_decode = wrap_delta_us / 1e3 / tok_ms
    ledger_payload = {
        'metric': 'obs_ledger_overhead',
        'value': round(max(implied_train, implied_decode), 5),
        'unit': 'fraction',
        'platform': plat,
        'vs_baseline': None,
        'wrap_dispatch_delta_us': round(wrap_delta_us, 2),
        'train_implied_tax': round(implied_train, 5),
        'decode_implied_tax': round(implied_decode, 5),
        'programs': _program_summary(),
        'train_steps_per_sec_ledger_on': round(l_rates['train'][True], 1),
        'train_steps_per_sec_ledger_off': round(l_rates['train'][False],
                                                1),
        'train_overhead': l_tax('train'),
        'train_tax_pairs': [round(t, 4) for t in l_pair_tax['train']],
        'decode_tokens_per_sec_ledger_on': round(
            l_rates['decode'][True], 1),
        'decode_tokens_per_sec_ledger_off': round(
            l_rates['decode'][False], 1),
        'decode_overhead': l_tax('decode'),
        'decode_tax_pairs': [round(t, 4) for t in l_pair_tax['decode']],
        'acceptance': 'implied steady-state tax < 0.002 on both legs; '
                      'A/B pair medians within the host noise band the '
                      'enclosed pairs demonstrate',
        'receipt_file': 'BENCH_OBS_r03.json',
        'timing': 'headline value = measured per-dispatch wrapper '
                  'delta (tiny program behind a 100-leaf pytree — '
                  'deeper than any real step\'s dispatch tree — the '
                  'shipped wrap vs the hook-suppressed set_raw_jit '
                  'twin; dispatch is the plain jit C++ fast path '
                  'either way, so the delta is one Python frame + the '
                  'flag check; median of 7 trials of 3000 calls) '
                  'divided by each leg\'s measured per-step / '
                  'per-token wall, one dispatch per step/token '
                  'assumed (conservative: scanned windows and '
                  'multi-slot decode dispatch less often).  '
                  f'Corroboration: median of {reps} back-to-back '
                  'off/on pair ratios per leg, best-of-3 runs per slot '
                  '(min-wall), both paths warmed — the end-to-end A/B '
                  'cannot resolve a µs-scale delta through minute-long '
                  'loops on a shared host (the enclosed pairs span the '
                  'noise band) but holds the line against any '
                  'systemic tax.  Compiler truth is harvested at '
                  'trace time + lazy AOT analysis on read, so '
                  'steady-state tax is the wrapper frame alone',
    }
    _write_receipt_file(ledger_payload)
    _emit(ledger_payload)
    sampler_payload = {
        'metric': 'obs_sampler_overhead',
        'value': max(0.0, s_tax('train'), s_tax('decode')),
        'unit': 'fraction',
        'platform': plat,
        'vs_baseline': None,
        'sample_every_s': sample_every,
        'slo_specs': 2,
        'train_steps_per_sec_sampler_on': round(s_rates['train'][True],
                                                1),
        'train_steps_per_sec_sampler_off': round(s_rates['train'][False],
                                                 1),
        'train_overhead': s_tax('train'),
        'train_tax_pairs': [round(t, 4) for t in s_pair_tax['train']],
        'decode_tokens_per_sec_sampler_on': round(
            s_rates['decode'][True], 1),
        'decode_tokens_per_sec_sampler_off': round(
            s_rates['decode'][False], 1),
        'decode_overhead': s_tax('decode'),
        'decode_tax_pairs': [round(t, 4) for t in s_pair_tax['decode']],
        'acceptance': 'overhead < 0.02 on both legs',
        'receipt_file': 'BENCH_OBS_r02.json',
        'timing': f'median of {reps} back-to-back off/on pair ratios '
                  'per leg over an ENABLED recorder; sampler at '
                  f'{sample_every:g}s (the production default) with two '
                  'SLO specs evaluated per tick; negative = below this '
                  'host\'s noise floor',
    }
    _write_receipt_file(sampler_payload)
    _emit(sampler_payload)
    payload = {
        'metric': 'obs_recorder_overhead',
        # a negative per-leg reading means the recorder's cost is below
        # this machine's run-to-run noise floor; the headline is the
        # worst leg clamped at 0 (the raw legs stay in the receipt)
        'value': max(0.0, tax('train'), tax('decode')),
        'unit': 'fraction',
        'platform': plat,
        'vs_baseline': None,
        'train_steps_per_sec_recorder_on': round(rates['train'][True], 1),
        'train_steps_per_sec_recorder_off': round(rates['train'][False], 1),
        'train_overhead': tax('train'),
        'train_tax_pairs': [round(t, 4) for t in pair_tax['train']],
        'decode_tokens_per_sec_recorder_on': round(rates['decode'][True],
                                                   1),
        'decode_tokens_per_sec_recorder_off': round(rates['decode'][False],
                                                    1),
        'decode_overhead': tax('decode'),
        'decode_tax_pairs': [round(t, 4) for t in pair_tax['decode']],
        'acceptance': 'overhead < 0.02 on both legs',
        'batch': batch_size,
        'steps': n_batches,
        'requests': n_req,
        'max_new': max_new,
        'receipt_file': 'BENCH_OBS_r01.json',
        'timing': f'median of {reps} back-to-back off/on pair ratios '
                  'per leg, warm leg discarded; negative = below this '
                  'host\'s noise floor',
    }
    _write_receipt_file(payload)
    _emit(payload)
    return 0


def _write_receipt_file(payload: dict) -> None:
    """Commit a mode's receipt next to the ledger files (the
    ``receipt_file`` key names it)."""
    name = payload.get('receipt_file')
    if not name:
        return
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, 'w') as f:
        json.dump(payload, f, indent=1)
        f.write('\n')


def _q_ms(tracker, name: str, q: float):
    """A tracker quantile in ms, or None when unmeasured — the receipt
    must stay strict JSON (NaN is not)."""
    v = tracker.stats.quantile(name, q)
    return None if v != v else round(v * 1e3, 2)


def bench_online() -> int:
    """Train-while-serve ledger (doc/online.md): the FULL OnlinePipeline —
    supervised trainer publishing a serving checkpoint every
    ``save_every`` steps, colocated engine/batcher/registry hot-swapping
    them under constant-rate traffic — against a train-only supervised
    twin differing ONLY in the serving stack being absent.  Reports
    steps/sec while serving, the serving tax (ratio vs train-only),
    freshness/swap-lag p50/p99, swap count, and the zero-drop counter.
    On CPU the two tasks share cores, so the tax reads high; on a real
    chip the serve forwards interleave into trainer bubbles."""
    import tempfile

    from cxxnet_tpu.io.data import DataBatch, IIterator
    from cxxnet_tpu.nnet.execution import ExecutionPlan
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.online import OnlineConfig, OnlinePipeline
    from cxxnet_tpu.runtime.supervisor import (SupervisorConfig,
                                               TrainSupervisor)
    from cxxnet_tpu.utils.config import parse_config_string

    batch_size = _bench_batch(64)
    n_batches = int(os.environ.get('CXXNET_ONLINE_BATCHES', '96'))
    save_every = int(os.environ.get('CXXNET_ONLINE_SAVE_EVERY', '16'))
    rounds = int(os.environ.get('CXXNET_ONLINE_ROUNDS', '3'))
    conf = _SCAN_MLP + f'batch_size = {batch_size}\n' + _extra_conf()

    rng = np.random.RandomState(0)
    centers = rng.randn(16, 256).astype(np.float32) * 2
    batches = []
    for _ in range(n_batches):
        y = rng.randint(0, 16, batch_size)
        x = centers[y] + 0.3 * rng.randn(batch_size, 256).astype(np.float32)
        batches.append(DataBatch(x.reshape(batch_size, 1, 1, 256),
                                 y[:, None].astype(np.float32)))

    class ListIter(IIterator):
        def __iter__(self):
            return iter(batches)

    def request_rows():
        y = rng.randint(0, 16, 8)
        return (centers[y]
                + 0.3 * rng.randn(8, 256).astype(np.float32)
                ).reshape(8, 1, 1, 256)

    # train-only twin: same supervised loop, no serving stack
    def train_only(tmp):
        trainer = NetTrainer(parse_config_string(conf))
        trainer.init_model()
        plan = ExecutionPlan.resolve(requested_k=1, silent=True)
        sup = TrainSupervisor(
            trainer, os.path.join(tmp, 'train_only'),
            SupervisorConfig(batch_deadline=120.0, nan_breaker=3,
                             save_every=save_every, save_async=1))
        factory = lambda s: iter(batches[s % n_batches:])   # noqa: E731
        sup.run(factory,
                make_stepper=lambda: plan.round_stepper(trainer,
                                                        lookahead=0))
        t0 = time.perf_counter()
        n = 0
        for _ in range(rounds):
            n += sup.run(factory,
                         make_stepper=lambda: plan.round_stepper(
                             trainer, lookahead=0))
        sup.close()
        return n / (time.perf_counter() - t0)

    with tempfile.TemporaryDirectory() as tmp:
        rate_train_only = train_only(tmp)
        trainer = NetTrainer(parse_config_string(conf))
        trainer.init_model()
        pipe = OnlinePipeline(
            trainer, ListIter(),
            lambda: NetTrainer(parse_config_string(
                conf + 'inference_only = 1\n')),
            OnlineConfig(model_dir=os.path.join(tmp, 'online'),
                         save_every=save_every, reload_poll=0.02,
                         buckets=(8,), qps=100.0,
                         watchdog_deadline=120.0, silent=True),
            request_source=request_rows)
        import io as _io
        sink = _io.StringIO()
        try:
            warm = pipe.run(num_rounds=1, out=sink)
            # scope every receipt field to the measured window: drop the
            # warm round's freshness/lag samples and snapshot its counts
            # so the reported swaps/served/dropped are deltas
            pipe.tracker.stats.clear()
            t0 = time.perf_counter()
            summary = pipe.run(num_rounds=rounds, start_round=2, out=sink)
            wall = time.perf_counter() - t0
        finally:
            pipe.close(timeout=30.0)
    steps = rounds * n_batches
    rate = steps / wall
    tr = pipe.tracker
    import jax
    _emit({
        'metric': 'online_steps_per_sec_while_serving',
        'value': round(rate, 1),
        'unit': 'steps/sec',
        'platform': jax.devices()[0].platform,
        'vs_baseline': None,
        'train_only_steps_per_sec': round(rate_train_only, 1),
        'serving_tax': round(1.0 - rate / rate_train_only, 3),
        'freshness_p50_ms': _q_ms(tr, 'freshness_s', 0.5),
        'freshness_p99_ms': _q_ms(tr, 'freshness_s', 0.99),
        'swap_lag_p50_ms': _q_ms(tr, 'swap_lag_s', 0.5),
        'swaps': summary['swaps'] - warm['swaps'],
        'served': summary['served'] - warm['served'],
        'dropped': summary['dropped'] - warm['dropped'],
        'slo_breaches': summary['slo_breaches'] - warm['slo_breaches'],
        'save_every': save_every,
        'batch': batch_size,
        'steps': steps,
        'rounds': rounds,
        'timing': f'wall over {rounds} supervised epochs under traffic; '
                  'warm epoch excluded from every field',
    })
    return 0


def bench_e2e_alexnet() -> int:
    """END-TO-END AlexNet throughput: the real CLI training-loop path —
    imgbin pages -> native/PIL JPEG decode -> augment (crop+mirror) ->
    threadbuffer -> trainer.update (H2D *included*) — on synthetic data
    packed with the in-tree im2bin.  This is the number to read next to
    the device-only ``alexnet`` mode; the JSON carries both plus the
    measured host-link bandwidth so the gap is attributable.
    """
    import tempfile

    import jax

    from cxxnet_tpu.io.data import create_iterator
    from cxxnet_tpu.models import alexnet_conf
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    batch_size = _bench_batch(256)
    n_images = int(os.environ.get('CXXNET_E2E_IMAGES', '1024'))

    with tempfile.TemporaryDirectory() as tmp:
        lst, binpath = _pack_synthetic_imgbin(tmp, n_images)

        conf = alexnet_conf() + f"""
batch_size = {batch_size}
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
compute_type = bfloat16
""" + _extra_conf()
        trainer = NetTrainer(parse_config_string(conf))
        trainer.init_model()
        # default: uint8 on the wire + device-side normalize (half the
        # H2D bytes, no per-batch host ml_dtypes cast); set
        # CXXNET_E2E_DEVNORM=0 to A/B the host-normalized f32/bf16 path
        dev_norm = os.environ.get('CXXNET_E2E_DEVNORM', '1') == '1'
        it = create_iterator(_imgbinx_chain(lst, binpath, batch_size,
                                            device_normalize=dev_norm))
        it.init()

        # round 0: compile + pipeline warmup (untimed)
        for b in it:
            trainer.update(b)
        jax.device_get(trainer.params['16']['bias'])

        # measure the host link once (what a production PCIe host hides);
        # probe matches the wire dtype (uint8 under device_normalize,
        # else pre-cast bf16) so the window is transfer, not host cast
        import ml_dtypes
        wire_dtype = np.uint8 if dev_norm else ml_dtypes.bfloat16
        probe = np.zeros((batch_size, 3, 227, 227), wire_dtype)
        fetch_first = jax.jit(lambda t: t.ravel()[0])

        def _put_synced(x):
            # a 1-element fetch is the completion barrier
            np.asarray(fetch_first(trainer._shard_batch(x)))

        _put_synced(probe)                               # warm both paths
        t0 = time.perf_counter()
        _put_synced(probe)
        link_s = time.perf_counter() - t0
        link_mb = probe.nbytes / 1e6          # wire bytes (uint8 or bf16)

        # production path: one-batch lookahead (stage i+1 before stepping
        # i) so the host link overlaps device compute — same loop shape as
        # main.py:_train_rounds
        n_done, t0, pending = 0, time.perf_counter(), None
        for _round in range(2):
            for b in it:
                staged = trainer.stage_batch(b)
                if pending is not None:
                    trainer.update_staged(pending)
                pending = staged
                n_done += b.batch_size - b.num_batch_padd
        if pending is not None:
            trainer.update_staged(pending)
        jax.device_get(trainer.params['16']['bias'])
        dt = time.perf_counter() - t0

    ips = n_done / dt
    _emit({
        'metric': 'alexnet_e2e_images_per_sec_per_chip',
        'value': round(ips, 1),
        'unit': 'images/sec',
        'vs_baseline': round(ips / BASELINE_IMAGES_PER_SEC, 3),
        'host_link_mb_per_s': round(link_mb / link_s, 1),
        'batch_h2d_mb': round(link_mb, 1),
    })
    return 0


# --- MNIST time-to-accuracy ------------------------------------------------

_MNIST_FILES = ('train-images-idx3-ubyte.gz', 'train-labels-idx1-ubyte.gz',
                't10k-images-idx3-ubyte.gz', 't10k-labels-idx1-ubyte.gz')
_MNIST_URL = 'https://storage.googleapis.com/cvdf-datasets/mnist/'


def _read_idx(path: str) -> np.ndarray:
    with gzip.open(path, 'rb') as f:
        magic, = struct.unpack('>i', f.read(4))
        ndim = magic & 0xff
        dims = struct.unpack('>' + 'i' * ndim, f.read(4 * ndim))
        return np.frombuffer(f.read(), np.uint8).reshape(dims)


def _locate_mnist() -> str | None:
    """Find (or fetch) REAL MNIST; None -> caller uses the surrogate."""
    ddir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'example', 'MNIST', 'data')
    def complete() -> bool:
        try:
            return all(os.path.exists(os.path.join(ddir, f))
                       for f in _MNIST_FILES) and \
                _read_idx(os.path.join(ddir, _MNIST_FILES[0])).shape[0] >= 60000
        except Exception:
            return False
    if complete():
        return ddir
    os.makedirs(ddir, exist_ok=True)
    try:
        import urllib.request
        for f in _MNIST_FILES:
            dst = os.path.join(ddir, f)
            if not os.path.exists(dst):
                # bounded timeout (silent-drop egress filters would hang
                # forever) + atomic rename (a truncated file would lock
                # every later run into the surrogate path)
                with urllib.request.urlopen(_MNIST_URL + f,
                                            timeout=30) as r, \
                        open(dst + '.part', 'wb') as w:
                    while True:
                        chunk = r.read(1 << 20)
                        if not chunk:
                            break
                        w.write(chunk)
                os.replace(dst + '.part', dst)
        if complete():
            return ddir
    except Exception:
        pass
    return None


_MNIST_CONV_NET = """
netconfig=start
layer[+1:cv1] = conv:cv1
  kernel_size = 5
  pad = 2
  nchannel = 32
layer[+1:ac1] = relu
layer[+1:mp1] = max_pooling
  kernel_size = 2
  stride = 2
layer[+1:cv2] = conv:cv2
  kernel_size = 5
  pad = 2
  nchannel = 64
layer[+1:ac2] = relu
layer[+1:mp2] = max_pooling
  kernel_size = 2
  stride = 2
layer[+1:fl] = flatten
layer[+1:fc1] = fullc:fc1
  nhidden = 256
layer[+1:ac3] = relu
layer[+1:fc2] = fullc:fc2
  nhidden = 10
layer[+0] = softmax
netconfig=end
input_shape = 1,28,28
batch_size = 100
random_type = xavier
eta = 0.05
momentum = 0.9
wd = 0.0
metric = error
eval_train = 0
"""


def bench_mnist_tta() -> int:
    """Wall-clock (incl. compile) to 2% test error on REAL MNIST with a
    LeNet-style conv net, through the framework's own data+trainer path.
    Falls back to the quadrant-blob surrogate (MNIST shapes, MLP) when the
    real data is absent and cannot be fetched; the JSON says which ran."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string

    ddir = _locate_mnist()
    if ddir is None:
        return _mnist_tta_surrogate()

    imgs = _read_idx(os.path.join(ddir, _MNIST_FILES[0]))
    labels = _read_idx(os.path.join(ddir, _MNIST_FILES[1]))
    timgs = _read_idx(os.path.join(ddir, _MNIST_FILES[2]))
    tlabels = _read_idx(os.path.join(ddir, _MNIST_FILES[3]))

    # normalize once, outside the timed loop; rounds only reshuffle indices
    imgs_f = (imgs.astype(np.float32) / 255.0)[:, None]
    labels_f = labels.astype(np.float32).reshape(-1, 1)
    timgs_f = (timgs.astype(np.float32) / 255.0)[:, None]
    tlabels_f = tlabels.astype(np.float32).reshape(-1, 1)

    def batches(x, y, bs, rng=None):
        idx = np.arange(len(x))
        if rng is not None:
            rng.shuffle(idx)
        return [DataBatch(x[idx[i:i + bs]], y[idx[i:i + bs]])
                for i in range(0, len(idx) - bs + 1, bs)]

    trainer = NetTrainer(parse_config_string(_MNIST_CONV_NET))
    trainer.init_model()
    rng = np.random.RandomState(0)
    test = batches(timgs_f, tlabels_f, 100)

    t0 = time.perf_counter()
    err, rounds = 1.0, 0
    first_update_sec = first_eval_sec = None
    while err > 0.02 and rounds < 15:
        trainer.start_round(rounds)
        for b in batches(imgs_f, labels_f, 100, rng):
            tu0 = time.perf_counter()
            trainer.update(b)
            if first_update_sec is None:
                # jit tracing+compile happens synchronously inside the
                # first call: this split separates one-time compile from
                # training in the wall number (the reference's ~30s CPU
                # baseline had no compile component)
                first_update_sec = time.perf_counter() - tu0
        te0 = time.perf_counter()
        res = trainer.evaluate(iter(test), 'test')
        if first_eval_sec is None:
            first_eval_sec = time.perf_counter() - te0
        err = float(res.split(':')[-1])
        rounds += 1
    dt = time.perf_counter() - t0
    _emit({
        'metric': 'mnist_time_to_2pct_error',
        'value': round(dt, 2),
        'unit': 'sec',
        'vs_baseline': round(BASELINE_MNIST_TTA_SEC / dt, 3),
        'data': 'mnist',
        'rounds': rounds,
        'final_error': round(err, 4),
        'compile_split_sec': {'first_update': round(first_update_sec, 2),
                              'first_eval': round(first_eval_sec, 2)},
    })
    return 0 if err <= 0.02 else 1


def _mnist_tta_surrogate() -> int:
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.models import mlp_conf
    from cxxnet_tpu.utils.config import parse_config_string

    conf = mlp_conf() + """
batch_size = 100
eta = 0.1
momentum = 0.9
metric = error
eval_train = 0
"""
    trainer = NetTrainer(parse_config_string(conf))
    trainer.init_model()
    rng = np.random.RandomState(0)

    def blobs(n):
        y = rng.randint(0, 10, n)
        x = np.zeros((n, 784), np.float32)
        for i, c in enumerate(y):
            x[i, c * 78:(c + 1) * 78] = rng.rand(78)
        return x.reshape(n, 1, 1, 784), y.astype(np.float32).reshape(-1, 1)

    train = [DataBatch(*blobs(100)) for _ in range(60)]
    test = [DataBatch(*blobs(100)) for _ in range(10)]
    t0 = time.perf_counter()
    err, rounds = 1.0, 0
    first_update_sec = first_eval_sec = None
    while err > 0.02 and rounds < 15:
        trainer.start_round(rounds)
        for b in train:
            tu0 = time.perf_counter()
            trainer.update(b)
            if first_update_sec is None:
                first_update_sec = time.perf_counter() - tu0
        te0 = time.perf_counter()
        res = trainer.evaluate(iter(test), 'test')
        if first_eval_sec is None:
            first_eval_sec = time.perf_counter() - te0
        err = float(res.split(':')[-1])
        rounds += 1
    dt = time.perf_counter() - t0
    _emit({
        'metric': 'mnist_time_to_2pct_error',
        'value': round(dt, 2),
        'unit': 'sec',
        'vs_baseline': round(BASELINE_MNIST_TTA_SEC / dt, 3),
        'data': 'surrogate',
        'rounds': rounds,
        'final_error': round(err, 4),
        'compile_split_sec': {'first_update': round(first_update_sec, 2),
                              'first_eval': round(first_eval_sec, 2)},
    })
    return 0 if err <= 0.02 else 1


_CNN_FUSED_CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->1] = relu
layer[1->2] = max_pooling
  kernel_size = 2
  stride = 2
layer[2->3] = conv:c2
  kernel_size = 3
  pad = 1
  nchannel = 16
layer[3->3] = relu
layer[3->4] = flatten
layer[4->5] = fullc:fc1
  nhidden = 10
layer[5->6] = softmax
netconfig = end

input_shape = 3,12,12
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
"""

# the fold leg's topology: conv+BN stacks, the shape serve.fold_bn
# rewrites (doc/kernels.md "Inference conv+BN folding")
_CNN_FOLD_CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->2] = batch_norm:bn1
layer[2->3] = relu
layer[3->4] = conv:c2
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = 16
layer[4->5] = batch_norm:bn2
layer[5->6] = relu
layer[6->7] = flatten
layer[7->8] = fullc:fc1
  nhidden = 10
layer[8->9] = softmax
netconfig = end

input_shape = 3,12,12
random_type = xavier
"""


def bench_cnn_fused() -> int:
    """graftfuse A/B (doc/kernels.md), two legs in ONE receipt:

    * **inference** — a real ``PredictEngine`` with ``fold_bn=1``
      (conv+BN folded at build time, nnet/fold.py) vs the unfolded
      engine, rows/sec; scores twin-asserted within the fold pass's
      pinned tolerance, ``fold_view`` stamped;
    * **micro_batch sweep** — μ-cuDNN-style conv microbatching at every
      declared split: steps/sec AND the ``train.step`` program's
      ledger ``peak_bytes`` (compiler truth, obs/programs.py) per
      split, with final params bitwise-asserted against the unsplit
      trainer — the split bounds peak HBM, it never changes the math.

    On a cpu host the twins are real correctness proofs, the speedups
    are not chip numbers (the receipt's ``platform`` stamp says which).
    """
    import jax

    from cxxnet_tpu.nnet.fold import FOLD_ATOL, FOLD_RTOL
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.obs.programs import get_ledger
    from cxxnet_tpu.serve.engine import PredictEngine
    from cxxnet_tpu.utils.config import parse_config_string

    plat = jax.devices()[0].platform
    led = get_ledger()
    batch = _bench_batch(8)
    steps = _bench_steps(6)
    rng = np.random.RandomState(0)
    data = rng.randn(batch, 3, 12, 12).astype(np.float32)
    label = rng.randint(0, 10, (batch, 1)).astype(np.float32)

    def make(extra: str) -> NetTrainer:
        tr = NetTrainer(parse_config_string(
            _CNN_FUSED_CONF + f'batch_size = {batch}\n'
            + extra + _extra_conf()))
        tr.init_model()
        return tr

    def train_steps(tr: NetTrainer, n: int) -> None:
        d = tr._shard_batch(data)
        lb = tr._shard_batch(label, cast=False)
        for _ in range(n):
            tr.update_on_device(d, lb)

    def steps_per_sec(tr: NetTrainer) -> float:
        dstack = tr.shard_batch_stack(np.stack([data, data]))
        lstack = tr.shard_batch_stack(np.stack([label, label]),
                                      cast=False)
        m1 = tr.compile_multi_step(1)
        mk = tr.compile_multi_step(steps)

        def run(fn, n):
            return float(np.asarray(
                tr.update_n_on_device(fn, dstack, lstack, n)))

        per_step, _ = _quotient_per_step(
            lambda: run(m1, 1), lambda: run(mk, steps), steps)
        return 1.0 / per_step

    # ---- leg 1: conv+BN folded vs plain inference ------------------------
    calib = rng.randn(batch, 3, 12, 12).astype(np.float32)
    srv = NetTrainer(parse_config_string(
        _CNN_FOLD_CONF + f'batch_size = {batch}\n' + _extra_conf()))
    srv.init_model()
    eng_plain = PredictEngine(srv, (batch,))
    eng_fold = PredictEngine(srv, (batch,), fold_bn=1, fold_batch=calib)
    fold_view = eng_fold.fold_view()
    if not fold_view or not fold_view.get('pairs'):
        raise AssertionError('fold_bn=1 planned no conv+BN pairs')
    # the twin is the fold pass's pinned contract: equality ON the
    # calibration batch (BN here uses incoming-batch statistics even at
    # eval — the reference quirk — so the frozen-stats fold is exact
    # only where its statistics came from; doc/kernels.md)
    q = calib
    s_plain = eng_plain.predict_scores(q)
    s_fold = eng_fold.predict_scores(q)
    infer_err = float(np.max(np.abs(s_fold - s_plain)))
    infer_twin = bool(np.allclose(s_fold, s_plain,
                                  rtol=FOLD_RTOL, atol=FOLD_ATOL))
    if not infer_twin:
        raise AssertionError(
            f'folded engine diverged from unfolded: score maxerr '
            f'{infer_err}')

    def rows_per_sec(eng) -> float:
        reps = max(4, steps)
        eng.predict_scores(q)            # compile + warm
        walls = []
        for _ in range(4):
            t0 = time.perf_counter()
            for _ in range(reps):
                eng.predict_scores(q)
            walls.append(time.perf_counter() - t0)
        return batch * reps / min(walls)

    rows_fold = rows_per_sec(eng_fold)
    rows_plain = rows_per_sec(eng_plain)
    infer_speedup = rows_fold / rows_plain

    # ---- leg 2: micro_batch sweep ----------------------------------------
    splits = [s for s in (1, 2, 4, 8) if batch % s == 0]
    sweep, base_snap = [], None

    def snap(tr: NetTrainer) -> dict:
        # a host copy taken BEFORE the timing loop advances the trainer
        return {lk: {f: np.asarray(v, np.float32)
                     for f, v in fields.items()}
                for lk, fields in tr.params.items()}

    for split in splits:
        tr = make(f'micro_batch = {split}\n')
        train_steps(tr, 3)
        if split == splits[0]:
            base_snap, mb_err = snap(tr), 0.0
        else:
            mb_err = max(float(np.max(np.abs(
                np.asarray(tr.params[lk][f], np.float32)
                - base_snap[lk][f])))
                for lk in base_snap for f in base_snap[lk])
            if mb_err != 0.0:
                raise AssertionError(
                    f'micro_batch={split} step diverged from unsplit: '
                    f'param maxerr {mb_err}')
        # compiler truth: THIS trainer's train.step entry (full #N name
        # — base-name matching would conflate the sweep's instances)
        entries = led.entries_for(tr._prog_step.name)
        peak = max((int(e.peak_bytes) for e in entries), default=0)
        sweep.append({'micro_batch': split,
                      'steps_per_sec': round(steps_per_sec(tr), 2),
                      'peak_bytes': peak,
                      'bitwise_equal_to_unsplit': True})
    peaks = [r['peak_bytes'] for r in sweep]

    payload = {
        'metric': 'cnn_fused_speedup',
        'value': round(infer_speedup, 4),
        'unit': 'x',
        'platform': plat,
        'vs_baseline': None,
        'inference': {
            'speedup': round(infer_speedup, 4),
            'folded_rows_per_sec': round(rows_fold, 2),
            'plain_rows_per_sec': round(rows_plain, 2),
            'fold_view': fold_view,
            'twin_ok': infer_twin,
            'score_max_abs_err': infer_err,
            'rtol': FOLD_RTOL, 'atol': FOLD_ATOL,
        },
        'micro_batch': {
            'sweep': sweep,
            'peak_bytes_monotone': bool(
                all(a >= b for a, b in zip(peaks, peaks[1:]))),
        },
        'batch': batch,
        'programs': _program_summary(),
        'receipt_file': 'BENCH_CNN_r01.json',
        'timing': 'train legs scan-in-jit K-vs-1 quotient; inference '
                  'legs best-of-4 wall; every A/B twin-asserted in-bench',
    }
    _write_receipt_file(payload)
    _emit(payload)
    return 0


def bench_autotune() -> int:
    """grafttune A/B (doc/autotune.md): run the two-stage search on TWO
    bench modes — the supervised train scan and serve decode — then
    re-measure the tuned config against the hand-tuned default with
    fresh state, so the headline speedup is an independent measurement,
    not the search's own probe replayed.  The receipt stamps the full
    search story (declared budget vs wall, stage-1 ledger pruning
    counts, every probe) plus an in-receipt recompile-storm-guard
    drill: an online TuneController driven through a verdict sequence
    that would thrash a bucket ladder, against a ledger program with a
    tight ``obs.recompile`` bound — green means zero
    ``RecompileStormError`` records and total compiles under both the
    program's bound and the space's declared compile budget."""
    import jax

    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.models import transformer as TT
    from cxxnet_tpu.nnet import execution
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.obs.programs import get_ledger
    from cxxnet_tpu.runtime import faults
    from cxxnet_tpu.serve.decode import DecodeService
    from cxxnet_tpu.tune import (LedgerGate, TuneController, TuneSearch,
                                 TuneSpace)
    from cxxnet_tpu.utils.config import parse_config_string

    plat = jax.devices()[0].platform
    led = get_ledger()

    # ---- leg 1: train scan (steps_per_dispatch) --------------------------
    batch_size = _bench_batch(32)
    n_probe = int(os.environ.get('CXXNET_TUNE_PROBE_STEPS', '32'))
    conf = _SCAN_MLP + f'batch_size = {batch_size}\n' + _extra_conf()
    rng = np.random.RandomState(0)
    centers = rng.randn(16, 256).astype(np.float32) * 2
    batches = []
    for _ in range(n_probe):
        y = rng.randint(0, 16, batch_size)
        x = centers[y] + 0.3 * rng.randn(batch_size, 256).astype(np.float32)
        batches.append(DataBatch(x.reshape(batch_size, 1, 1, 256),
                                 y[:, None].astype(np.float32)))

    search_trainer = NetTrainer(parse_config_string(conf))
    search_trainer.init_model()
    # warm-up at the baseline K fills the ledger — stage 1 prices every
    # candidate from THIS program's compiler truth
    execution.measured_probe(search_trainer, 1, batches, repeats=1)
    led.ensure_analyzed_batch()
    base_bytes = max(
        (e.peak_bytes or (e.argument_bytes + e.output_bytes
                          + e.temp_bytes))
        for e in led.entries())
    # the declared ceiling comes FROM the measured baseline footprint:
    # ~5x headroom-adjusted means the k=8 rung (pricing 8x) cannot fit
    # and must be pruned by the ledger, never measured
    scan_mem_mb = base_bytes * 5.0 / (1 << 20)
    scan_spec = (f'knobs=steps_per_dispatch:1..8;mode=train;budget=60;'
                 f'seed=0;probe_steps={n_probe};probe_repeats=3;'
                 f'mem_mb={scan_mem_mb:.3f}')
    scan_space = TuneSpace.parse(scan_spec)
    scan_base = {'steps_per_dispatch': 1}
    scan_gate = LedgerGate(
        base_bytes=float(base_bytes),
        ceiling_bytes=scan_space.mem_mb * (1 << 20)
        * (1.0 - scan_space.headroom),
        baseline=scan_base, mem_knobs=scan_space.mem_knobs())
    scan_res = TuneSearch(
        scan_space,
        lambda c: execution.measured_probe(
            search_trainer, c['steps_per_dispatch'], batches,
            repeats=scan_space.probe_repeats),
        gate=scan_gate, baseline=scan_base).run('train')
    k_tuned = scan_res.best['steps_per_dispatch']

    # independent A/B: fresh trainers, the tuned K vs the default K —
    # and the bitwise-twin contract: both legs dispatch the same batches
    # the same number of times, so final params must be IDENTICAL (a
    # tuned config may move knobs, never the math)
    def scan_leg(k):
        tr = NetTrainer(parse_config_string(conf))
        tr.init_model()
        rate = execution.measured_probe(tr, k, batches, repeats=4)
        return rate, tr

    rate_default, t_def = scan_leg(1)
    rate_tuned, t_tuned = scan_leg(k_tuned)
    scan_best = dict(scan_res.best)
    scan_fallback = False
    if k_tuned == 1:
        # the search kept the hand-set default: identical configs are
        # 1.0x by definition — the re-measure only adds noise
        rate_tuned = rate_default
    elif rate_tuned < rate_default:
        # validation gate: a tuned config the independent re-measure
        # cannot confirm is never shipped — fall back to the default
        # (the same >=baseline contract the search itself keeps)
        scan_best = dict(scan_base)
        rate_tuned = rate_default
        scan_fallback = True
    scan_bitwise = all(
        np.array_equal(np.asarray(t_def.params[lk][fk]),
                       np.asarray(t_tuned.params[lk][fk]))
        for lk, fields in t_def.params.items() for fk in fields)
    if not scan_bitwise:
        raise AssertionError(
            'tuned scan leg diverged bitwise from the per-step leg — '
            'the autotuner may move knobs, never the math')
    scan_speedup = rate_tuned / rate_default

    # ---- leg 2: serve decode (slots x pages) -----------------------------
    cfg = TT.TransformerConfig(vocab_size=64, d_model=32, num_heads=2,
                               d_ff=64, num_stages=1, seq_len=128,
                               attn='local')
    params = TT.init_params(np.random.RandomState(0), cfg)
    max_prompt, max_new = 12, 16
    n_req = int(os.environ.get('CXXNET_TUNE_REQUESTS', '16'))
    dec_base = {'slots': 2, 'pages': 16}

    def build_svc(cand):
        return DecodeService(
            params, cfg, slots=cand['slots'], pages=cand['pages'],
            page_size=8, max_prompt=max_prompt, max_new_bound=max_new,
            eos_id=None, max_queue=64, max_wait=0.002, deadline=60.0)

    def dec_prompts(seed):
        prng = np.random.RandomState(seed)
        return [prng.randint(0, cfg.vocab_size,
                             (1, int(prng.randint(1, max_prompt))))
                .astype(np.int32) for _ in range(n_req)]

    def dec_rate(svc, reps):
        prompts = dec_prompts(0)

        def one_pass():
            t0 = time.perf_counter()
            reqs = [svc.submit_async(p, max_new, 0.0, None)
                    for p in prompts]
            toks = sum(len(svc.batcher.wait(r)) for r in reqs)
            return toks / max(1e-9, time.perf_counter() - t0)

        one_pass()                       # compile off the clock
        return max(one_pass() for _ in range(reps))

    # baseline engine warm-up: its resident footprint is the stage-1
    # base price for every candidate's slots/pages scaling
    svc0 = build_svc(dec_base)
    try:
        dec_base_bytes = float(svc0.engine.resident_bytes())
    finally:
        svc0.close(30.0)
    dec_mem_mb = dec_base_bytes * 5.0 / (1 << 20)
    dec_spec = (f'knobs=slots:1..8,pages:8..32;mode=decode;budget=120;'
                f'seed=0;probe_steps={n_req};probe_repeats=2;'
                f'max_probes=6;mem_mb={dec_mem_mb:.3f}')
    dec_space = TuneSpace.parse(dec_spec)
    dec_gate = LedgerGate(
        base_bytes=dec_base_bytes,
        ceiling_bytes=dec_space.mem_mb * (1 << 20)
        * (1.0 - dec_space.headroom),
        baseline=dec_base, mem_knobs=dec_space.mem_knobs(),
        feasible=lambda c: ('fewer KV pages than decode slots'
                            if c['pages'] < c['slots'] else None))

    def dec_probe(cand):
        svc = build_svc(cand)
        try:
            return dec_rate(svc, dec_space.probe_repeats)
        finally:
            svc.close(30.0)

    dec_res = TuneSearch(dec_space, dec_probe, gate=dec_gate,
                         baseline=dec_base).run('decode')

    # independent A/B re-measure + the stream-twin contract on the
    # tuned engine: every served stream equals its offline generate
    def dec_leg(cand, twin):
        svc = build_svc(cand)
        try:
            rate = dec_rate(svc, 4)
            twin_ok = True
            if twin:
                for p in dec_prompts(0)[:2]:
                    got = svc.batcher.wait(
                        svc.submit_async(p, max_new, 0.0, None))
                    off = np.asarray(TT.generate(
                        svc.engine.oracle_params(), p, max_new,
                        svc.engine.cfg, temperature=0.0,
                        rng=None, eos_id=None))[0]
                    twin_ok = twin_ok and \
                        (np.asarray(got) == off[:len(got)]).all()
            return rate, twin_ok
        finally:
            svc.close(30.0)

    dec_rate_default, _ = dec_leg(dec_base, twin=False)
    dec_rate_tuned, dec_twin = dec_leg(dec_res.best, twin=True)
    dec_best = dict(dec_res.best)
    dec_fallback = False
    if dec_res.best == dec_base:
        dec_rate_tuned = dec_rate_default
    elif dec_rate_tuned < dec_rate_default:
        dec_best = dict(dec_base)
        dec_rate_tuned = dec_rate_default
        dec_fallback = True
    if not dec_twin:
        raise AssertionError(
            'tuned decode engine broke the stream-twin contract')
    dec_speedup = dec_rate_tuned / dec_rate_default

    # ---- in-receipt recompile-storm guard drill --------------------------
    drill_space = TuneSpace.parse(
        'knobs=slots:1..8;mode=decode;budget=5;compile_budget=4')
    drill_log = faults.FailureLog()
    storm_before = len(faults.global_failure_log().records(
        'RecompileStormError'))
    prog = led.program('tune.storm_drill', bound=2)
    drill_fn = prog.jit(lambda x: x * 2.0,
                        key_fn=lambda a, _k: f's{a[0].shape[0]}')

    ctl = TuneController(
        drill_space, verdicts=lambda: {'v': {'state': 'BREACHED'}},
        gauges=lambda: {'hbm.headroom_frac[d0]': 0.01},
        failure_log=drill_log, hysteresis=1, cooldown=0.0)
    # every re-plan really recompiles: each slot count is a new shape
    # through a bound ledger program — exactly the bucket-ladder thrash
    # the guard exists for
    ctl.bind('slots', lambda v: drill_fn(np.zeros((max(1, v),),
                                                  np.float32)),
             8, program=prog)
    for _ in range(8):                   # a thrashing verdict stream
        ctl.evaluate()
    storm_errors = (len(faults.global_failure_log().records(
        'RecompileStormError')) - storm_before) \
        + len(drill_log.records('RecompileStormError'))
    vetoes = int(ctl.stats.get('recompile_vetoes'))
    drill_ok = (storm_errors == 0 and vetoes >= 1
                and ctl.compiles() <= drill_space.compile_budget
                and prog.compiles <= prog.bound)
    if not drill_ok:
        raise AssertionError(
            f'storm-guard drill failed: storm_errors={storm_errors} '
            f'vetoes={vetoes} compiles={ctl.compiles()} '
            f'program={prog.compiles}/{prog.bound}')

    def search_block(res, space):
        return {'spec': space.describe(), 'budget_s': space.budget,
                'wall_s': round(res.wall_s, 3),
                'budget_honored': res.budget_honored,
                'stage1_candidates': res.stage1_candidates,
                'stage1_pruned': res.stage1_pruned,
                'measured': res.measured, 'failed': res.failed}

    payload = {
        'metric': 'autotune_speedup',
        # the headline is the WORSE of the two modes: the claim is
        # "tuned beats the hand-set default everywhere", not on average
        'value': round(min(scan_speedup, dec_speedup), 4),
        'unit': 'x',
        'platform': plat,
        'vs_baseline': None,
        'modes': {
            'scan': {
                'speedup': round(scan_speedup, 4),
                'default': scan_base, 'tuned': scan_best,
                'fallback_to_default': scan_fallback,
                'default_steps_per_sec': round(rate_default, 2),
                'tuned_steps_per_sec': round(rate_tuned, 2),
                'bitwise_equal': bool(scan_bitwise),
                'search': search_block(scan_res, scan_space),
            },
            'decode': {
                'speedup': round(dec_speedup, 4),
                'default': dec_base, 'tuned': dec_best,
                'fallback_to_default': dec_fallback,
                'default_tokens_per_sec': round(dec_rate_default, 2),
                'tuned_tokens_per_sec': round(dec_rate_tuned, 2),
                'stream_twins': bool(dec_twin),
                'search': search_block(dec_res, dec_space),
            },
        },
        'search': {
            'budget_s': scan_space.budget + dec_space.budget,
            'wall_s': round(scan_res.wall_s + dec_res.wall_s, 3),
            'budget_honored': bool(scan_res.budget_honored
                                   and dec_res.budget_honored),
            'stage1_candidates': (scan_res.stage1_candidates
                                  + dec_res.stage1_candidates),
            'stage1_pruned': (scan_res.stage1_pruned
                              + dec_res.stage1_pruned),
            'measured': scan_res.measured + dec_res.measured,
        },
        'storm_guard': {
            'replans': ctl.status_view()['replans'],
            'vetoes': vetoes,
            'compiles': ctl.compiles(),
            'compile_budget': drill_space.compile_budget,
            'program_compiles': prog.compiles,
            'program_bound': prog.bound,
            'storm_errors': storm_errors,
        },
        'batch': batch_size,
        'requests': n_req,
        'programs': _program_summary(),
        'receipt_file': 'BENCH_TUNE_r01.json',
        'timing': 'speedups are independent re-measures (fresh state, '
                  'best-of-3) of tuned vs default, not the search\'s '
                  'own probes; scan legs bitwise-assert final params',
    }
    _write_receipt_file(payload)
    _emit(payload)
    return 0 if min(scan_speedup, dec_speedup) >= 1.0 else 1


_MODES = {'alexnet': ('alexnet_images_per_sec_per_chip', bench_alexnet),
          'inception_bn': ('inception_bn_images_per_sec_per_chip',
                           bench_inception_bn),
          'googlenet': ('googlenet_images_per_sec_per_chip',
                        bench_googlenet),
          'vgg16': ('vgg16_images_per_sec_per_chip', bench_vgg16),
          'e2e_alexnet': ('alexnet_e2e_images_per_sec_per_chip',
                          bench_e2e_alexnet),
          'eval_alexnet': ('alexnet_eval_images_per_sec_per_chip',
                           bench_eval_alexnet),
          'io': ('host_io_images_per_sec', bench_io),
          'bench_io': ('host_io_images_per_sec', bench_io),  # alias
          'scan': ('supervised_scan_steps_per_sec', bench_scan),
          'online': ('online_steps_per_sec_while_serving', bench_online),
          'obs': ('obs_recorder_overhead', bench_obs),
          'mnist_tta': ('mnist_time_to_2pct_error', bench_mnist_tta),
          'transformer': ('transformer_tokens_per_sec_per_chip',
                          bench_transformer),
          'decode': ('decode_tokens_per_sec_per_chip', bench_decode),
          'cnn_fused': ('cnn_fused_speedup', bench_cnn_fused),
          'autotune': ('autotune_speedup', bench_autotune)}


#: modes that never touch a device
_HOST_ONLY = ('io', 'bench_io')


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else 'alexnet'
    if mode not in _MODES:
        print(f'unknown bench mode {mode!r}; choose from '
              f'{sorted(_MODES)}', file=sys.stderr)
        return 2
    metric, fn = _MODES[mode]
    global _platform
    try:
        if mode not in _HOST_ONLY:
            from cxxnet_tpu.utils.backend import (enable_compile_cache,
                                                  require_chip)
            enable_compile_cache()
            _platform = require_chip()
        return fn()
    except BaseException as e:           # noqa: BLE001 — one JSON line, always
        _emit({'metric': metric, 'value': None, 'unit': None,
               'vs_baseline': None, 'error': f'{type(e).__name__}: {e}'})
        return 1


if __name__ == '__main__':
    sys.exit(main())
