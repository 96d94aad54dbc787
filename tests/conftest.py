"""Test environment: pin the CPU backend with an 8-device virtual mesh.

Tests validate multi-chip sharding logic without TPU hardware via
``xla_force_host_platform_device_count`` (the driver dry-runs the real
multi-chip path separately through ``__graft_entry__.dryrun_multichip``).
Both variables are set before the first ``import jax``, which is when JAX
reads them; the ``JAX_PLATFORMS=cpu`` pin is also what lets confs that say
``dev = tpu`` run here (nnet/trainer.select_devices).
"""

import gc
import os
import threading
import time

import pytest

os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()


def pytest_configure(config):
    config.addinivalue_line('markers',
                            'slow: long-running end-to-end tests')
    config.addinivalue_line(
        'markers',
        'faults: deterministic fault-injection / recovery suite '
        '(seeded, tier-1: runs under -m "not slow"; select with -m faults)')
    config.addinivalue_line(
        'markers',
        'serve: online inference serving suite — engine/batcher/registry, '
        'CPU-only, no network, in-process client threads '
        '(tier-1: runs under -m "not slow"; select with -m serve)')
    config.addinivalue_line(
        'markers',
        'async_ckpt: asynchronous checkpointing suite — snapshot/writer/'
        'double-buffer/barrier semantics, CPU-only, deterministic '
        '(tier-1: runs under -m "not slow"; select with -m async_ckpt)')
    config.addinivalue_line(
        'markers',
        'io_perf: parallel input pipeline + scanned step-loop dispatch '
        'suite — worker-pool determinism, thread lifecycle, '
        'steps_per_dispatch bitwise equality; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m io_perf)')
    config.addinivalue_line(
        'markers',
        'serve_decode: continuous-batching decode suite — paged KV '
        'cache, slot join/leave, offline-generate stream twins, '
        'multi-model budgeter; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m serve_decode)')
    config.addinivalue_line(
        'markers',
        'online: train-while-serve suite — streaming imgbin source, '
        'freshness SLO, hot-swap-under-traffic pipeline, chaos drill; '
        'CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m online)')
    config.addinivalue_line(
        'markers',
        'lint: graftlint static-analysis suite — the five AST invariant '
        'checkers over seeded fixtures AND the live codebase, plus the '
        'shrink-only baseline ratchet; pure host code, no device '
        '(tier-1: runs under -m "not slow"; select with -m lint)')
    config.addinivalue_line(
        'markers',
        'execution: ExecutionPlan / composable step-loop suite — '
        'scanned K-dispatch composed with update_period, train metrics, '
        'supervision and chaos recovery, bitwise twins + demotion-matrix '
        'drift; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m execution)')
    config.addinivalue_line(
        'markers',
        'quant: quantized-inference tier suite — int8/bf16 storage, '
        'W8A8 qdot Pallas-vs-XLA bitwise twin, PredictEngine/DecodeEngine '
        'exact + pinned-tolerance twins vs f32; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m quant)')
    config.addinivalue_line(
        'markers',
        'serve_spec: prefix-shared paged KV cache + greedy speculative '
        'decoding suite — content-addressed prefix index, refcounted '
        'pages, CoW, tail prefill bitwise twins, verify-window '
        'token-equality, draft hot-swap; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m serve_spec)')
    config.addinivalue_line(
        'markers',
        'obs: graftscope telemetry suite — hub registration, span '
        'nesting + trace-id propagation, flight-recorder ring + '
        'fault-triggered dumps, Prometheus/statusz endpoints, Chrome '
        'trace export; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m obs)')
    config.addinivalue_line(
        'markers',
        'slo: graftwatch SLO suite — gauge-history rings + sampler, '
        'the slo.<name>= grammar, multi-window burn-rate verdicts '
        '(OK/AT_RISK/BREACHED), freshness-through-the-engine '
        'equivalence, /slos + degraded /healthz endpoints, '
        'breach-triggered postmortems, fleet scrape/merge units; '
        'CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m slo)')
    config.addinivalue_line(
        'markers',
        'scenario: graftstorm suite — seeded adversarial traffic '
        'scenarios (diurnal/flash/heavy-tail/tenants/abandonment), '
        'exactly-reconciling scenario ledger, SLO-driven autoscaler '
        'hysteresis/degradation, live-cap shrink safety under '
        'refcounted prefix pages; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m scenario)')
    config.addinivalue_line(
        'markers',
        'dist: elastic multi-host training suite — coordinator/client '
        'membership, host-sharded stream bitwise twins, and the '
        'multi-process chaos drills (real worker subprocesses over '
        'localhost; host_loss/partition recovery bitwise-equal to '
        'fault-free twins); CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m dist)')
    config.addinivalue_line(
        'markers',
        'shard: graftshard suite — mesh-sharded decode serving '
        '(serve.shard=tp:N head-sharded params + KV pool, bitwise '
        'stream twins at every shard count), disaggregated prefill '
        'workers, data-parallel PredictEngine replicas, per-device '
        'budgeter/gauge reconciliation; CPU-only (8 virtual devices; '
        'tier-1: runs under -m "not slow"; select with -m shard)')
    config.addinivalue_line(
        'markers',
        'kv_tier: graftcache suite — tiered KV prefix cache (HBM page '
        'pool -> bounded host RAM -> crc32-digested disk records), '
        'demote/promote bitwise stream twins, LRU + byte-budget '
        'enforcement, cross-replica share-dir adopt, corrupt-record '
        'quarantine drills; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m kv_tier)')
    config.addinivalue_line(
        'markers',
        'tune: grafttune autotuner suite — autotune= grammar '
        'round-trips, ledger-gated stage-1 pruning, seeded measured '
        'probes with byte-deterministic tuned_<task>.conf artifacts, '
        'tuned-vs-hand-written bitwise twins, online TuneController '
        're-plan bounds + recompile-storm guard drill; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m tune)')
    config.addinivalue_line(
        'markers',
        'cnn_fused: graftfuse suite — '
        'inference conv+BN folding through a real PredictEngine '
        '(hot-swap re-fold + double-fold identity guard), μ-cuDNN '
        'conv microbatching bitwise at every declared split with '
        'ledger peak-bytes bounds; CPU-only '
        '(tier-1: runs under -m "not slow"; select with -m cnn_fused)')


# every pipeline thread the framework starts carries a cxxnet- name
# prefix (utils/thread_buffer.py producers, utils/parallel_pool.py
# workers, serve/decode.py loop threads, parallel/elastic.py
# coordinator/heartbeat threads) precisely so this fixture can hold the
# line on lifecycle
_PIPELINE_THREAD_PREFIXES = ('cxxnet-tb-', 'cxxnet-pool-', 'cxxnet-decode-',
                             'cxxnet-elastic-', 'cxxnet-obs-',
                             'cxxnet-scale-', 'cxxnet-kv-',
                             'cxxnet-prefill-', 'cxxnet-replica-',
                             'cxxnet-tune-')


def _pipeline_threads():
    return {t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(_PIPELINE_THREAD_PREFIXES)}


@pytest.fixture(autouse=True)
def _no_pipeline_thread_leaks():
    """No stray ThreadBuffer producer / pool worker survives a test.

    Abandoned iterator generators retire their threads from the
    generator's ``finally`` (ThreadBuffer stop event, pool sentinel
    drain), which on CPython fires at refcount-zero — so the check
    collects garbage and grants a grace window before calling leak."""
    before = _pipeline_threads()
    yield
    deadline = time.time() + 5.0
    while True:
        leaked = _pipeline_threads() - before
        if not leaked:
            return
        # only pay a full collection when a candidate leak exists — an
        # abandoned generator's finally (which retires its threads) may
        # just not have run yet
        gc.collect()
        leaked = _pipeline_threads() - before
        if not leaked:
            return
        if time.time() > deadline:
            pytest.fail(
                'pipeline threads leaked past the test: '
                f'{sorted(t.name for t in leaked)} — close() the '
                'ThreadBuffer/iterator or let its generator be collected')
        time.sleep(0.05)
