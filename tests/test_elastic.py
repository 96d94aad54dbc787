"""Elastic multi-host training suite (`-m dist`, tier-1, CPU-only).

Three layers, mirroring doc/fault_tolerance.md "Multi-host recovery":

* protocol/membership units — framing, rendezvous, push/pull assembly,
  barrier value exchange, rollback on peer death, heartbeat-timeout
  membership (threads, no subprocess, no jax device work),
* the input-sharding invariant — per-host streams through the nworker
  pool interleave back into the 1-host stream bitwise at 1/2/4 hosts,
* the chaos drills — REAL multi-process workers over localhost
  (``python -m cxxnet_tpu.main`` under the ElasticLauncher): a worker
  killed mid-epoch (``host_loss``), a network partition + divergence in
  one run, at 1, 2, and 4 hosts — every run's final params BITWISE
  equal to the fault-free single-host twin's.
"""

from __future__ import annotations

import io
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from cxxnet_tpu.parallel.elastic import (ElasticClient, ElasticConfig,
                                         ElasticCoordinator,
                                         ElasticLauncher, recv_frame,
                                         send_frame)
from cxxnet_tpu.runtime import faults

pytestmark = pytest.mark.dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_INST = 32          # instances in the shared dataset
BATCH = 16           # GLOBAL batch size -> 2 steps/epoch
ROUNDS = 4           # -> 8 optimizer steps end-to-end
FINAL_MODEL = f'{ROUNDS:04d}.model'

CONF = f"""
data = train
iter = imgbin
  image_list = train.lst
  image_bin = train.bin
iter = end
netconfig = start
layer[0->1] = flatten
layer[1->2] = fullc:f1
  nhidden = 8
layer[2->3] = sigmoid
layer[3->4] = fullc:f2
  nhidden = 4
layer[4->4] = softmax
netconfig = end
input_shape = 3,12,12
batch_size = {BATCH}
dev = cpu
eta = 0.05
momentum = 0.9
num_round = {ROUNDS}
divideby = 256
train.save_every = 4
train.watchdog_deadline = 60
dist.shards = 4
dist.heartbeat = 1.0
silent = 1
"""


# --- shared dataset / helpers ----------------------------------------------


@pytest.fixture(scope='module')
def workdir(tmp_path_factory):
    """One imgbin dataset (a single standard 64MB page, so worker
    subprocesses read it with the stock reader — no page-size games)
    plus the conf every drill shares."""
    from PIL import Image

    from cxxnet_tpu.io.iter_stream import append_records
    root = tmp_path_factory.mktemp('elastic')
    rng = np.random.RandomState(7)
    recs = []
    for i in range(N_INST):
        cls = i % 4
        img = np.zeros((12, 12, 3), np.uint8)
        r0, c0 = (cls // 2) * 6, (cls % 2) * 6
        img[r0:r0 + 6, c0:c0 + 6] = rng.randint(100, 255, (6, 6, 3))
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format='JPEG', quality=92)
        recs.append((i, [float(cls)], buf.getvalue()))
    append_records(str(root / 'train.bin'), str(root / 'train.lst'), recs)
    (root / 'elastic.conf').write_text(CONF)
    return root


def _sub_env():
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env['JAX_PLATFORMS'] = 'cpu'
    # workers are plain 1-device CPU processes (the pytest parent's
    # 8-device virtual mesh flag must not leak in)
    env['XLA_FLAGS'] = ''
    return env


def _launch(workdir, model_dir, hosts, *overrides, rejoin=2):
    la = ElasticLauncher(
        argv=['elastic.conf', f'model_dir={model_dir}', *overrides],
        hosts=hosts, rejoin=rejoin, heartbeat=1.0, env=_sub_env(),
        cwd=str(workdir))
    rc = la.run()
    return rc, la


def _run_single_host_inprocess(workdir, model_dir, *overrides):
    """The fault-free single-host twin, run in THIS process (the
    dist.hosts=1 path spins its own local coordinator)."""
    from cxxnet_tpu.main import main as cli_main
    old = os.getcwd()
    os.chdir(workdir)
    try:
        rc = cli_main(['elastic.conf', 'dist.hosts=1',
                       f'model_dir={model_dir}', *overrides])
    finally:
        os.chdir(old)
    assert rc == 0


def _final_params(workdir, model_dir):
    """Params of the run's final model file, as host arrays."""
    import jax

    from cxxnet_tpu.nnet import checkpoint as model_io
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_file
    cfg = parse_config_file(str(workdir / 'elastic.conf'))
    out = {}

    def _read(f):
        f.read(4)
        tr = NetTrainer(cfg)
        tr.load_model(f)
        out['params'] = jax.device_get(tr.params)

    path = str(workdir / model_dir / FINAL_MODEL)
    model_io.read_model_file(path, _read)
    return out['params']


def _assert_params_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope='module')
def twin(workdir):
    """Fault-free single-host twin params — the reference every drill's
    final params must equal BITWISE."""
    _run_single_host_inprocess(workdir, 'm_twin')
    return _final_params(workdir, 'm_twin')


# --- protocol / membership units -------------------------------------------


def test_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        payload = np.arange(7, dtype=np.float32)
        send_frame(a, {'op': 'push', 'step': 3},
                   (payload.tobytes(), b'\x01\x02'))
        hdr, bufs = recv_frame(b)
        assert hdr['op'] == 'push' and hdr['step'] == 3
        np.testing.assert_array_equal(
            np.frombuffer(bufs[0], np.float32), payload)
        assert bufs[1] == b'\x01\x02'
    finally:
        a.close()
        b.close()


def _client(addr, rank, nhosts, **kw):
    c = ElasticClient(addr, rank, nhosts, heartbeat=0.2,
                      sync_timeout=10.0, rendezvous_timeout=10.0, **kw)
    c.connect()
    return c


def test_coordinator_rendezvous_push_barrier_and_rollback():
    coord = ElasticCoordinator(2, heartbeat_timeout=30.0)
    addr = coord.start()
    c0 = c1 = None
    try:
        c0 = _client(addr, 0, 2)
        c1 = _client(addr, 1, 2)
        gens = [None, None]
        t = threading.Thread(
            target=lambda: gens.__setitem__(1, c1.rendezvous()))
        t.start()
        gens[0] = c0.rendezvous()
        t.join(10)
        assert gens == [0, 0]

        # push/pull: each host one shard; both receive the full set,
        # byte-identical to what was pushed
        g0 = np.array([1.0, 2.0], np.float32)
        g1 = np.array([3.0, 4.0], np.float32)
        out = [None, None]

        def push1():
            out[1] = c1.all_shards(0, [1], [g1],
                                   [np.array([0.5], np.float32)])

        t = threading.Thread(target=push1)
        t.start()
        out[0] = c0.all_shards(0, [0], [g0],
                               [np.array([0.25], np.float32)])
        t.join(10)
        for full, losses in out:
            assert sorted(full) == [0, 1]
            np.testing.assert_array_equal(full[0], g0)
            np.testing.assert_array_equal(full[1], g1)
            assert losses[0] == np.float32(0.25)
            assert losses[1] == np.float32(0.5)

        # barrier exchanges values by rank
        vals = [None, None]
        t = threading.Thread(
            target=lambda: vals.__setitem__(1, c1.barrier('v', value='b')))
        t.start()
        vals[0] = c0.barrier('v', value='a')
        t.join(10)
        assert vals[0] == {0: 'a', 1: 'b'} == vals[1]

        # peer death mid-step: c1 vanishes ABRUPTLY (no goodbye), c0's
        # next push gets a rollback -> HostLossError, generation moves
        c1.abort()
        c1 = None
        with pytest.raises(faults.HostLossError):
            c0.all_shards(1, [0], [g0], [np.array([0.0], np.float32)])
        assert coord.generation() == 1

        # resync: survivor + a fresh rank-1 rendezvous into gen 1
        c1 = _client(addr, 1, 2)
        got = [None, None]
        t = threading.Thread(
            target=lambda: got.__setitem__(1, c1.rendezvous()))
        t.start()
        got[0] = c0.resync('test', 1)
        t.join(10)
        assert got == [1, 1]
    finally:
        for c in (c0, c1):
            if c is not None:
                c.close()
        coord.stop()


def test_heartbeat_timeout_declares_host_lost():
    coord = ElasticCoordinator(2, heartbeat_timeout=0.6)
    addr = coord.start()
    c0 = None
    raw = None
    try:
        c0 = _client(addr, 0, 2)
        # rank 1 joins WITHOUT ever heartbeating (raw hello socket)
        host, _, port = addr.rpartition(':')
        raw = socket.create_connection((host, int(port)))
        done = []

        def hello():
            send_frame(raw, {'op': 'hello', 'rank': 1})
            done.append(recv_frame(raw)[0])

        t = threading.Thread(target=hello)
        t.start()
        assert c0.rendezvous() == 0
        t.join(10)
        assert done and done[0]['op'] == 'welcome'
        # the silent member is declared lost; the survivor's next op
        # rolls back
        with pytest.raises(faults.HostLossError):
            c0.barrier('fence', value=1, timeout=15.0)
        assert any('missed heartbeats' in e for e in coord.events())
    finally:
        if raw is not None:
            raw.close()
        if c0 is not None:
            c0.close()
        coord.stop()


def test_elastic_config_validation():
    with pytest.raises(faults.DistInitError):
        ElasticConfig(hosts=2, rank=2, batch_size=16).resolve()
    with pytest.raises(ValueError):
        ElasticConfig(hosts=2, rank=0, shards=3, batch_size=16).resolve()
    with pytest.raises(ValueError):
        ElasticConfig(hosts=2, rank=0, shards=4, batch_size=18).resolve()
    cfg = ElasticConfig(hosts=2, rank=1, shards=4, batch_size=16).resolve()
    assert cfg.owned_shards == [1, 3]


# --- fault-plan grammar -----------------------------------------------------


def test_fault_plan_host_loss_partition_grammar():
    p = faults.FaultPlan.parse(
        'host_loss=10;host_loss@every=7:1;partition=5:3.5;'
        'partition@every=9')
    d = p.describe()
    assert 'host_loss=10' in d and 'host_loss@every=7:1' in d
    assert 'partition=5:3.5' in d and 'partition@every=9:30' in d
    # partition fires once per distinct step (replays converge)
    assert p.on_elastic_step(5, 0, 2) == 3.5
    assert p.on_elastic_step(5, 0, 2) is None
    # host_loss default target is the highest rank; a non-target rank
    # never fires
    assert p.on_elastic_step(10, 0, 2) is None
    # disarmed on incarnation > 0 (allow_kill=False): recorded, no kill
    p2 = faults.FaultPlan.parse('host_loss=3')
    assert p2.on_elastic_step(3, 1, 2, allow_kill=False) is None
    assert p2.fired() == ['host_loss=3:1#disarmed']


# --- host-sharded input stream ---------------------------------------------


def _aug_stage(workdir, hosts, rank, nworker=2):
    from cxxnet_tpu.io.iter_augment import AugmentIterator
    from cxxnet_tpu.io.iter_imbin import ImageBinIterator
    src = ImageBinIterator()
    it = AugmentIterator(src)
    for k, v in (('image_list', str(workdir / 'train.lst')),
                 ('image_bin', str(workdir / 'train.bin')),
                 ('input_shape', '3,12,12'), ('divideby', '256'),
                 ('silent', '1'), ('nworker', str(nworker)),
                 ('elastic_hosts', str(hosts)),
                 ('elastic_rank', str(rank))):
        it.set_param(k, v)
    it.init()
    return it


def _collect(it):
    return [(inst.index, inst.data.tobytes(), inst.label.tobytes())
            for inst in it]


def test_global_stream_bitwise_identical_across_host_counts(workdir):
    """THE input invariant: per-host streams (nworker pool active on
    every host) interleave round-robin back into the 1-host stream,
    bitwise, at 2 and 4 hosts."""
    ref = _collect(_aug_stage(workdir, 1, 0))
    assert len(ref) == N_INST
    for hosts in (2, 4):
        streams = [_collect(_aug_stage(workdir, hosts, r))
                   for r in range(hosts)]
        merged = []
        for i in range(N_INST):
            merged.append(streams[i % hosts][i // hosts])
        assert merged == ref


def test_serial_path_rejects_elastic_sharding(workdir):
    it = _aug_stage(workdir, 2, 0, nworker=0)
    with pytest.raises(ValueError, match='nworker'):
        next(iter(it))


def test_stream_fence_pins_pass_length(workdir):
    """stream_fence ends an imgbin_stream pass after exactly N
    instances — the host-agreed pass length for growing files."""
    from cxxnet_tpu.io.iter_stream import ImageBinStreamIterator
    it = ImageBinStreamIterator()
    for k, v in (('image_list', str(workdir / 'train.lst')),
                 ('image_bin', str(workdir / 'train.bin')),
                 ('silent', '1'), ('stream_fence', '10')):
        it.set_param(k, v)
    it.init()
    first = [inst.index for inst in it]
    second = [inst.index for inst in it]
    assert first == list(range(10)) == second


# --- the chaos drills (real multi-process workers) -------------------------


def test_host_loss_drill_two_hosts_bitwise_twin(workdir, twin):
    """Headline: kill rank 1 mid-epoch; survivor restores-last-good,
    the replacement rejoins, final params == the fault-free single-host
    twin, bitwise."""
    rc, la = _launch(workdir, 'm_kill2', 2,
                     'train.fault_plan=host_loss=5:1')
    assert rc == 0
    assert (1, 1) in la.respawns
    assert any('lost rank 1' in e for e in la.coordinator.events())
    _assert_params_equal(_final_params(workdir, 'm_kill2'), twin)


def test_host_loss_drill_one_and_four_hosts(workdir, twin):
    """The same drill at the matrix edges: a single-host run whose only
    worker dies (launcher respawns it), and a 4-host run losing its
    highest rank."""
    rc, la = _launch(workdir, 'm_kill1', 1,
                     'train.fault_plan=host_loss=5')
    assert rc == 0 and (0, 1) in la.respawns
    _assert_params_equal(_final_params(workdir, 'm_kill1'), twin)

    rc, la = _launch(workdir, 'm_kill4', 4,
                     'train.fault_plan=host_loss=5')
    assert rc == 0 and (3, 1) in la.respawns
    _assert_params_equal(_final_params(workdir, 'm_kill4'), twin)


def test_partition_and_divergence_drill_two_hosts(workdir, twin):
    """One run, two faults: a 6s full network partition at step 3
    (outliving the 5s heartbeat window -> declared lost, all roll
    back), then an injected NaN at step 6 (every host trips the breaker
    deterministically, one generation bump).  Still bitwise-twin."""
    rc, la = _launch(workdir, 'm_chaos', 2,
                     'train.fault_plan=partition=3:6;nan_at_step=6',
                     'train.nan_breaker=1')
    assert rc == 0
    assert la.respawns == []          # nobody died: both faults rejoin
    events = la.coordinator.events()
    assert sum('rendezvous complete' in e for e in events) >= 3
    _assert_params_equal(_final_params(workdir, 'm_chaos'), twin)


def test_cli_launcher_end_to_end(workdir, twin):
    """The full CLI surface: ``python -m cxxnet_tpu.main conf
    dist.hosts=2`` IS the launcher — coordinator, spawn, kill, respawn,
    rejoin, and the final model, in one command."""
    r = subprocess.run(
        [sys.executable, '-m', 'cxxnet_tpu.main', 'elastic.conf',
         'dist.hosts=2', 'model_dir=m_cli', 'silent=0',
         'train.fault_plan=host_loss=5:1'],
        cwd=str(workdir), env=_sub_env(), capture_output=True,
        text=True, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert 'respawning' in r.stdout
    assert '[elastic] rank 0 done' in r.stdout
    # both workers print the same params crc — and the model file
    # equals the twin byte-for-byte
    crcs = {line.split('params_crc=')[1].split()[0]
            for line in r.stdout.splitlines() if 'params_crc=' in line}
    assert len(crcs) == 1
    _assert_params_equal(_final_params(workdir, 'm_cli'), twin)


# --- fleet observability (graftwatch, doc/observability.md "Fleet view") ---


def test_fleet_obs_merged_metrics_slos_and_trace(workdir, twin, tmp_path):
    """Acceptance: 2 REAL worker ranks under the launcher with fleet
    observability on — the merged /metrics carries both ranks' gauges
    under rank labels, a fleet-scoped SLO evaluates to a typed verdict,
    the merged Chrome trace loads with one lane per host, and the
    scrape survives rank 1's mid-run death (host_loss drill) — all
    while the run stays bitwise-twin."""
    import json
    import time as _time
    import urllib.request

    trace_out = str(tmp_path / 'fleet_trace.json')
    la = ElasticLauncher(
        argv=['elastic.conf', 'model_dir=m_fleet',
              'train.fault_plan=host_loss=5:1'],
        hosts=2, rejoin=2, heartbeat=1.0, env=_sub_env(),
        cwd=str(workdir), fleet_port=0, sample_every=0.3,
        slo_specs=[('progress', 'fleet.elastic_steps.max.rate>=0.01@6'),
                   ('membership', 'fleet.ranks_alive>=1@3:10')],
        trace_merge=trace_out)
    rc_box = {}
    t = threading.Thread(target=lambda: rc_box.setdefault('rc', la.run()))
    t.start()
    try:
        deadline = _time.monotonic() + 180
        while la.fleet_server is None and t.is_alive() \
                and _time.monotonic() < deadline:
            _time.sleep(0.05)
        assert la.fleet_server is not None, 'fleet endpoint never came up'
        url = la.fleet_server.url
        text = ''
        while t.is_alive() and _time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(f'{url}/metrics',
                                            timeout=5) as r:
                    text = r.read().decode()
            except OSError:
                _time.sleep(0.1)
                continue
            if ('cxxnet_elastic_steps{rank="0"}' in text
                    and 'cxxnet_elastic_steps{rank="1"}' in text):
                break
            _time.sleep(0.2)
        assert 'cxxnet_elastic_steps{rank="0"}' in text, text[:2000]
        assert 'cxxnet_elastic_steps{rank="1"}' in text, text[:2000]
        assert 'cxxnet_fleet_ranks_alive' in text
        # the live /slos serves the typed fleet verdicts mid-run
        with urllib.request.urlopen(f'{url}/slos', timeout=5) as r:
            slos = json.loads(r.read())
        assert set(slos) == {'progress', 'membership'}
    finally:
        t.join(300)
    assert rc_box.get('rc') == 0
    # the drill killed rank 1 mid-run; the scrape survived it and the
    # respawned incarnation re-announced into the same port file
    assert (1, 1) in la.respawns
    assert 'cxxnet_elastic_steps{rank="0"}' in la.fleet_metrics
    assert 'cxxnet_elastic_steps{rank="1"}' in la.fleet_metrics
    # fleet-scoped verdicts captured at run end, typed states only
    assert set(la.fleet_verdicts) == {'progress', 'membership'}
    for v in la.fleet_verdicts.values():
        assert v['state'] in ('OK', 'AT_RISK', 'BREACHED')
    # burn=10 demands a SUSTAINED membership hole; the drill's dip (and
    # any shutdown-window sample) must never read as a breach
    assert la.fleet_verdicts['membership']['state'] in ('OK', 'AT_RISK')
    # merged Perfetto trace: pid = rank = one lane group per host
    with open(trace_out) as f:
        trace = json.load(f)
    events = trace['traceEvents']
    assert {e['pid'] for e in events} == {0, 1}
    lanes = {(e['pid'], e['args']['name']) for e in events
             if e.get('ph') == 'M' and e['name'] == 'process_name'}
    assert lanes == {(0, 'host rank 0'), (1, 'host rank 1')}
    assert any(e['name'].startswith('elastic.') for e in events
               if e.get('ph') == 'X')
    # fleet observability never perturbs training: still the twin
    _assert_params_equal(_final_params(workdir, 'm_fleet'), twin)


# --- hardened jax.distributed init (satellite) -----------------------------


def test_init_distributed_validates_rank_typed():
    from cxxnet_tpu.parallel.distributed import init_distributed
    with pytest.raises(faults.DistInitError):
        init_distributed('127.0.0.1:1', nproc=2, rank=2)
    with pytest.raises(faults.DistInitError):
        init_distributed('127.0.0.1:1', nproc=0, rank=0)


def test_maybe_init_distributed_warns_on_solo_coordinator(monkeypatch,
                                                          capsys):
    from cxxnet_tpu.parallel.distributed import maybe_init_distributed
    monkeypatch.setenv('CXXNET_COORDINATOR', '127.0.0.1:9999')
    monkeypatch.delenv('CXXNET_NUM_WORKER', raising=False)
    monkeypatch.delenv('PS_RANK', raising=False)
    assert maybe_init_distributed([('param_server', 'dist')]) is False
    assert 'single-process' in capsys.readouterr().err


def test_init_distributed_retries_slow_coordinator(monkeypatch):
    """A flaky initialize is a retry (with shutdown between attempts),
    not a hang; exhaustion is a typed DistInitError."""
    import jax

    from cxxnet_tpu.parallel.distributed import init_distributed
    calls = {'init': 0, 'shutdown': 0}

    def flaky_init(**kw):
        calls['init'] += 1
        assert kw['initialization_timeout'] == 7
        if calls['init'] < 3:
            raise RuntimeError('coordinator not up yet')

    monkeypatch.setattr(jax.distributed, 'initialize', flaky_init)
    monkeypatch.setattr(jax.distributed, 'shutdown',
                        lambda: calls.__setitem__(
                            'shutdown', calls['shutdown'] + 1))
    policy = faults.RetryPolicy(retry_on=(RuntimeError,), base_delay=0.0,
                                max_delay=0.0, jitter=0.0,
                                sleep=lambda _t: None)
    init_distributed('127.0.0.1:1', nproc=2, rank=0, timeout=7,
                     retry=policy)
    assert calls['init'] == 3 and calls['shutdown'] == 2

    calls['init'] = 0

    def always_down(**kw):
        calls['init'] += 1
        raise RuntimeError('nope')

    monkeypatch.setattr(jax.distributed, 'initialize', always_down)
    with pytest.raises(faults.DistInitError):
        init_distributed('127.0.0.1:1', nproc=2, rank=0, timeout=7,
                         retry=policy)
    assert calls['init'] == policy.max_attempts


def test_real_jax_distributed_two_process_world():
    """The hardened init against a REAL 2-process jax.distributed world
    over localhost (the satellite's 'real multi-process jax.distributed
    workers' leg — the elastic drills above use the coordinator
    transport precisely so kills stay drillable)."""
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    code = (
        'import sys\n'
        'from cxxnet_tpu.parallel.distributed import init_distributed\n'
        'import jax\n'
        f'init_distributed("127.0.0.1:{port}", nproc=2, '
        'rank=int(sys.argv[1]))\n'
        'print("pid", jax.process_index(), "of", jax.process_count(), '
        'flush=True)\n'
        'assert jax.process_count() == 2\n')
    procs = [subprocess.Popen(
        [sys.executable, '-c', code, str(r)],
        env=_sub_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for r in range(2)]
    outs = [p.communicate(timeout=120) for p in procs]
    assert [p.returncode for p in procs] == [0, 0], outs
    assert 'of 2' in outs[0][0] and 'of 2' in outs[1][0]


# --- lint surface ----------------------------------------------------------


def test_fault_taxonomy_covers_parallel_package():
    from cxxnet_tpu.analysis import fault_taxonomy
    assert 'cxxnet_tpu/parallel/' in fault_taxonomy.TARGET_DIRS
    from cxxnet_tpu.analysis.core import Repo
    repo = Repo(REPO)
    allowed = fault_taxonomy.fault_class_names(repo)
    assert {'HostLossError', 'CoordinatorUnreachableError',
            'ElasticSyncError', 'DistInitError'} <= allowed
    findings = [f for f in fault_taxonomy.run(repo)
                if f.path.startswith('cxxnet_tpu/parallel/')
                and not repo.module(f.path).allowed(f.rule, f.line)]
    assert findings == [], [f.format() for f in findings]
