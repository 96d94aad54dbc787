"""Train-while-serve suite (doc/online.md): the streaming imgbin source,
the freshness tracker/SLO, registry swap stamps, the OnlinePipeline
hot-swap-under-traffic acceptance run, and the full-loop chaos drill
(writer fault + corrupt serving checkpoint + NaN streak in ONE run,
server never regresses, trainer ends bitwise-equal to a fault-free twin).

CPU-only, deterministic: traffic is in-process, faults are seeded
FaultPlan events, and every stream/pipeline property is asserted against
a static or fault-free twin.
"""

import io as _io
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cxxnet_tpu.io import iter_imbin
from cxxnet_tpu.io.data import DataBatch, IIterator, create_iterator
from cxxnet_tpu.io.iter_stream import ImageBinStreamIterator, append_records
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.online import FreshnessTracker, OnlineConfig, OnlinePipeline
from cxxnet_tpu.runtime import faults
from cxxnet_tpu.utils.config import parse_config_string
from cxxnet_tpu.utils.io_stream import BinaryPage
from tests.test_io import write_mnist

pytestmark = pytest.mark.online

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- streaming imgbin source ----------------------------------------------

@pytest.fixture
def small_pages(monkeypatch):
    """2KB pages so multi-page streams are test-sized; native reader off
    (its page size is the real 64MB)."""
    monkeypatch.setattr(BinaryPage, 'K_PAGE_SIZE', 512)
    monkeypatch.setattr(BinaryPage, 'N_BYTES', 512 * 4)
    from cxxnet_tpu.runtime import native
    monkeypatch.setattr(native, 'native_available', lambda: False)


def _png(rng, size=6):
    from PIL import Image
    arr = rng.randint(0, 255, (size, size, 3)).astype(np.uint8)
    buf = _io.BytesIO()
    Image.fromarray(arr).save(buf, format='PNG')
    return buf.getvalue()


def _records(n, start=0, seed=0, size=6):
    rng = np.random.RandomState(seed + start)
    return [(i, [i % 4], _png(rng, size)) for i in range(start, start + n)]


def _stream_iter(tmp_path, **params):
    it = ImageBinStreamIterator()
    it.set_param('image_list', str(tmp_path / 's.lst'))
    it.set_param('image_bin', str(tmp_path / 's.bin'))
    it.set_param('silent', '1')
    for k, v in params.items():
        it.set_param(k, str(v))
    it.init()
    return it


def _static_iter(tmp_path):
    it = iter_imbin.ImageBinIterator()
    it.set_param('image_list', str(tmp_path / 's.lst'))
    it.set_param('image_bin', str(tmp_path / 's.bin'))
    it.set_param('silent', '1')
    it.init()
    return it


def _insts(it):
    return [(inst.index, inst.data.tobytes(), inst.label.tobytes())
            for inst in it]


def test_stream_bitwise_twin_while_growing(tmp_path, small_pages):
    """The acceptance property: a stream pass that tails the file WHILE
    a writer appends yields exactly the instance sequence a static
    imgbin pass yields over the final bytes."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    recs = _records(30)
    append_records(binp, lst, recs[:10])

    def writer():
        time.sleep(0.15)
        append_records(binp, lst, recs[10:22])
        time.sleep(0.15)
        append_records(binp, lst, recs[22:])

    t = threading.Thread(target=writer)
    t.start()
    got = _insts(_stream_iter(tmp_path, stream_idle=1.0, stream_poll=0.02))
    t.join()
    want = _insts(_static_iter(tmp_path))
    assert len(got) == 30
    assert got == want


def test_stream_snapshot_pass_replay_stable(tmp_path, small_pages):
    """``stream_idle=0``: one pass over the current bytes; replays yield
    the same prefix (append-only order is stable) and the iterator
    declares itself replay-stable — what supervised recovery re-winds
    on.  A pass started after growth sees the tail appended."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    append_records(binp, lst, _records(12))
    it = _stream_iter(tmp_path)
    assert it.is_replay_stable()
    first = _insts(it)
    assert [i for i, _, _ in first] == list(range(12))
    assert _insts(it) == first                 # replay: same prefix
    append_records(binp, lst, _records(8, start=12))
    grown = _insts(it)
    assert grown[:12] == first                 # prefix unchanged
    assert [i for i, _, _ in grown] == list(range(20))


def test_stream_rejects_shuffle_and_multipart(tmp_path):
    it = ImageBinStreamIterator()
    it.set_param('image_list', str(tmp_path / 's.lst'))
    it.set_param('image_bin', str(tmp_path / 's.bin'))
    it.set_param('shuffle', '1')
    with pytest.raises(ValueError, match='shuffle'):
        it.init()
    it2 = ImageBinStreamIterator()
    it2.set_param('image_conf_prefix', str(tmp_path / 'part%d'))
    it2.set_param('image_conf_ids', '0-1')
    with pytest.raises(ValueError, match='ONE appendable file'):
        it2.init()


def test_stream_incremental_refresh_reads_only_tail(tmp_path, small_pages,
                                                    monkeypatch):
    """Regression for the page-table refactor: catching up after growth
    header-scans ONLY the appended pages (scan_page_table is called with
    start_page = pages already indexed) and never re-yields consumed
    instances."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    append_records(binp, lst, _records(20, size=10))
    calls = []
    real = iter_imbin.scan_page_table

    def spy(path, start_page=0):
        calls.append(start_page)
        return real(path, start_page)

    monkeypatch.setattr(iter_imbin, 'scan_page_table', spy)
    it = _stream_iter(tmp_path)
    first = _insts(it)
    pages0 = len(it._tables[0][0])
    assert pages0 >= 2                       # multi-page under 2KB pages
    assert calls and calls[0] == 0
    append_records(binp, lst, _records(15, start=20, size=10))
    calls.clear()
    second = _insts(it)
    # the grown pass header-scanned ONLY from the already-indexed page on
    assert calls and min(calls) >= pages0
    assert [i for i, _, _ in second] == list(range(35))
    # static-twin equality over the final bytes
    assert second == _insts(_static_iter(tmp_path))


def test_scan_page_table_start_page(tmp_path, small_pages):
    """The factored index scan: start_page returns the page-count tail
    of the full scan (the unit under the stream's incremental refresh)."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    append_records(binp, lst, _records(20, size=10))
    full = iter_imbin.scan_page_table(binp)
    assert len(full) >= 3
    assert iter_imbin.scan_page_table(binp, start_page=1) == full[1:]
    assert iter_imbin.scan_page_table(binp, start_page=len(full)) == []


def test_stream_waits_for_lst_lines(tmp_path, small_pages):
    """A page visible before its .lst lines (a racing writer that broke
    the lines-first contract) is held back until the lines land, not
    mis-paired or fatal."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    recs = _records(6)
    append_records(binp, lst, recs[:3])
    # commit a page with NO lines (bypass the helper's ordering)
    page = BinaryPage()
    for _i, _l, blob in recs[3:]:
        assert page.push(blob)
    with open(binp, 'ab') as f:
        page.save(f)

    def late_lines():
        time.sleep(0.15)
        with open(lst, 'a') as f:
            for i, labels, _b in recs[3:]:
                f.write(f'{i}\t{labels[0]}\tstream\n')

    t = threading.Thread(target=late_lines)
    t.start()
    got = _insts(_stream_iter(tmp_path, stream_poll=0.02, stream_idle=0.5))
    t.join()
    assert [i for i, _, _ in got] == list(range(6))


def test_stream_through_chain_matches_static_imgbin(tmp_path, small_pages):
    """Through the full augment+batch chain (the trainer's view), the
    streaming source is bitwise-identical to static imgbin over the same
    bytes — including per-instance augmentation RNG (epoch-absolute
    index) and the nworker pool."""
    binp, lst = str(tmp_path / 's.bin'), str(tmp_path / 's.lst')
    append_records(binp, lst, _records(37, size=12))

    def chain(source, nworker):
        cfg = [('iter', source),
               ('image_list', lst), ('image_bin', binp),
               ('rand_crop', '1'), ('rand_mirror', '1'),
               ('input_shape', '3,8,8'), ('batch_size', '8'),
               ('round_batch', '1'), ('silent', '1'),
               ('iter', 'threadbuffer'), ('nworker', str(nworker))]
        it = create_iterator(cfg)
        it.init()
        out = [(b.data.tobytes(), b.label.tobytes(),
                b.inst_index.tobytes(), b.num_batch_padd) for b in it]
        close = getattr(it, 'close', None)
        if close:
            close(timeout=5.0)
        return out

    static = chain('imgbin', 1)
    assert chain('imgbin_stream', 1) == static
    assert chain('imgbin_stream', 4) == static


# --- freshness tracker ----------------------------------------------------

def test_freshness_tracker_samples_and_slo():
    log = faults.FailureLog()
    tr = FreshnessTracker(slo_s=0.05, log=log)
    t0 = time.monotonic()
    tr.record_step(10, t0)
    tr.record_swap(10, t0 + 0.01)
    # first serve closes the measurement; later serves don't re-sample
    fresh = tr.note_served(10)
    assert fresh is not None and fresh > 0
    assert tr.note_served(10) is None
    assert tr.stats.quantile('freshness_s', 0.5) == pytest.approx(fresh)
    assert tr.swaps == 1 and tr.unserved_swaps() == 0
    # breach: a sample beyond the SLO trips the typed counter + log
    tr2 = FreshnessTracker(slo_s=0.001, log=log)
    tr2.record_step(20, time.monotonic() - 1.0)
    tr2.record_swap(20)
    assert tr2.note_served(20) > 0.5
    assert tr2.breaches == 1
    assert isinstance(tr2.last_breach, faults.FreshnessSLOError)
    assert log.records('freshness_slo_breach')
    with pytest.raises(faults.FreshnessSLOError):
        tr2.check_strict()


def test_freshness_bootstrap_version_not_a_sample():
    """The boot version was never swapped — serving it measures nothing
    (the SLO is a property of swaps), and non-integer versions are
    ignored."""
    tr = FreshnessTracker()
    tr.record_step(0)
    assert tr.note_served(0) is None
    assert tr.note_served('v1.model') is None
    assert tr.stats.quantile('freshness_s', 0.5) != \
        tr.stats.quantile('freshness_s', 0.5)    # NaN: no samples


# --- registry swap stamps -------------------------------------------------

class _StampEngine:
    buckets = (1,)

    def __init__(self):
        self.version = -1

    def place_params(self, p):
        return p

    def warm_params(self, p):
        pass

    def swap_params(self, p, version=None):
        self.version = version


def test_registry_stamps_swap_step_and_age(tmp_path):
    from cxxnet_tpu.nnet import checkpoint
    from cxxnet_tpu.serve.registry import ModelRegistry
    eng = _StampEngine()
    reg = ModelRegistry(eng, str(tmp_path), current=-1,
                        loader=lambda e, p, retry=None: {})
    assert reg.last_swap_step == -1
    assert reg.last_swap_age_s() != reg.last_swap_age_s()   # NaN: never
    p = str(tmp_path / '0007.model')
    with open(p, 'wb') as f:
        f.write(b'payload')
    checkpoint.write_model_digest(p)
    assert reg.poll_once()
    assert reg.last_swap_step == 7 == eng.version
    age = reg.last_swap_age_s()
    assert 0 <= age < 5.0
    line = reg.report()
    assert '\tregistry-swaps:1' in line
    assert '\tregistry-last_swap_step:7' in line
    assert 'registry-last_swap_age_s:' in line


# --- digest-before-rename publish -----------------------------------------

def test_publish_model_file_digest_before_rename(tmp_path, monkeypatch):
    """The online publish order: the digest sidecar is on disk BEFORE the
    model file is renamed into place (a watcher never sees an
    unverifiable file), and the corrupt_model chaos event fires on the
    STAGED bytes — the published file deterministically fails digest
    verification, with no window in which the good bytes were visible."""
    from cxxnet_tpu.nnet import checkpoint
    seen = {}
    real_replace = os.replace

    def spy(src, dst):
        if str(dst).endswith('.model'):
            seen['sidecar_at_rename'] = os.path.exists(
                checkpoint.model_digest_path(str(dst)))
        return real_replace(src, dst)

    monkeypatch.setattr(checkpoint.os, 'replace', spy)
    p = str(tmp_path / '0001.model')
    checkpoint.publish_model_file(p, lambda f: f.write(b'payload' * 64))
    assert seen['sidecar_at_rename'] is True
    assert checkpoint.verify_model_digest(p) is None
    # corrupt the staged file of the next publish: digest mismatch from
    # the first instant the file exists
    plan = faults.FaultPlan(corrupt_model=(1,))
    prev = faults.install_plan(plan)
    try:
        p2 = str(tmp_path / '0002.model')
        checkpoint.publish_model_file(p2,
                                      lambda f: f.write(b'payload' * 64))
    finally:
        faults.install_plan(prev)
    assert plan.fired() == ['corrupt_model=1']
    assert os.path.exists(p2)
    assert checkpoint.verify_model_digest(p2) is not None


# --- the pipeline ---------------------------------------------------------

MLP_CONF = """
netconfig=start
layer[+1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 8
dev = cpu
eta = 0.05
momentum = 0.9
metric[label] = error
"""


class ListIter(IIterator):
    def __init__(self, batches):
        self.batches = batches

    def __iter__(self):
        return iter(self.batches)


def _make_batches(n, seed=0, bs=8):
    rng = np.random.RandomState(seed)
    centers = np.random.RandomState(99).randn(4, 16).astype(np.float32) * 2
    out = []
    for _ in range(n):
        y = rng.randint(0, 4, bs)
        x = centers[y] + 0.2 * rng.randn(bs, 16).astype(np.float32)
        out.append(DataBatch(x.reshape(bs, 1, 1, 16),
                             y[:, None].astype(np.float32)))
    return out


def _serve_factory():
    return NetTrainer(parse_config_string(MLP_CONF + 'inference_only = 1\n'))


def _request_source(seed=7):
    rng = np.random.RandomState(seed)
    centers = np.random.RandomState(99).randn(4, 16).astype(np.float32) * 2

    def req():
        y = rng.randint(0, 4, 4)
        return (centers[y] + 0.2 * rng.randn(4, 16).astype(np.float32)
                ).reshape(4, 1, 1, 16)
    return req


def _run_pipeline(tmp, batches, rounds=2, fault_plan=None, qps=200.0,
                  save_every=10, log=None, settled=None, **cfg_kw):
    """``settled(pipe)``: what the caller waits to see (up to 10 s) before
    the pipeline closes.  ``run`` returns once the last checkpoint is
    written; the watcher swaps it in on its own clock."""
    tr = NetTrainer(parse_config_string(MLP_CONF))
    tr.init_model()
    base = dict(model_dir=os.path.join(tmp, 'm'),
                save_every=save_every, reload_poll=0.02,
                buckets=(4, 8), qps=qps, watchdog_deadline=30.0,
                freshness_slo=30.0, silent=True)
    base.update(cfg_kw)
    cfg = OnlineConfig(**base)
    prev = faults.install_plan(fault_plan)
    pipe = OnlinePipeline(tr, ListIter(batches), _serve_factory, cfg,
                          request_source=_request_source(),
                          failure_log=log)
    try:
        summary = pipe.run(num_rounds=rounds, out=_io.StringIO())
        deadline = time.monotonic() + 10.0
        while settled is not None and not settled(pipe) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        pipe.close(timeout=10.0)
        faults.install_plan(prev)
    return pipe, summary, tr


def test_online_pipeline_acceptance(tmp_path):
    """The ISSUE acceptance run: one pipeline trains, publishes async
    every N steps, hot-swaps the colocated server >= 3 times with ZERO
    dropped requests, and reports freshness p50/p99 on the eval line."""
    batches = _make_batches(40)
    tr = NetTrainer(parse_config_string(MLP_CONF))
    tr.init_model()
    cfg = OnlineConfig(model_dir=str(tmp_path / 'm'), save_every=10,
                       reload_poll=0.02, buckets=(4, 8), qps=200.0,
                       watchdog_deadline=30.0, freshness_slo=30.0,
                       silent=True)
    pipe = OnlinePipeline(tr, ListIter(batches), _serve_factory, cfg,
                          request_source=_request_source())
    out = _io.StringIO()
    try:
        summary = pipe.run(num_rounds=2, out=out)
    finally:
        pipe.close(timeout=10.0)
    assert summary['swaps'] >= 3
    assert summary['dropped'] == 0
    assert summary['served'] > 0
    assert summary['steps'] == 80
    assert summary['freshness_p50_s'] > 0          # measured, not NaN
    assert summary['freshness_p99_s'] >= summary['freshness_p50_s']
    assert summary['slo_breaches'] == 0
    lines = out.getvalue().strip().splitlines()
    assert len(lines) == 2
    for line in lines:
        assert 'online-freshness_s.p50:' in line
        assert 'online-freshness_s.p99:' in line
        assert 'online-swaps:' in line
        assert 'online-dropped:0' in line
    # the serving half: registry stamps ride the serve report
    rep = pipe.serve_report()
    assert 'registry-last_swap_step:' in rep
    # model files are digest-sidecar'd (the registry verified them)
    models = [f for f in os.listdir(tmp_path / 'm')
              if f.endswith('.model')]
    assert len(models) >= 4
    assert all(os.path.exists(str(tmp_path / 'm' / (f + '.crc32')))
               for f in models)


def test_online_freshness_strict_raises_after_run(tmp_path):
    """freshness_strict=1: an impossible SLO raises the typed error at
    the END of the run (training and serving complete first)."""
    batches = _make_batches(30)
    with pytest.raises(faults.FreshnessSLOError):
        _run_pipeline(str(tmp_path), batches, rounds=1,
                      freshness_slo=1e-9, freshness_strict=True)


def test_online_chaos_drill_full_loop(tmp_path):
    """THE chaos drill (ISSUE acceptance): writer fault + corrupt
    serving checkpoint + NaN streak all fire in ONE online run.  The
    served version sequence never regresses and never includes the
    poisoned checkpoint; the trainer recovers and ends BITWISE equal to
    a fault-free twin on the same batches."""
    batches = _make_batches(40, seed=3)
    # commit #2 is the step-10 publish (after the bootstrap); nan streak
    # at steps 13/14 trips the breaker (supervisor nan_breaker=3 default
    # needs 3): use 13,14,15; raise_on_write=2 hits an early write and
    # must be retried transparently
    plan = faults.FaultPlan(
        seed=11, raise_on_write=(2,), corrupt_model=(2,),
        nan_at_step=(13, 14, 15))
    log = faults.FailureLog()
    pipe, summary, chaos_tr = _run_pipeline(
        str(tmp_path / 'chaos'), batches, rounds=2, fault_plan=plan,
        log=log)
    fired = plan.fired()
    assert 'raise_on_write=2' in fired
    assert 'corrupt_model=2' in fired
    assert any(f.startswith('nan_at_step=') for f in fired)
    # the NaN streak was detected and recovered from
    assert log.records('DivergenceError')
    assert log.records('restored')
    assert summary['restarts'] >= 1
    # served versions: strictly increasing, poisoned step 10 never served
    swap_steps = [s for s, _ in sorted(
        pipe.tracker._swap_t.items(), key=lambda kv: kv[1])]
    assert swap_steps == sorted(swap_steps)
    assert 10 not in swap_steps, \
        'the corrupted checkpoint must never be swapped in'
    assert pipe.registry.last_swap_step > 10
    # the registry rejected (not served) the poisoned file
    assert any(s == 'REJECTED' for s in pipe.registry.states())
    # zero dropped requests through all of it
    assert summary['dropped'] == 0
    # bitwise twin: same batches, no faults
    _pipe2, summary2, clean_tr = _run_pipeline(
        str(tmp_path / 'clean'), batches, rounds=2)
    assert summary2['steps'] == summary['steps'] == 80
    for lk, fields in clean_tr.params.items():
        for fk in fields:
            assert np.array_equal(np.asarray(chaos_tr.params[lk][fk]),
                                  np.asarray(clean_tr.params[lk][fk])), \
                f'chaos run diverged from fault-free twin at {lk}/{fk}'


def test_online_save_failure_degrades_freshness_not_training(tmp_path,
                                                             monkeypatch):
    """A serving-checkpoint write that fails past its retries is
    recorded (``async_save_failed``) and SKIPPED: training continues,
    later checkpoints still publish and swap, the server never sees the
    lost step, and nothing raises."""
    from cxxnet_tpu.nnet import checkpoint
    real = checkpoint.publish_model_file

    def flaky(path, write_fn, retry=None):
        if path.endswith('0008.model'):
            raise faults.RetryError('publish_model', 4,
                                    OSError('disk gone'))
        return real(path, write_fn, retry=retry)

    monkeypatch.setattr(checkpoint, 'publish_model_file', flaky)
    log = faults.FailureLog()
    pipe, summary, _tr = _run_pipeline(
        str(tmp_path), _make_batches(24, seed=5), rounds=1, log=log,
        save_every=8,
        settled=lambda p: any(s > 8 for s in list(p.tracker._swap_t)))
    assert summary['steps'] == 24
    assert summary['dropped'] == 0
    assert summary['save_failures'] >= 1          # the lost 0008 publish
    assert log.records('async_save_failed')
    swapped = sorted(pipe.tracker._swap_t)
    assert 8 not in swapped                       # never served
    assert any(s > 8 for s in swapped)            # ...but later steps are


# --- wrapper / capi surfaces ----------------------------------------------

def test_wrapper_online_surface(tmp_path):
    from cxxnet_tpu import capi, wrapper
    net = wrapper.Net(dev='cpu', cfg=MLP_CONF)
    net.set_param('seed', 1)
    net.init_model()
    batches = _make_batches(20, seed=9)
    net.online_start(ListIter(batches), str(tmp_path / 'm'), rounds=2,
                     save_every=8, reload=0.02, buckets='4,8',
                     watchdog_deadline=30.0)
    rows = _request_source()()
    # requests flow while training runs in the background
    scores = net.online_scores(rows)
    assert scores.shape == (4, 4)
    pred = net.online_predict(rows)
    assert pred.shape == (4,)
    summary = net.online_wait(timeout=120.0)
    assert summary['steps'] == 40
    assert summary['swaps'] >= 2
    stats = net.online_stats()
    assert 'online-swaps:' in stats and 'registry-last_swap_step:' in stats
    # capi mirrors
    assert 'online-swaps:' in capi.net_online_stats(net)
    import json
    assert json.loads(capi.net_online_wait(net))['steps'] == 40
    net.online_stop(timeout=10.0)
    # idempotent + restartable guard
    net.online_stop()
    with pytest.raises(RuntimeError, match='online_start'):
        net.online_stats()


def test_capi_online_start_parses_cfg(tmp_path):
    from cxxnet_tpu import capi
    net = capi.net_create('cpu', MLP_CONF)
    net.set_param('seed', 2)
    net.init_model()
    batches = _make_batches(10, seed=2)
    capi.net_online_start(
        net, ListIter(batches),
        f'model_dir={tmp_path}/m;rounds=1;save_every=5;reload=0.02;'
        f'buckets=4:8;freshness_slo=30;watchdog_deadline=30')
    rows = np.ascontiguousarray(_request_source()())
    out = capi.net_online_predict(net, memoryview(rows.tobytes()),
                                  rows.shape)
    assert out.shape == (4,)
    capi.net_online_wait(net)
    capi.net_online_stop(net)
    with pytest.raises(ValueError, match='model_dir'):
        capi.net_online_start(net, ListIter(batches), 'rounds=1')


# --- CLI drive ------------------------------------------------------------

def test_cli_task_online_e2e(tmp_path):
    """task=online through the real CLI: trains over mnist, serves the
    pred section's rows at online.qps, hot-swaps >= 3 times with zero
    drops, freshness gauges on every eval line, summary JSON on stdout."""
    write_mnist(str(tmp_path), n=256, rows=8, cols=8, seed=4)
    conf = tmp_path / 'online.conf'
    conf.write_text(f"""
data = train
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
  shuffle = 0
iter = end
pred = pred.txt
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
netconfig=start
layer[+1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 3
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,64
batch_size = 16
dev = cpu
eta = 0.05
momentum = 0.9
metric[label] = error
task = online
num_round = 2
online.save_every = 5
online.freshness_slo = 60
online.reload = 0.02
online.qps = 100
serve.buckets = 8,16
""")
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO + os.pathsep + os.environ.get('PYTHONPATH',
                                                             ''))
    r = subprocess.run(
        [sys.executable, '-m', 'cxxnet_tpu.main', str(conf)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    swaps = [ln for ln in r.stdout.splitlines()
             if ln.startswith('online: hot-swapped step ')]
    assert len(swaps) >= 3, r.stdout
    import json
    summary_line = [ln for ln in r.stdout.splitlines()
                    if ln.startswith('online summary: ')]
    assert summary_line, r.stdout
    summary = json.loads(summary_line[0][len('online summary: '):])
    assert summary['dropped'] == 0
    assert summary['swaps'] >= 3
    assert summary['slo_breaches'] == 0
    eval_lines = [ln for ln in r.stderr.splitlines()
                  if ln.startswith('[') and 'online-freshness_s.p50:' in ln]
    assert len(eval_lines) == 2, r.stderr
    assert 'online-freshness_s.p99:' in eval_lines[-1]
    assert '[online]' in r.stderr and 'registry-swaps:' in r.stderr
    # serving checkpoints landed with digests, by STEP number
    models = sorted(f for f in os.listdir(tmp_path / 'models')
                    if f.endswith('.model'))
    assert len(models) >= 4


def test_cli_task_online_continue_resumes_from_newest_step(tmp_path):
    """continue=1 on task=online: the round-counter scan is gap-tolerant
    (step-named publishes leave holes — 0005, 0010, ...), the newest
    step-named file is adopted, and the publish counter re-arms so the
    resumed run's checkpoints continue STRICTLY past it instead of
    overwriting stale counters."""
    write_mnist(str(tmp_path), n=128, rows=8, cols=8, seed=6)
    conf = tmp_path / 'online.conf'
    conf.write_text(f"""
data = train
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
  shuffle = 0
iter = end
netconfig=start
layer[+1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 3
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,64
batch_size = 16
dev = cpu
eta = 0.05
metric[label] = error
task = online
num_round = 1
online.save_every = 5
online.reload = 0.02
""")
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO + os.pathsep + os.environ.get('PYTHONPATH',
                                                             ''))

    def run(*overrides):
        r = subprocess.run(
            [sys.executable, '-m', 'cxxnet_tpu.main', str(conf),
             *overrides],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=420)
        assert r.returncode == 0, r.stdout + r.stderr
        return r

    run()
    first = sorted(int(f.split('.')[0]) for f in
                   os.listdir(tmp_path / 'models') if f.endswith('.model'))
    assert len(first) >= 2 and first[-1] >= 5   # step-named, with gaps
    r2 = run('continue=1')
    assert f'Init: continue online run from step {first[-1]}' in r2.stdout
    after = sorted(int(f.split('.')[0]) for f in
                   os.listdir(tmp_path / 'models') if f.endswith('.model'))
    new = [c for c in after if c > first[-1]]
    assert new, 'resumed run must publish past the adopted step'
    # nothing regressed or was overwritten: the old set is a prefix
    assert after[:len(first)] == first
