"""Continuous-batching decode engine suite (serve/decode.py).

The load-bearing claim is the bitwise-twin discipline: a request's token
stream through the slot/page engine equals an offline
``transformer.generate`` call with the same seed — no matter when the
request joined the running loop, which slots shared its steps, or how
its cache was paged.  Plus the paged-vs-dense logit identity, token-
granular shed/deadline errors, the multi-model memory budgeter, and the
``%04d.lm`` registry watch.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from cxxnet_tpu.models import transformer as T
from cxxnet_tpu.runtime.faults import (DeadlineExceededError,
                                       DecodePagesExhaustedError,
                                       DecodeSlotsExhaustedError,
                                       MemoryBudgetExceededError,
                                       TokenDeadlineExceededError)
from cxxnet_tpu.serve.decode import (DecodeEngine, DecodeService,
                                     LM_PATTERN, lm_loader, load_lm_params,
                                     save_lm_params)
from cxxnet_tpu.serve.registry import (MemoryBudgeter, ModelRegistry,
                                       MultiModelRegistry)

pytestmark = pytest.mark.serve_decode

CFG = T.TransformerConfig(vocab_size=64, d_model=32, num_heads=4,
                          d_ff=48, num_stages=2, seq_len=32, attn='local')


def _params(seed: int = 0):
    return T.init_params(np.random.RandomState(seed), CFG)


def _prompt(rng, lo=1, hi=12):
    return rng.randint(0, CFG.vocab_size,
                       (1, int(rng.randint(lo, hi)))).astype(np.int32)


def _wait_ok(req, timeout=60):
    assert req.event.wait(timeout), 'request never completed'
    if req.error is not None:
        raise req.error
    return req.result


def _offline(params, prompt, max_new, temperature=0.0, rng=None,
             eos_id=None):
    return np.asarray(T.generate(params, prompt, max_new, CFG,
                                 temperature=temperature, rng=rng,
                                 eos_id=eos_id))[0]


def _assert_twin(got, off):
    """Engine streams stop at the first EOS; offline keeps emitting it."""
    got = np.asarray(got)
    assert len(got) >= 1
    np.testing.assert_array_equal(got, off[:len(got)])
    if len(got) < len(off):
        assert (off[len(got):] == off[len(got) - 1]).all()


@pytest.fixture(scope='module')
def engine():
    eng = DecodeEngine(_params(), CFG, slots=4, pages=64, page_size=8,
                       max_prompt=16, max_new_bound=64)
    yield eng
    eng.close(30)


# --- paged-vs-dense bitwise identity ---------------------------------------

class TestPagedVsDense:
    def _setup_caches(self, w_pad: int):
        """Dense cache via prefill + paged pool holding the same rows."""
        params = _params()
        rng = np.random.RandomState(3)
        s0 = 8
        prompt = rng.randint(0, 64, (2, s0)).astype(np.int32)
        ks, vs, logits0 = jax.jit(
            lambda p, t, w: T.prefill_kv(p, t, w, CFG))(
                params, prompt, np.int32(w_pad))
        hd = CFG.d_model // CFG.num_heads
        Tlen = 32
        kc = np.zeros((CFG.num_stages, 2, Tlen, CFG.num_heads, hd),
                      np.float32)
        vc = np.zeros_like(kc)
        kc[:, :, :s0] = np.asarray(ks)
        vc[:, :, :s0] = np.asarray(vs)
        tok0 = np.asarray(logits0.argmax(-1), np.int32)
        return params, kc, vc, tok0, s0, Tlen

    @pytest.mark.parametrize('w_pad', [0, 3])
    def test_paged_step_logits_bitwise_equal_dense(self, w_pad):
        """One decode step over a page-table-gathered cache must produce
        BITWISE the dense-cache logits — including the left-pad
        bucket-masking leg (w>0: pad slots never attended)."""
        params, kc, vc, tok0, s0, Tlen = self._setup_caches(w_pad)
        hd = CFG.d_model // CFG.num_heads
        ps, n_slots = 8, 2
        pp = Tlen // ps                                   # logical pages
        # scatter the dense rows into a shuffled physical page pool
        n_phys = n_slots * pp + 3
        kpool = np.zeros((CFG.num_stages, n_phys, ps, CFG.num_heads, hd),
                         np.float32)
        vpool = np.zeros_like(kpool)
        rng = np.random.RandomState(9)
        phys = rng.permutation(np.arange(1, n_phys))[:n_slots * pp]
        table = phys.reshape(n_slots, pp).astype(np.int32)
        for b in range(n_slots):
            for lp in range(pp):
                kpool[:, table[b, lp]] = kc[:, b, lp * ps:(lp + 1) * ps]
                vpool[:, table[b, lp]] = vc[:, b, lp * ps:(lp + 1) * ps]

        # dense reference: the scalar-t path generate() itself scans
        t_scalar = np.int32(s0)
        w_scalar = np.int32(w_pad)
        dense = jax.jit(lambda p, tok, kc, vc, t, w: T.decode_step(
            p, CFG, tok, kc, vc, t, w))(
                params, tok0, jax.numpy.asarray(kc),
                jax.numpy.asarray(vc), t_scalar, w_scalar)

        # paged path: gather pages -> per-row t/w vectors (the engine's
        # step shape), same shared decode_step math
        def paged(p, kpool, vpool, table, tok, t, w):
            kcg = kpool[:, table].reshape(CFG.num_stages, n_slots, Tlen,
                                          CFG.num_heads, hd)
            vcg = vpool[:, table].reshape(CFG.num_stages, n_slots, Tlen,
                                          CFG.num_heads, hd)
            return T.decode_step(p, CFG, tok, kcg, vcg, t, w)

        tv = np.full((n_slots,), s0, np.int32)
        wv = np.full((n_slots,), w_pad, np.int32)
        pg = jax.jit(paged)(params, kpool, vpool, table, tok0, tv, wv)

        np.testing.assert_array_equal(np.asarray(dense[0]),
                                      np.asarray(pg[0]))
        # the newly written K/V rows agree too (what the engine scatters)
        np.testing.assert_array_equal(np.asarray(dense[3]),
                                      np.asarray(pg[3]))
        np.testing.assert_array_equal(np.asarray(dense[4]),
                                      np.asarray(pg[4]))


# --- stream twins -----------------------------------------------------------

class TestStreamTwins:
    def test_greedy_staggered_mixed_lengths(self, engine):
        """Mixed prompt lengths, staggered joins: every stream equals
        its offline generate twin; emissions are incremental."""
        rng = np.random.RandomState(1)
        prompts = [_prompt(rng) for _ in range(6)]
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(engine.submit_direct(p, max_new=5 + i))
            time.sleep(0.01)            # later requests join mid-decode
        for i, (p, r) in enumerate(zip(prompts, reqs)):
            got = _wait_ok(r)
            assert len(got) == 5 + i
            _assert_twin(got, _offline(engine.params, p, 5 + i))
            assert len(r.token_times) == len(got)
            assert all(b >= a for a, b in
                       zip(r.token_times, r.token_times[1:]))

    def test_sampled_rng_schedule_matches_offline(self, engine):
        """Per-request sampling keys: stream n pulls key n of
        split(rng, max_new+1) — exactly generate()'s schedule, even with
        slots sharing steps."""
        rng = np.random.RandomState(2)
        prompts = [_prompt(rng) for _ in range(4)]
        keys = [jax.random.PRNGKey(50 + i) for i in range(4)]
        reqs = [engine.submit_direct(p, max_new=8, temperature=0.8,
                                     rng=k)
                for p, k in zip(prompts, keys)]
        for p, k, r in zip(prompts, keys, reqs):
            got = _wait_ok(r)
            _assert_twin(got, _offline(engine.params, p, 8,
                                       temperature=0.8, rng=k))

    def test_mixed_greedy_and_sampled_share_steps(self, engine):
        rng = np.random.RandomState(7)
        pg, ps_ = _prompt(rng), _prompt(rng)
        key = jax.random.PRNGKey(123)
        r1 = engine.submit_direct(pg, max_new=6)
        r2 = engine.submit_direct(ps_, max_new=6, temperature=1.2,
                                  rng=key)
        _assert_twin(_wait_ok(r1), _offline(engine.params, pg, 6))
        _assert_twin(_wait_ok(r2), _offline(engine.params, ps_, 6,
                                            temperature=1.2, rng=key))

    def test_max_new_one_is_prefill_only(self, engine):
        rng = np.random.RandomState(8)
        p = _prompt(rng)
        got = _wait_ok(engine.submit_direct(p, max_new=1))
        assert got.shape == (1,)
        _assert_twin(got, _offline(engine.params, p, 1))


# --- slot/page lifecycle ----------------------------------------------------

class TestSlotLifecycle:
    def test_eos_frees_slot_early_and_stream_prefix_matches(self):
        params = _params()
        rng = np.random.RandomState(4)
        p = _prompt(rng)
        base = _offline(params, p, 12)
        eos = int(base[2])              # fires at stream position 2
        eng = DecodeEngine(params, CFG, slots=2, pages=32, page_size=8,
                           max_prompt=16, max_new_bound=16, eos_id=eos)
        try:
            free0 = len(eng._free_pages)
            got = _wait_ok(eng.submit_direct(p, max_new=12))
            off = _offline(params, p, 12, eos_id=eos)
            _assert_twin(got, off)
            assert got[-1] == eos and len(got) <= 12
            deadline = time.time() + 5
            while len(eng._free_pages) != free0 and time.time() < deadline:
                time.sleep(0.01)
            assert len(eng._free_pages) == free0, \
                'EOS must return every page to the pool'
        finally:
            eng.close(30)

    def test_queueing_when_slots_full(self):
        """More requests than slots: later ones wait, join as slots
        free, and still match their offline twins."""
        params = _params()
        svc = DecodeService(params, CFG, slots=1, pages=32, page_size=8,
                            max_prompt=16, max_new_bound=16,
                            deadline=60.0)
        try:
            rng = np.random.RandomState(5)
            prompts = [_prompt(rng) for _ in range(3)]
            reqs = [svc.submit_async(p, 6) for p in prompts]
            for p, r in zip(prompts, reqs):
                got = svc.batcher.wait(r)
                _assert_twin(got, _offline(params, p, 6))
        finally:
            svc.close(30)

    def test_token_deadline_mid_stream(self, engine):
        """A deadline that expires mid-stream sheds at token granularity:
        typed error carrying the emitted count, slot and pages freed."""
        rng = np.random.RandomState(6)
        p = _prompt(rng)
        req = engine.submit_direct(p, max_new=64, deadline=0.0001)
        assert req.event.wait(30)
        assert isinstance(req.error, TokenDeadlineExceededError)
        assert req.error.tokens_emitted >= 1
        assert len(req.tokens) == req.error.tokens_emitted
        deadline = time.time() + 5
        while engine.busy() and time.time() < deadline:
            time.sleep(0.01)
        assert not engine.busy()

    def test_page_pool_exhaustion_preempts_youngest(self):
        params = _params()
        eng = DecodeEngine(params, CFG, slots=2, pages=12, page_size=2,
                           max_prompt=8, max_new_bound=8)
        try:
            rng = np.random.RandomState(7)
            p1, p2 = _prompt(rng, 1, 4), _prompt(rng, 1, 4)
            r1 = eng.submit_direct(p1, max_new=8)
            r2 = eng.submit_direct(p2, max_new=8)
            assert r1.event.wait(60) and r2.event.wait(60)
            # oldest stream finishes; the youngest is the typed victim
            assert r1.error is None
            _assert_twin(r1.result, _offline(params, p1, 8))
            assert isinstance(r2.error, DecodePagesExhaustedError)
            assert r2.error.tokens_emitted >= 1
        finally:
            eng.close(30)

    def test_unshared_pages_refcount_to_zero_and_low_water_tracked(self):
        """The refcount plumbing (PR 12 prefix sharing) is invisible on
        the unshared path: every page a retired stream held goes back to
        refcount 0 / the free list, and the free-page low-water mark
        gauge records the deepest draw."""
        params = _params()
        eng = DecodeEngine(params, CFG, slots=2, pages=32, page_size=8,
                           max_prompt=16, max_new_bound=16)
        try:
            rng = np.random.RandomState(13)
            for _ in range(2):
                p = _prompt(rng)
                _assert_twin(_wait_ok(eng.submit_direct(p, max_new=6)),
                             _offline(params, p, 6))
            deadline = time.time() + 5
            while time.time() < deadline:
                with eng._cond:
                    if len(eng._free_pages) == eng.n_pages - 1:
                        break
                time.sleep(0.01)
            with eng._cond:
                assert len(eng._free_pages) == eng.n_pages - 1
                assert (eng._page_refs == 0).all()
                assert eng._free_min < eng.n_pages - 1
            assert 'pg-free_pages_min' in eng.report('pg')
        finally:
            eng.close(30)

    def test_inadmissible_requests_typed(self, engine):
        rng = np.random.RandomState(9)
        r = engine.submit_direct(rng.randint(0, 64, (1, 200)), max_new=4)
        assert isinstance(r.error, DecodeSlotsExhaustedError)
        r = engine.submit_direct(_prompt(rng), max_new=1000)
        assert isinstance(r.error, DecodeSlotsExhaustedError)

    def test_resident_bytes_includes_kv_pool(self):
        """The budgeter ledger entry is params + the FULL paged pool:
        pages x page_size x stages x heads x head_dim x dtype, K and V —
        pinned closed-form so the dominant allocation can never silently
        fall out of eviction decisions again."""
        params = _params()
        eng = DecodeEngine(params, CFG, slots=2, pages=48, page_size=8,
                           max_prompt=16, max_new_bound=16)
        try:
            hd = CFG.d_model // CFG.num_heads
            itemsize = jax.numpy.dtype(CFG.dtype).itemsize
            pool = 2 * CFG.num_stages * 48 * 8 * CFG.num_heads * hd \
                * itemsize
            pbytes = sum(np.asarray(l).nbytes
                         for l in jax.tree.leaves(params))
            assert eng.resident_bytes() == pool + pbytes
            assert pool > pbytes   # the pool IS the dominant allocation
        finally:
            eng.close(30)


# --- hot swap ---------------------------------------------------------------

class TestHotSwap:
    def test_swap_mid_decode_drains_in_flight_on_old_params(self):
        pa, pb = _params(0), _params(11)
        eng = DecodeEngine(pa, CFG, slots=2, pages=64, page_size=8,
                           max_prompt=16, max_new_bound=64)
        try:
            rng = np.random.RandomState(10)
            p1, p2 = _prompt(rng), _prompt(rng)
            r1 = eng.submit_direct(p1, max_new=48)
            time.sleep(0.02)            # r1 is mid-decode
            assert not r1.event.is_set()
            eng.swap_params(pb, version='B')   # blocks through the drain
            assert r1.event.is_set(), 'swap returned before drain'
            assert r1.error is None, 'zero dropped requests across swap'
            _assert_twin(r1.result, _offline(pa, p1, 48))
            assert eng.version == 'B' and eng.swap_count == 1
            r2 = eng.submit_direct(p2, max_new=8)
            _assert_twin(_wait_ok(r2), _offline(pb, p2, 8))
        finally:
            eng.close(30)

    def test_registry_hot_swap_mid_decode_zero_drops(self, tmp_path):
        """The acceptance leg: the registry cycle lands a newer ``.lm``
        while a stream is mid-decode — the swap drains (in-flight
        finishes on the OLD params), nothing drops, and the next request
        serves the new checkpoint."""
        pa, pb = _params(0), _params(21)
        mdir = tmp_path / 'lms'
        mdir.mkdir()
        save_lm_params(str(mdir / '0001.lm'), pa)
        eng = DecodeEngine(pa, CFG, slots=2, pages=64, page_size=8,
                           max_prompt=16, max_new_bound=64)
        reg = ModelRegistry(eng, str(mdir), current=1,
                            pattern=LM_PATTERN, loader=lm_loader)
        try:
            assert not reg.poll_once()         # nothing newer
            rng = np.random.RandomState(12)
            p1, p2 = _prompt(rng), _prompt(rng)
            r1 = eng.submit_direct(p1, max_new=48)   # long, mid-decode
            assert not r1.event.is_set()
            save_lm_params(str(mdir / '0002.lm'), pb)
            assert reg.poll_once()             # verify→load→warm→SWAP
            assert reg.current == 2
            assert 'SWAPPED' in reg.states()
            assert r1.event.is_set(), 'poll returned before the drain'
            assert r1.error is None, 'zero dropped requests across swap'
            _assert_twin(r1.result, _offline(pa, p1, 48))
            _assert_twin(_wait_ok(eng.submit_direct(p2, max_new=6)),
                         _offline(pb, p2, 6))
        finally:
            eng.close(30)

    def test_registry_rejects_corrupt_lm_and_keeps_serving(self, tmp_path):
        pa, pb = _params(0), _params(22)
        mdir = tmp_path / 'lms'
        mdir.mkdir()
        save_lm_params(str(mdir / '0001.lm'), pa)
        eng = DecodeEngine(pa, CFG, slots=2, pages=32, page_size=8,
                           max_prompt=16, max_new_bound=16)
        reg = ModelRegistry(eng, str(mdir), current=1,
                            pattern=LM_PATTERN, loader=lm_loader)
        try:
            path = str(mdir / '0002.lm')
            save_lm_params(path, pb)
            with open(path, 'r+b') as f:        # silent byte corruption
                f.seek(100)
                f.write(b'\xff\xff\xff\xff')
            assert not reg.poll_once()
            assert 'REJECTED' in reg.states()
            assert reg.current == 1
            rng = np.random.RandomState(13)
            p = _prompt(rng)
            _assert_twin(_wait_ok(eng.submit_direct(p, max_new=4)),
                         _offline(pa, p, 4))    # old params keep serving
        finally:
            eng.close(30)


# --- memory budgeter --------------------------------------------------------

class _StubEngine:
    def __init__(self, nbytes, busy=False):
        self.nbytes = nbytes
        self._busy = busy
        self.closed = False
        self.version = 0

    def resident_bytes(self):
        return self.nbytes

    def busy(self):
        return self._busy

    def close(self, timeout=None):
        self.closed = True


class TestBudgeter:
    def test_ledger_accounting(self):
        b = MemoryBudgeter(100)
        b.account('a', 60)
        b.account('b', 30)
        assert b.usage() == 90 and b.over_budget() == 0
        b.account('c', 30)
        assert b.over_budget() == 20
        assert b.release('a') == 60
        assert b.usage() == 60
        assert MemoryBudgeter(0).over_budget() == 0   # unbounded

    def test_evicts_coldest_never_serving(self):
        fleet = MultiModelRegistry(mem_budget=130)
        engines = {}

        def mk(mid, nbytes, busy=False):
            def factory():
                engines[mid] = _StubEngine(nbytes, busy)
                return engines[mid]
            return factory

        fleet.add_model('a', mk('a', 60), load=True)
        time.sleep(0.01)
        fleet.add_model('b', mk('b', 60), load=True)
        assert fleet.loaded() == ['a', 'b']
        # loading c (60) pushes past 130: 'a' is coldest -> evicted
        fleet.add_model('c', mk('c', 60), load=True)
        assert fleet.loaded() == ['b', 'c']
        assert engines['a'].closed
        assert fleet.evictions == 1
        # touch b (hot), then reload a: c is now coldest
        fleet.get('b')
        fleet.get('a')
        assert fleet.loaded() == ['a', 'b']

    def test_budget_exceeded_when_everything_is_serving(self):
        fleet = MultiModelRegistry(mem_budget=100)
        fleet.add_model('serving', lambda: _StubEngine(80, busy=True),
                        load=True)
        fleet.add_model('cold', lambda: _StubEngine(80))
        with pytest.raises(MemoryBudgetExceededError):
            fleet.get('cold')
        # the serving model was never touched; the cold load rolled back
        assert fleet.loaded() == ['serving']
        assert fleet.budgeter.usage() == 80
        # once the serving model goes idle the cold one can displace it
        fleet.get('serving')._busy = False
        fleet.get('cold')
        assert fleet.loaded() == ['cold']

    def test_lease_blocks_eviction_until_block_exits(self):
        """The get()-then-forward race: a leased engine is never an
        eviction victim even while idle (busy() false); the same load
        succeeds once the lease is released."""
        fleet = MultiModelRegistry(mem_budget=100)
        fleet.add_model('a', lambda: _StubEngine(80), load=True)
        fleet.add_model('b', lambda: _StubEngine(80))
        with fleet.lease('a') as eng:
            assert not eng.busy()          # idle — but protected
            with pytest.raises(MemoryBudgetExceededError):
                fleet.get('b')
            assert fleet.loaded() == ['a']
        fleet.get('b')                     # lease released: evictable
        assert fleet.loaded() == ['b']

    def test_real_decode_engines_under_budget(self):
        """Acceptance leg: a second model loading under memory pressure
        evicts the cold model, never the one with in-flight streams."""
        pa, pb = _params(0), _params(31)
        # one engine is ~140KB resident: the budget fits one, never two
        fleet = MultiModelRegistry(mem_budget=200_000)

        def mk(params):
            return lambda: DecodeEngine(params, CFG, slots=2, pages=16,
                                        page_size=8, max_prompt=16,
                                        max_new_bound=32)

        try:
            fleet.add_model('a', mk(pa), load=True)
            eng_a = fleet.get('a')
            rng = np.random.RandomState(14)
            p = _prompt(rng)
            req = eng_a.submit_direct(p, max_new=32)   # 'a' is serving
            with pytest.raises(MemoryBudgetExceededError):
                fleet.add_model('b', mk(pb), load=True)
            assert fleet.loaded() == ['a']
            got = _wait_ok(req)                        # never dropped
            _assert_twin(got, _offline(pa, p, 32))
            deadline = time.time() + 5
            while eng_a.busy() and time.time() < deadline:
                time.sleep(0.01)
            fleet.get('b')                 # idle now: cold 'a' evicted
            assert fleet.loaded() == ['b']
        finally:
            fleet.close(30)


# --- gen cache satellites ---------------------------------------------------

class TestGenCacheStats:
    def test_hit_miss_counters(self):
        params = _params()
        rng = np.random.RandomState(15)
        p = rng.randint(0, 64, (1, 5)).astype(np.int32)
        T.gen_cache_stats(reset=True)
        T.generate(params, p, 3, CFG)
        s1 = T.gen_cache_stats()
        T.generate(params, p, 3, CFG)
        s2 = T.gen_cache_stats()
        assert s2['hit'] == s1['hit'] + 1
        assert s2['miss'] == s1['miss']

    def test_shrinking_env_enforced_on_next_call(self, monkeypatch):
        params = _params()
        rng = np.random.RandomState(16)
        monkeypatch.setenv('CXXNET_GEN_CACHE_MAX', '8')
        p1 = rng.randint(0, 64, (1, 5)).astype(np.int32)
        T.generate(params, p1, 3, CFG)
        T.generate(params, p1, 5, CFG)      # second size class
        assert len(T._GEN_CACHE) >= 2
        monkeypatch.setenv('CXXNET_GEN_CACHE_MAX', '1')
        T.generate(params, p1, 3, CFG)      # a HIT must still re-enforce
        assert len(T._GEN_CACHE) == 1

    def test_decode_report_exports_gen_cache(self, engine):
        line = engine.report('decode')
        assert 'decode-gen_cache.hit' in line
        assert 'decode-gen_cache.miss' in line


# --- lm file round-trip -----------------------------------------------------

def test_lm_params_roundtrip(tmp_path):
    params = _params(42)
    path = str(tmp_path / '0001.lm')
    save_lm_params(path, params)
    assert os.path.exists(path + '.crc32')
    loaded = load_lm_params(path)
    a = jax.tree.leaves(params)
    b = jax.tree.leaves(loaded)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --- wrapper / C-ABI / CLI surface ------------------------------------------

class TestSurfaces:
    def test_capi_lm_serve_roundtrip(self, tmp_path):
        """The flat C-ABI decode surface: start from a saved .lm file,
        generate (twin-checked), stats, stop."""
        from cxxnet_tpu import capi
        params = _params(5)
        path = str(tmp_path / '0001.lm')
        save_lm_params(path, params)
        svc = capi.lm_serve_start(
            'vocab=64;d_model=32;heads=4;d_ff=48;stages=2;'
            f'slots=2;pages=32;page_size=8;max_prompt=16;max_new=16;'
            f'model_in={path}')
        try:
            rng = np.random.RandomState(17)
            prompt = rng.randint(0, 64, (6,)).astype(np.int32)
            toks = capi.lm_serve_generate(svc, memoryview(prompt), 6, 5)
            assert toks.dtype == np.int32 and toks.flags['C_CONTIGUOUS']
            _assert_twin(toks, _offline(params, prompt[None], 5))
            sampled = capi.lm_serve_generate(svc, memoryview(prompt), 6,
                                             5, temperature=0.9, seed=3)
            _assert_twin(sampled,
                         _offline(params, prompt[None], 5,
                                  temperature=0.9,
                                  rng=jax.random.PRNGKey(3)))
            assert 'decode-completed' in capi.lm_serve_stats(svc)
        finally:
            capi.lm_serve_stop(svc)

    @pytest.mark.parametrize('surface', ['wrapper', 'capi'])
    def test_lm_serve_refuses_a_flash_decode_option(self, surface):
        """The engine has one attention leg: a spec that still asks for
        the other is refused by name, as any unknown key is, before a
        model is built."""
        from cxxnet_tpu import capi, wrapper
        start = {'wrapper': wrapper.LMServe.from_spec,
                 'capi': capi.lm_serve_start}[surface]
        with pytest.raises(ValueError,
                           match="unknown lm_serve option: 'flash_decode'"):
            start('vocab=64;d_model=32;heads=4;d_ff=48;stages=2;'
                  'flash_decode=1')

    def test_cli_parses_no_flash_decode_key(self):
        """``main.py``'s key table, as the config-key lint extracts it,
        holds no ``serve.flash_decode``."""
        import os
        from cxxnet_tpu.analysis import config_keys
        from cxxnet_tpu.analysis.core import Repo
        repo = Repo(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        keys = set(config_keys.parsed_keys(
            repo.module('cxxnet_tpu/main.py')))
        serve = {k for k in keys if k.startswith('serve.')}
        assert {'serve.dtype', 'serve.mode', 'serve.spec_k'} <= serve
        assert 'serve.flash_decode' not in serve

    def test_capi_net_serve_start_parses_fleet_options(self):
        from cxxnet_tpu import capi

        class NetStub:
            kw = None

            def serve_start(self, **kw):
                NetStub.kw = kw

        capi.net_serve_start(
            NetStub(), 'buckets=1:8;mem_budget=1000;'
                       'models=a:/tmp/x|b:/tmp/y')
        assert NetStub.kw['buckets'] == '1,8'
        assert NetStub.kw['mem_budget'] == 1000
        assert NetStub.kw['models'] == {'a': '/tmp/x', 'b': '/tmp/y'}

    def test_cli_decode_mode(self, tmp_path):
        """task=serve serve.mode=decode end to end: token streams in the
        pred file, the twin spot-check line, per-token stats."""
        conf = tmp_path / 'dec.conf'
        conf.write_text(
            'task = serve\n'
            'serve.mode = decode\n'
            'serve.lm = "vocab=64;d_model=32;heads=4;d_ff=48;stages=2"\n'
            'serve.slots = 2\n'
            'serve.pages = 32\n'
            'serve.page_size = 8\n'
            'serve.max_prompt = 12\n'
            'serve.max_new = 6\n'
            'serve.requests = 4\n'
            f'pred = {tmp_path}/toks.txt\n')
        r = _run_decode_cli(str(conf), str(tmp_path))
        assert 'decode twin check' in r.stdout
        assert 'finished serving 4 decode streams' in r.stdout
        assert 'decode-tokens' in r.stderr
        lines = (tmp_path / 'toks.txt').read_text().strip().splitlines()
        assert len(lines) == 4
        assert all(len(ln.split()) == 6 for ln in lines)


def _run_decode_cli(conf_path, cwd, *overrides, timeout=300):
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = repo + os.pathsep + env.get('PYTHONPATH', '')
    r = subprocess.run(
        [sys.executable, '-m', 'cxxnet_tpu.main', conf_path, *overrides],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (r.stdout, r.stderr)
    return r


# --- e2e acceptance ---------------------------------------------------------

def test_e2e_concurrent_mixed_traffic_swap_and_budget():
    """The acceptance run: concurrent clients, mixed prompt lengths,
    staggered arrivals — every stream equals its offline twin; a
    hot-swap mid-decode drains with zero drops (in-flight streams finish
    on the old params, later ones decode under the new)."""
    pa, pa2 = _params(0), _params(99)
    svc = DecodeService(pa, CFG, slots=4, pages=64, page_size=8,
                        max_prompt=16, max_new_bound=32, deadline=120.0)
    results = []
    lock = threading.Lock()

    def client(cid):
        rng = np.random.RandomState(700 + cid)
        for i in range(3):
            p = _prompt(rng)
            key = jax.random.PRNGKey(cid * 17 + i)
            temp = 0.9 if (cid + i) % 2 else 0.0
            req = svc.submit_async(p, 8, temp, key if temp else None)
            svc.batcher.wait(req)
            with lock:
                results.append((p, temp, key, req))
            time.sleep(rng.uniform(0, 0.02))

    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        svc.engine.swap_params(pa2, version='v2')   # mid-traffic
        for t in threads:
            t.join(120)
        assert len(results) == 12
        assert not any(r.error for *_, r in results), \
            'zero dropped requests across the swap'
        old_side = new_side = 0
        for p, temp, key, req in results:
            # drain semantics: a stream ran wholly under ONE params tree
            off_a = _offline(pa, p, 8, temperature=temp,
                             rng=key if temp else None)
            off_b = _offline(pa2, p, 8, temperature=temp,
                             rng=key if temp else None)
            got = np.asarray(req.result)
            if len(got) == len(off_a) and (got == off_a).all():
                old_side += 1
            else:
                _assert_twin(got, off_b)
                new_side += 1
        assert old_side >= 1 and new_side >= 1, \
            f'swap should split traffic (old={old_side}, new={new_side})'
        assert svc.engine.swap_count == 1
    finally:
        svc.close(30)
