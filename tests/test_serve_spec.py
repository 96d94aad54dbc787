"""Prefix-shared paged KV cache + greedy speculative decoding suite
(serve/decode.py "Prefix sharing" / "Speculative decoding").

The load-bearing claims:

* **prefix sharing is BITWISE-invisible** — a stream whose prompt
  prefix was spliced from the content-addressed index equals the same
  request served unshared equals its offline ``transformer.generate``
  twin, greedy and sampled, at any join time and pad width (the tail
  prefill is pinned bitwise-equal to the full prefill row-for-row),
* **refcounts protect shared pages** — preempting or expiring a stream
  never frees a page another slot (or the index) still references, and
  ``resident_bytes`` counts each physical page once no matter how many
  page tables reference it,
* **greedy spec decode is TOKEN-EQUAL to the target alone** — every
  accepted token is the target's own greedy pick at its position, so
  the stream equals offline greedy ``generate`` for every seed tested
  (on every ``serve.dtype`` tier; the verify window's float
  reassociation perturbs logits at the ulp level, which these twins
  police per seed).
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from cxxnet_tpu.models import transformer as T
from cxxnet_tpu.runtime.faults import (DecodePagesExhaustedError,
                                       PrefixIndexFullError)
from cxxnet_tpu.serve.batcher import DynamicBatcher, ServeRequest
from cxxnet_tpu.serve.decode import DecodeEngine
from cxxnet_tpu.serve.registry import MultiModelRegistry

pytestmark = pytest.mark.serve_spec

CFG = T.TransformerConfig(vocab_size=64, d_model=32, num_heads=4,
                          d_ff=48, num_stages=2, seq_len=32, attn='local')
DCFG = T.TransformerConfig(vocab_size=64, d_model=16, num_heads=2,
                           d_ff=24, num_stages=1, seq_len=32, attn='local')


def _params(seed: int = 0, cfg=CFG):
    return T.init_params(np.random.RandomState(seed), cfg)


PARAMS = _params()
DRAFT = _params(1, DCFG)


def _wait_ok(req, timeout=120):
    assert req.event.wait(timeout), 'request never completed'
    if req.error is not None:
        raise req.error
    return req.result


def _offline(prompt, max_new, temperature=0.0, rng=None, params=None,
             cfg=None):
    return np.asarray(T.generate(
        PARAMS if params is None else params, prompt, max_new,
        CFG if cfg is None else cfg, temperature=temperature,
        rng=rng))[0]


def _assert_twin(got, off):
    got = np.asarray(got)
    assert len(got) >= 1
    np.testing.assert_array_equal(got, off[:len(got)])


# --- the tail prefill is bitwise-equal to the full prefill ------------------

class TestTailPrefill:
    @pytest.mark.parametrize('w_pad,s0', [(0, 16), (3, 13)])
    def test_tail_rows_and_logits_bitwise_equal_full_prefill(self, w_pad,
                                                             s0):
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, 64, (1, s0)).astype(np.int32)
        padded = np.pad(prompt, ((0, 0), (w_pad, 0)))
        ks, vs, lg = jax.jit(
            lambda p, t, w: T.prefill_kv(p, t, w, CFG))(
                PARAMS, padded, np.int32(w_pad))
        ks, vs, lg = np.asarray(ks), np.asarray(vs), np.asarray(lg)
        t0 = 8                      # one full 8-token page shared
        tks, tvs, tlg = jax.jit(
            lambda p, pk, pv, tl, w: T.prefill_tail_kv(p, pk, pv, tl, w,
                                                       CFG))(
            PARAMS, ks[:, :, :t0], vs[:, :, :t0], padded[:, t0:],
            np.int32(w_pad))
        np.testing.assert_array_equal(np.asarray(tks), ks[:, :, t0:])
        np.testing.assert_array_equal(np.asarray(tvs), vs[:, :, t0:])
        np.testing.assert_array_equal(np.asarray(tlg), lg)


# --- verify window: dense, token-equality -----------------------------------

class TestVerifyStep:
    def _prefilled(self, S=2, s0=8):
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, 64, (S, s0)).astype(np.int32)
        ks, vs, lg = jax.jit(
            lambda p, t, w: T.prefill_kv(p, t, w, CFG))(
                PARAMS, prompt, np.int32(0))
        hd = CFG.d_model // CFG.num_heads
        Tlen = 32
        kc = np.zeros((CFG.num_stages, S, Tlen, CFG.num_heads, hd),
                      np.float32)
        vc = np.zeros_like(kc)
        kc[:, :, :s0] = np.asarray(ks)
        vc[:, :, :s0] = np.asarray(vs)
        tok0 = np.asarray(np.asarray(lg).argmax(-1), np.int32)
        return kc, vc, tok0, s0

    def test_verify_window_token_equal_sequential_decode(self):
        """The greedy chain through one K=4 verify window equals K
        sequential decode_steps' argmax chain (the spec-decode
        token-equality kernel claim), and the K/V rows land where the
        sequential steps put them (allclose at ulp scale; the STREAM
        equality tests below are the binding contract)."""
        kc, vc, tok0, s0 = self._prefilled()
        S, K = kc.shape[1], 4
        t = np.full(S, s0, np.int32)
        w = np.zeros(S, np.int32)
        kcs, vcs = jax.numpy.asarray(kc), jax.numpy.asarray(vc)
        tok = jax.numpy.asarray(tok0)
        step = jax.jit(lambda p, tk, kk, vv, tt, ww: T.decode_step(
            p, CFG, tk, kk, vv, tt, ww))
        window, seq_argmax = [np.asarray(tok0)], []
        for k in range(K):
            lg, kcs, vcs, _, _ = step(PARAMS, tok, kcs, vcs, t + k, w)
            tok = lg.argmax(-1).astype(jax.numpy.int32)
            seq_argmax.append(np.asarray(tok))
            if k < K - 1:
                window.append(np.asarray(tok))
        toks = np.stack(window, axis=1)
        vl, kc2, vc2, knew, vnew = jax.jit(
            lambda p, tk, kk, vv, tt, ww: T.verify_step(
                p, CFG, tk, kk, vv, tt, ww))(
            PARAMS, toks, jax.numpy.asarray(kc), jax.numpy.asarray(vc),
            t, w)
        np.testing.assert_array_equal(
            np.asarray(vl).argmax(-1), np.stack(seq_argmax, axis=1))
        np.testing.assert_allclose(
            np.asarray(kc2)[:, :, s0:s0 + K], np.asarray(knew),
            rtol=0, atol=0)
        np.testing.assert_allclose(
            np.asarray(kc2)[:, :, s0:s0 + K],
            np.asarray(kcs)[:, :, s0:s0 + K], atol=1e-5)


# --- prefix sharing: stream equality + index mechanics ----------------------

class TestPrefixSharing:
    def _engine(self, **kw):
        kw.setdefault('slots', 4)
        kw.setdefault('pages', 64)
        kw.setdefault('page_size', 8)
        kw.setdefault('max_prompt', 16)
        kw.setdefault('max_new_bound', 32)
        kw.setdefault('prefix_share', 16)
        return DecodeEngine(PARAMS, CFG, **kw)

    def test_shared_streams_equal_unshared_equal_offline(self):
        """The acceptance-criteria grid: greedy and sampled, staggered
        joins, mixed prefix lengths, w in {0, 3} — shared streams ==
        unshared streams == offline twins, bitwise."""
        rng = np.random.RandomState(11)
        base = rng.randint(0, 64, (1, 16)).astype(np.int32)   # w=0
        base13 = np.concatenate(
            [base[:, :12], rng.randint(0, 64, (1, 1))], axis=1)  # w=3
        keyed = jax.random.PRNGKey(5)
        work = [
            (base.copy(), 8, 0.0, None),
            (base.copy(), 6, 0.0, None),
            (base13.copy(), 8, 0.0, None),
            (base13.copy(), 8, 0.9, keyed),
            (np.concatenate([base[:, :8],
                             rng.randint(0, 64, (1, 4))], axis=1),
             8, 0.0, None),                       # shorter shared prefix
        ]
        shared = self._engine()
        unshared = self._engine(prefix_share=0)
        try:
            got = {}
            for name, eng in (('on', shared), ('off', unshared)):
                reqs = []
                for i, (p, mn, temp, key) in enumerate(work):
                    reqs.append(eng.submit_direct(
                        p, max_new=mn, temperature=temp, rng=key))
                    if i % 2:
                        time.sleep(0.02)          # staggered joins
                got[name] = [np.asarray(_wait_ok(r)) for r in reqs]
            for (p, mn, temp, key), g_on, g_off in zip(
                    work, got['on'], got['off']):
                off = _offline(p, mn, temperature=temp, rng=key)
                _assert_twin(g_on, off)
                np.testing.assert_array_equal(g_on, g_off)
            assert shared.stats.get('prefix_hits') >= 2
            assert shared.stats.get('prefix_published') >= 2
            assert unshared.stats.get('prefix_hits') == 0
        finally:
            shared.close(30)
            unshared.close(30)

    def test_resident_bytes_counts_shared_pages_once(self):
        """Two slots sharing a prefix report the same footprint as one
        (the PR 10 closed-form pool accounting stays refcount-correct),
        and the second stream's private page draw is only its tail."""
        eng = self._engine(max_new_bound=8)
        try:
            p = np.arange(16, dtype=np.int32)[None]
            rb_zero = eng.resident_bytes()
            r1 = eng.submit_direct(p, max_new=8)
            _wait_ok(r1)
            rb_one = eng.resident_bytes()
            r2 = eng.submit_direct(p.copy(), max_new=8)
            _wait_ok(r2)
            # the pool is ONE allocation: footprint is invariant to how
            # many page tables share its pages
            assert eng.resident_bytes() == rb_one == rb_zero
            assert eng.stats.get('prefix_hits') == 1
            with eng._cond:
                used = eng.n_pages - 1 - len(eng._free_pages)
            # both streams retired: only the 2 published prefix pages
            # stay resident (held once by the index, never per sharer)
            assert used == 2
        finally:
            eng.close(30)

    def test_preemption_never_frees_shared_pages_and_replay_twin(self):
        """Pool-dry preemption of a stream holding shared pages
        decrements refcounts only; the survivor (sharing the same
        physical prefix pages) finishes bitwise-intact, and the victim's
        replay after readmission is token-equal."""
        # tiny pool: 2 prefix pages (shared) + index ref; two streams
        # decoding far enough to exhaust the rest
        eng = DecodeEngine(PARAMS, CFG, slots=2, pages=8, page_size=8,
                           max_prompt=16, max_new_bound=32,
                           prefix_share=4)
        try:
            p = np.arange(16, dtype=np.int32)[None]
            off = _offline(p, 24)
            # the loop takes the engine's lock at every token boundary:
            # held over both admissions, neither stream decodes before
            # both have joined, so which one the dry pool sheds follows
            # from the order the engine admitted them in, not from how
            # far a head start let r1 run
            with eng._cond:
                r1 = eng.submit_direct(p, max_new=24)
                r2 = eng.submit_direct(p.copy(), max_new=24)
                joined = [(j['req'], j['seq']) for j in eng._joinq]
            # admitted in this order, r1 the elder: it holds pages first
            assert [r for r, _ in joined] == [r1, r2]
            assert joined[0][1] < joined[1][1]
            res1 = _wait_ok(r1)
            _assert_twin(res1, off)
            with pytest.raises(DecodePagesExhaustedError):
                _wait_ok(r2)
            assert eng.stats.get('prefix_hits') == 1
            assert eng.stats.get('shed_pages') == 1
            # replay after readmission: token-equal (and hits again)
            r3 = eng.submit_direct(p.copy(), max_new=24)
            _assert_twin(_wait_ok(r3), off)
            with eng._cond:
                refs = eng._page_refs.copy()
                free = set(eng._free_pages)
            # no page is both free and referenced
            assert all(refs[pg] == 0 for pg in free)
        finally:
            eng.close(30)

    def test_pool_dry_reclaim_never_frees_probed_hit_pages(self):
        """Regression (PR 12 review): when the pool is dry at admission
        and the only reclaimable index pages ARE the ones the request
        just probed as hits, reclaim must skip them — freeing one would
        alias the same physical page as both a shared prefix page and a
        fresh allocation, and the tail writes would clobber the prefix
        rows the stream reads (observed live as a twin divergence)."""
        eng = DecodeEngine(PARAMS, CFG, slots=2, pages=10, page_size=4,
                           max_prompt=16, max_new_bound=5,
                           prefix_share=8)
        try:
            a = np.arange(16, dtype=np.int32)[None]
            _assert_twin(_wait_ok(eng.submit_direct(a, max_new=4)),
                         _offline(a, 4))  # publishes 4 pages, finishes
            # a cold stream drains the remaining pool and KEEPS
            # decoding: A's pages are now the only reclaimable
            # (refcount-1) entries while C is admitted
            b = np.arange(16, 32, dtype=np.int32)[None]
            rb = eng.submit_direct(b, max_new=5)
            # C hits A's prefix with the pool dry — its admission must
            # wait for B rather than reclaim its own hit pages
            got = _wait_ok(eng.submit_direct(a.copy(), max_new=5))
            _assert_twin(got, _offline(a, 5))
            _assert_twin(_wait_ok(rb), _offline(b, 5))
            assert eng.stats.get('prefix_hits') >= 1
        finally:
            eng.close(30)

    def test_index_eviction_frees_pages_and_full_error_recorded(self):
        """LRU eviction keeps the index at its page cap; a prompt whose
        shareable pages exceed the whole cap records the typed
        PrefixIndexFullError outcome and serves unshared."""
        eng = self._engine(prefix_share=1)   # cap < 2 full pages
        try:
            p = np.arange(16, dtype=np.int32)[None]   # 2 shareable pages
            _wait_ok(eng.submit_direct(p, max_new=4))
            assert eng.stats.get('prefix_index_full') == 1
            assert eng.stats.get('prefix_published') == 0
            # a one-page prompt (s0b=8) fits the cap; a second distinct
            # one LRU-evicts it and the evictee's page goes back to the
            # pool (refcount zero)
            q1 = np.arange(8, dtype=np.int32)[None]
            q2 = np.arange(8, 16, dtype=np.int32)[None]
            _wait_ok(eng.submit_direct(q1, max_new=4))
            assert eng.stats.get('prefix_published') == 1
            _wait_ok(eng.submit_direct(q2, max_new=4))
            assert eng.stats.get('prefix_published') == 2
            with eng._cond:
                assert len(eng._prefix) == 1
                assert (eng._page_refs[1:] > 0).sum() == 1
        finally:
            eng.close(30)
        err = PrefixIndexFullError(3, 1)
        assert err.needed == 3 and err.cap == 1

    def test_swap_drains_and_clears_prefix_index(self):
        """A param hot-swap releases every index reference (stale keys
        would leak pages) and post-swap streams twin the NEW params."""
        eng = self._engine()
        try:
            p = np.arange(16, dtype=np.int32)[None]
            _wait_ok(eng.submit_direct(p, max_new=4))
            with eng._cond:
                assert len(eng._prefix) >= 1
            new_params = _params(9)
            eng.swap_params(new_params, version=9)
            with eng._cond:
                assert len(eng._prefix) == 0
                assert (eng._page_refs[1:] == 0).all()
                assert len(eng._free_pages) == eng.n_pages - 1
            r = eng.submit_direct(p.copy(), max_new=6)
            _assert_twin(_wait_ok(r), _offline(p, 6, params=new_params))
        finally:
            eng.close(30)

    def test_prefill_cost_prices_hits_at_their_tail(self):
        eng = self._engine()
        try:
            p = np.arange(16, dtype=np.int32)[None]
            req = ServeRequest(p, 30.0)
            assert eng.prefill_cost(req) == 16       # cold: full prompt
            _wait_ok(eng.submit_direct(p, max_new=4))
            assert eng.prefill_cost(ServeRequest(p, 30.0)) == 8  # tail
        finally:
            eng.close(30)

    def test_report_exports_pool_and_prefix_gauges(self):
        eng = self._engine()
        try:
            p = np.arange(16, dtype=np.int32)[None]
            _wait_ok(eng.submit_direct(p, max_new=4))
            line = eng.report('px')
            for key in ('px-free_pages', 'px-free_pages_min',
                        'px-pages_used', 'px-pages_shared',
                        'px-prefix_index_pages', 'px-prefix_published'):
                assert key in line, line
        finally:
            eng.close(30)


# --- batcher admission pricing ----------------------------------------------

class TestBatcherCost:
    def test_cost_budget_closes_window(self):
        """With a cost_fn, the coalescing window closes before the
        budget is breached (order preserved), and the first request
        always rides."""
        executed = []
        gate = threading.Event()

        class Stub:
            buckets = (8,)

            def predict_scores(self, data):
                gate.wait(5)
                executed.append(data.shape[0])
                return np.zeros((data.shape[0], 1), np.float32)

        b = DynamicBatcher(Stub(), max_wait=0.2, deadline=10.0,
                           cost_fn=lambda r: int(r.meta['cost']),
                           max_cost=10)
        try:
            reqs = [b.submit_async(np.zeros((1, 1), np.float32),
                                   meta={'cost': c})
                    for c in (6, 3, 9, 1)]
            gate.set()
            for r in reqs:
                b.wait(r)
            # 6+3 fit the 10-cost budget; 9 starts the next window
            assert executed[0] == 2 and sum(executed) == 4
            assert b.stats.get('cost_closed') >= 1
        finally:
            b.close(10)

    def test_max_cost_requires_cost_fn(self):
        class Stub:
            buckets = (4,)
        with pytest.raises(ValueError):
            DynamicBatcher(Stub(), max_cost=5)


# --- speculative decoding ---------------------------------------------------

class TestSpecDecode:
    def _engine(self, draft=(DRAFT, DCFG), dtype='f32', **kw):
        kw.setdefault('slots', 3)
        kw.setdefault('pages', 64)
        kw.setdefault('page_size', 8)
        kw.setdefault('max_prompt', 16)
        kw.setdefault('max_new_bound', 16)
        kw.setdefault('spec_k', 4)
        return DecodeEngine(PARAMS, CFG, draft=draft, dtype=dtype, **kw)

    @pytest.mark.parametrize('seed', [5, 23, 71])
    def test_spec_streams_token_equal_target_greedy(self, seed):
        """Spec-decoded streams == target-only greedy == offline
        generate, per seed, with a cold (disagreeing) draft, staggered
        joins and mixed prompt lengths."""
        eng = self._engine()
        try:
            rng = np.random.RandomState(seed)
            reqs = []
            for i in range(5):
                p = rng.randint(0, 64,
                                (1, int(rng.randint(2, 14)))).astype(
                                    np.int32)
                reqs.append((p, eng.submit_direct(p, max_new=10)))
                if i % 2:
                    time.sleep(0.02)
            for p, r in reqs:
                _assert_twin(_wait_ok(r), _offline(p, 10))
            assert eng.stats.get('spec_steps') >= 1
            assert eng.stats.get('spec_proposed') >= 3
        finally:
            eng.close(30)

    def test_twin_draft_high_acceptance(self):
        """A draft sharing the target's params accepts most proposals
        (the self-speculation upper bound) — and stays token-equal."""
        eng = self._engine(draft=(PARAMS, CFG))
        try:
            p = np.asarray([[1, 2, 3, 4, 5]], np.int32)
            _assert_twin(_wait_ok(eng.submit_direct(p, max_new=12)),
                         _offline(p, 12))
            acc = (eng.stats.get('spec_accepted')
                   / max(1.0, eng.stats.get('spec_proposed')))
            assert acc >= 0.5, acc
            assert 'spec_accept_rate' in eng.report('sd')
        finally:
            eng.close(30)

    def test_int8_tier_token_equal(self):
        """Spec decode on the quantized tier: the oracle is generate()
        over the ENGINE's stored (quantized) tree — exact, per seed."""
        eng = self._engine(dtype='int8')
        try:
            for seed in (3, 4):
                p = np.random.RandomState(seed).randint(
                    0, 64, (1, 6)).astype(np.int32)
                got = _wait_ok(eng.submit_direct(p, max_new=8))
                _assert_twin(got, np.asarray(T.generate(
                    eng.params, p, 8, eng.cfg))[0])
        finally:
            eng.close(30)

    def test_sampled_stream_pauses_spec_exactly(self):
        """A sampled stream in a spec engine keeps its exact per-key RNG
        schedule (spec pauses while it is live — never approximates),
        and greedy streams riding the same steps stay token-equal."""
        eng = self._engine()
        try:
            p = np.asarray([[3, 1, 4, 1, 5, 9]], np.int32)
            key = jax.random.PRNGKey(42)
            r1 = eng.submit_direct(p, max_new=8, temperature=0.8,
                                   rng=key)
            r2 = eng.submit_direct(p.copy(), max_new=8)
            _assert_twin(_wait_ok(r1),
                         _offline(p, 8, temperature=0.8, rng=key))
            _assert_twin(_wait_ok(r2), _offline(p, 8))
        finally:
            eng.close(30)

    def test_spec_composes_with_prefix_share(self):
        eng = self._engine(prefix_share=8)
        try:
            p = np.arange(16, dtype=np.int32)[None]
            off = _offline(p, 10)
            _assert_twin(_wait_ok(eng.submit_direct(p, max_new=10)), off)
            _assert_twin(_wait_ok(eng.submit_direct(p.copy(),
                                                    max_new=10)), off)
            assert eng.stats.get('prefix_hits') == 1
        finally:
            eng.close(30)

    def test_spec_k_without_draft_rejected(self):
        with pytest.raises(ValueError):
            DecodeEngine(PARAMS, CFG, spec_k=4)

    def test_draft_vocab_mismatch_rejected(self):
        bad = T.TransformerConfig(vocab_size=32, d_model=16, num_heads=2,
                                  d_ff=24, num_stages=1, attn='local')
        with pytest.raises(ValueError):
            DecodeEngine(PARAMS, CFG, spec_k=2,
                         draft=(_params(1, bad), bad))


# --- draft hot-swap through the registry ------------------------------------

class TestDraftRegistry:
    def test_attach_draft_hot_swaps_and_streams_unchanged(self, tmp_path):
        """A new draft checkpoint dropped into the watched dir swaps in
        through the verify/blacklist machinery — and cannot change a
        stream, only the acceptance rate."""
        from cxxnet_tpu.serve.decode import (LM_PATTERN, lm_loader,
                                             save_lm_params)
        fleet = MultiModelRegistry()
        eng_holder = {}

        def factory():
            eng = DecodeEngine(PARAMS, CFG, slots=2, pages=32,
                               page_size=8, max_prompt=16,
                               max_new_bound=16, spec_k=3,
                               draft=(DRAFT, DCFG))
            eng_holder['eng'] = eng
            return eng

        fleet.add_model('lm', factory, load=True)
        draft_dir = tmp_path / 'drafts'
        draft_dir.mkdir()
        reg = fleet.attach_draft('lm', str(draft_dir),
                                 pattern=LM_PATTERN, loader=lm_loader)
        try:
            eng = eng_holder['eng']
            p = np.asarray([[1, 2, 3, 4, 5, 6]], np.int32)
            off = _offline(p, 8)
            _assert_twin(_wait_ok(eng.submit_direct(p, max_new=8)), off)
            assert fleet.poll_once() == 0          # nothing to adopt
            # publish a new draft (= the target tree: acceptance rises)
            save_lm_params(str(draft_dir / '0001.lm'), PARAMS)
            # the adapter quantizes/validates against the DRAFT
            # structure: the target tree differs -> REJECTED, old draft
            # keeps proposing
            assert fleet.poll_once() == 0
            assert 'REJECTED' in reg.states()
            save_lm_params(str(draft_dir / '0002.lm'), _params(8, DCFG))
            assert fleet.poll_once() == 1
            assert eng.draft_version == 2
            _assert_twin(_wait_ok(eng.submit_direct(p.copy(),
                                                    max_new=8)), off)
        finally:
            fleet.close(30)


# --- CLI / capi surfaces ----------------------------------------------------

class TestSurfaces:
    def test_capi_lm_serve_spec_keys(self):
        from cxxnet_tpu import capi
        svc = capi.lm_serve_start(
            'vocab=64;d_model=32;heads=4;d_ff=48;stages=2;'
            'slots=2;pages=32;page_size=8;max_prompt=16;max_new=16;'
            'prefix_share=8;spec_k=3;'
            'draft.d_model=16;draft.heads=2;draft.d_ff=24;'
            'draft.stages=1;draft.seed=1')
        try:
            assert svc.engine._spec_k == 3
            assert svc.engine._prefix_cap == 8
            assert svc.engine._draft_cfg.vocab_size == 64
            prompt = np.arange(6, dtype=np.int32)
            toks = capi.lm_serve_generate(svc, memoryview(prompt), 6, 5)
            off = np.asarray(T.generate(
                svc.engine.params, prompt[None], 5, svc.engine.cfg))[0]
            _assert_twin(toks, off)
            assert 'decode-completed' in capi.lm_serve_stats(svc)
        finally:
            capi.lm_serve_stop(svc)

    def test_cli_decode_prefix_spec(self, tmp_path):
        """task=serve serve.mode=decode with prefix sharing + spec
        decode end to end: the drive's built-in twin check passes and
        the stderr stats carry the new gauges."""
        import subprocess
        import sys
        conf = tmp_path / 'dec.conf'
        conf.write_text(
            'task = serve\n'
            'serve.mode = decode\n'
            'serve.lm = "vocab=64;d_model=32;heads=4;d_ff=48;stages=2"\n'
            'serve.draft = "d_model=16;heads=2;d_ff=24;stages=1;seed=1"\n'
            'serve.spec_k = 3\n'
            'serve.prefix_share = 8\n'
            'serve.slots = 2\n'
            'serve.pages = 32\n'
            'serve.page_size = 8\n'
            'serve.max_prompt = 16\n'
            'serve.max_new = 8\n'
            'serve.requests = 6\n'
            f'pred = {tmp_path / "toks.txt"}\n')
        r = subprocess.run(
            [sys.executable, '-m', 'cxxnet_tpu.main', str(conf)],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, 'JAX_PLATFORMS': 'cpu'})
        assert r.returncode == 0, r.stdout + r.stderr
        assert 'decode twin check: 3 streams equal' in r.stdout
        assert 'spec_k=3' in r.stdout
        assert 'decode-free_pages_min' in r.stderr
        lines = (tmp_path / 'toks.txt').read_text().strip().splitlines()
        assert len(lines) == 6
