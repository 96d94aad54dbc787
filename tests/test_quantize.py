"""Quantized-inference tier suite (nnet/quantize.py, doc/serving.md
"Quantized inference").

The twin policy under test is two-sided:

* **exact twins** — a quantized model is a *different but deterministic*
  model, so its serving outputs have bitwise oracles: a quantized
  ``DecodeEngine``'s streams equal ``transformer.generate`` over the
  engine's own quantized tree + compute config; a quantized
  ``PredictEngine``'s scores equal an f32 engine fed the dequantized
  tree; the W8A8 ``qdot`` leg is bitwise-identical between the Pallas
  MXU kernel and the XLA ``dot_general`` fallback (exact int32
  accumulation).
* **pinned tolerance twins** — the accuracy delta vs f32 is policed by
  thresholds written HERE (top-1 agreement, logit error bounds):
  loosening one is a visible diff, never silent.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.models import transformer as T
from cxxnet_tpu.nnet import quantize as Q
from cxxnet_tpu.serve import PredictEngine
from cxxnet_tpu.serve.decode import DecodeEngine

pytestmark = pytest.mark.quant

CFG = T.TransformerConfig(vocab_size=64, d_model=32, num_heads=4,
                          d_ff=48, num_stages=2, seq_len=32, attn='local')
CFG_BF16 = dataclasses.replace(CFG, dtype=jnp.bfloat16)


def _params(seed=0):
    return T.init_params(np.random.RandomState(seed), CFG)


def _lm_int8(params):
    return Q.quantize_tree(params, 'int8', out_dtype=jnp.bfloat16,
                           quant_key=Q.lm_quant_key)


# --- QuantLeaf / quantize_tree mechanics ------------------------------------

def test_quantize_leaf_roundtrip_error_bound():
    """Symmetric per-channel int8: |x - q*scale| <= scale/2 everywhere
    (round-to-nearest), per channel."""
    rng = np.random.RandomState(0)
    x = (rng.randn(64, 48) * rng.uniform(0.1, 5.0, 48)).astype(np.float32)
    leaf = Q.quantize_leaf(x)
    assert leaf.q.dtype == np.int8 and leaf.scale.shape == (48,)
    deq = np.asarray(leaf.dequantize(np.float32))
    assert (np.abs(deq - x) <= leaf.scale[None, :] / 2 + 1e-7).all()


def test_quantize_leaf_dead_channel_and_nbytes():
    x = np.zeros((16, 4), np.float32)
    x[:, 1] = 3.0
    leaf = Q.quantize_leaf(x)
    assert leaf.scale[0] == 1.0 and (leaf.q[:, 0] == 0).all()
    assert leaf.nbytes == 16 * 4 * 1 + 4 * 4


def test_stacked_quantleaf_stage_slicing():
    """The transformer idiom: tree.map(lambda a: a[i]) over a stacked
    QuantLeaf must equal quantizing the slice directly — the leading
    stack axis keeps per-entry scales by construction."""
    rng = np.random.RandomState(1)
    x = rng.randn(3, 16, 8).astype(np.float32)
    stacked = Q.quantize_leaf(x)
    sliced = jax.tree.map(lambda a: a[1], stacked,
                          is_leaf=lambda n: False)
    direct = Q.quantize_leaf(x[1])
    np.testing.assert_array_equal(np.asarray(sliced.q),
                                  np.asarray(direct.q))
    np.testing.assert_array_equal(np.asarray(sliced.scale),
                                  np.asarray(direct.scale))


def test_quantize_tree_modes_and_keys():
    params = _params()
    bf = Q.quantize_tree(params, 'bf16')
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(bf))
    q8 = _lm_int8(params)
    # matmul leaves quantized, norm scales/biases stay plain bf16
    assert isinstance(q8['embed'], Q.QuantLeaf)
    assert isinstance(q8['head'], Q.QuantLeaf)
    assert isinstance(q8['stages']['wq'], Q.QuantLeaf)
    assert not isinstance(q8['stages']['ln1_scale'], Q.QuantLeaf)
    assert q8['stages']['ln1_scale'].dtype == jnp.bfloat16
    assert Q.quantize_tree(params, 'f32') is params
    with pytest.raises(ValueError):
        Q.parse_serve_dtype('fp8')
    assert Q.parse_serve_dtype('float32') == 'f32'


def test_tree_nbytes_reduction_ratios():
    params = _params()
    f32 = Q.tree_nbytes(params)
    assert Q.tree_nbytes(Q.quantize_tree(params, 'bf16')) * 2 == f32
    assert Q.tree_nbytes(_lm_int8(params)) * 3 < f32  # > 3x smaller


# --- qdot: the W8A8 leg ------------------------------------------------------

def test_qdot_plain_array_is_native_matmul():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(5, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(Q.qdot(x, w)),
                                  np.asarray(x @ w))


# --- DecodeEngine tiers ------------------------------------------------------

class TestDecodeTiers:
    def _streams(self, dtype, prompts, temps, keys):
        eng = DecodeEngine(_params(), CFG, slots=4, pages=64, page_size=8,
                           max_prompt=16, max_new_bound=32, dtype=dtype)
        try:
            reqs = [eng.submit_direct(p, max_new=10, temperature=tp,
                                      rng=k)
                    for p, tp, k in zip(prompts, temps, keys)]
            outs = []
            for r in reqs:
                assert r.event.wait(60) and r.error is None, r.error
                outs.append(np.asarray(r.result))
            ref, cfg = eng.params, eng.cfg
            resident = eng.resident_bytes()
        finally:
            eng.close(30)
        return outs, ref, cfg, resident

    def test_exact_stream_twins_every_tier(self):
        """EVERY serve.dtype tier keeps the bitwise-twin discipline: the
        engine's streams equal generate() over its own stored tree and
        compute config — greedy and sampled."""
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 64, (1, int(rng.randint(1, 12))))
                   .astype(np.int32) for _ in range(4)]
        temps = [0.0, 0.0, 0.8, 1.2]
        keys = [None, None, jax.random.PRNGKey(9), jax.random.PRNGKey(10)]
        residents = {}
        for dtype in ('f32', 'bf16', 'int8'):
            outs, ref, cfg, resident = self._streams(
                dtype, prompts, temps, keys)
            for o, p, tp, k in zip(outs, prompts, temps, keys):
                off = np.asarray(T.generate(ref, p, 10, cfg,
                                            temperature=tp, rng=k))[0]
                np.testing.assert_array_equal(o, off)
            residents[dtype] = resident
        # resident-byte ladder: bf16 halves params+pool; int8 shrinks
        # further (params ~4x; the bf16 pool shares the ledger)
        assert residents['bf16'] < residents['f32'] * 0.55
        assert residents['int8'] < residents['bf16']

    def test_int8_tolerance_twin_vs_f32(self):
        """PINNED tolerance vs the f32 model (never silently looser):
        prefill logits within 5% relative, top-1 equal, and the greedy
        stream agrees with f32's on a majority prefix — all
        deterministic on this fixed seed."""
        params = _params()
        rng = np.random.RandomState(6)
        prompt = rng.randint(0, 64, (1, 7)).astype(np.int32)
        q8 = _lm_int8(params)
        _, _, l32 = jax.jit(lambda p, t: T.prefill_kv(p, t, jnp.int32(0),
                                                      CFG))(params, prompt)
        _, _, l8 = jax.jit(lambda p, t: T.prefill_kv(p, t, jnp.int32(0),
                                                     CFG_BF16))(q8, prompt)
        l32, l8 = np.asarray(l32), np.asarray(l8)
        rel = np.abs(l8 - l32).max() / np.abs(l32).max()
        assert rel < 0.05, f'int8 prefill logits drifted: rel={rel}'
        assert (l8.argmax(-1) == l32.argmax(-1)).all()
        s32 = np.asarray(T.generate(params, prompt, 12, CFG))[0]
        s8 = np.asarray(T.generate(q8, prompt, 12, CFG_BF16))[0]
        agree = (s32 == s8).mean()
        assert s32[0] == s8[0]
        assert agree >= 0.5, f'int8 greedy stream agreement {agree}'

    def test_bf16_tolerance_twin_vs_f32(self):
        params = _params()
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, 64, (1, 9)).astype(np.int32)
        p16 = Q.quantize_tree(params, 'bf16')
        _, _, l32 = jax.jit(lambda p, t: T.prefill_kv(p, t, jnp.int32(0),
                                                      CFG))(params, prompt)
        _, _, l16 = jax.jit(lambda p, t: T.prefill_kv(p, t, jnp.int32(0),
                                                      CFG_BF16))(p16,
                                                                 prompt)
        l32, l16 = np.asarray(l32), np.asarray(l16)
        rel = np.abs(l16 - l32).max() / np.abs(l32).max()
        assert rel < 0.02, f'bf16 prefill logits drifted: rel={rel}'
        assert (l16.argmax(-1) == l32.argmax(-1)).all()

    def test_quantized_hot_swap_keeps_twin(self):
        """swap_params takes the HOST f32 tree (what .lm files carry),
        re-quantizes at swap time, and the post-swap streams twin the
        new quantized tree."""
        eng = DecodeEngine(_params(0), CFG, slots=2, pages=32,
                           page_size=8, max_prompt=16, max_new_bound=16,
                           dtype='int8')
        try:
            new_host = _params(1)
            eng.swap_params(new_host, version=1)
            rng = np.random.RandomState(8)
            p = rng.randint(0, 64, (1, 5)).astype(np.int32)
            r = eng.submit_direct(p, max_new=6)
            assert r.event.wait(60) and r.error is None
            off = np.asarray(T.generate(eng.params, p, 6, eng.cfg))[0]
            np.testing.assert_array_equal(np.asarray(r.result), off)
            assert eng.version == 1
        finally:
            eng.close(30)

    def test_budgeter_fits_more_int8_models(self):
        """The point of the tier: under a budget sized for ONE f32
        decode engine, two int8 engines fit where two f32 ones cannot
        (resident_bytes reports the true quantized footprint)."""
        from cxxnet_tpu.runtime.faults import MemoryBudgetExceededError
        from cxxnet_tpu.serve.registry import MultiModelRegistry

        def factory(dtype):
            return lambda: DecodeEngine(
                _params(), CFG, slots=2, pages=32, page_size=8,
                max_prompt=16, max_new_bound=16, dtype=dtype)

        probe = factory('f32')()
        budget = int(probe.resident_bytes() * 1.2)
        probe.close(30)

        fleet = MultiModelRegistry(mem_budget=budget)
        fleet.add_model('a8', factory('int8'))
        fleet.add_model('b8', factory('int8'))
        try:
            fleet.get('a8')
            fleet.get('b8')
            assert sorted(fleet.loaded()) == ['a8', 'b8']
        finally:
            fleet.close(10)

        fleet32 = MultiModelRegistry(mem_budget=budget)
        fleet32.add_model('a32', factory('f32'), pinned=True)
        fleet32.add_model('b32', factory('f32'))
        try:
            fleet32.get('a32')
            with pytest.raises(MemoryBudgetExceededError):
                fleet32.get('b32')
        finally:
            fleet32.close(10)


# --- PredictEngine tiers -----------------------------------------------------

class TestPredictTiers:
    @pytest.fixture()
    def nets(self):
        from tests.test_serve import make_net
        return make_net

    def _host(self, engine):
        return jax.tree.map(lambda x: np.asarray(x), engine.params)

    def test_exact_and_tolerance_twins(self, nets):
        """Bucket-ladder scores on every tier: bitwise-equal to an f32
        engine fed the dequantized tree (exact twin), and within PINNED
        bounds of the original f32 scores with full top-1 agreement
        (tolerance twin).  The request spans the ladder (pad + chunk)."""
        e32 = PredictEngine(nets(seed=3)._trainer, (1, 4))
        host = self._host(e32)
        rng = np.random.RandomState(9)
        data = rng.randn(11, 1, 1, 8).astype(np.float32)  # chunks + pad
        s32 = e32.predict_scores(data)
        bounds = {'bf16': 1e-4, 'int8': 1e-3}
        for dtype in ('bf16', 'int8'):
            eq = PredictEngine(nets(seed=3)._trainer, (1, 4), dtype=dtype)
            assert eq.compile_count == 0
            sq = eq.predict_scores(data)
            # exact twin: f32 engine over the dequantized tree
            et = PredictEngine(nets(seed=3)._trainer, (1, 4))
            deq = Q.dequantize_tree(Q.quantize_tree(host, dtype),
                                    jnp.float32)
            et.swap_params(jax.tree.map(lambda x: np.asarray(x), deq))
            np.testing.assert_array_equal(sq, et.predict_scores(data))
            # tolerance twin: pinned bound, never silently looser
            diff = float(np.abs(sq - s32).max())
            assert diff < bounds[dtype], (dtype, diff)
            assert (sq.argmax(-1) == s32.argmax(-1)).all()
            # resident ledger: bf16 halves; int8 beats bf16 even on this
            # toy net where biases/scales dominate (the >=3x param claim
            # is pinned on the transformer tree + the bench receipt)
            if dtype == 'bf16':
                assert eq.resident_bytes() * 2 == e32.resident_bytes()
            else:
                assert eq.resident_bytes() * 2 < e32.resident_bytes()

    def test_quantized_swap_through_registry_sequence(self, nets):
        """The registry's place -> warm -> swap sequence on a quantized
        engine: host f32 tree in, quantized tier served out, and the
        re-passed placed tree short-circuits cleanly."""
        eq = PredictEngine(nets(seed=3)._trainer, (1, 4), dtype='int8')
        donor = PredictEngine(nets(seed=5)._trainer, (1, 4))
        host = self._host(donor)
        placed = eq.place_params(host)
        eq.warm_params(placed)
        eq.swap_params(placed, version=7)
        assert eq.version == 7
        rng = np.random.RandomState(10)
        data = rng.randn(3, 1, 1, 8).astype(np.float32)
        et = PredictEngine(nets(seed=0)._trainer, (1, 4))
        deq = Q.dequantize_tree(Q.quantize_tree(host, 'int8'),
                                jnp.float32)
        et.swap_params(jax.tree.map(lambda x: np.asarray(x), deq))
        np.testing.assert_array_equal(eq.predict_scores(data),
                                      et.predict_scores(data))

    def test_swap_rejects_structure_change(self, nets):
        eq = PredictEngine(nets(seed=3)._trainer, (1, 4), dtype='int8')
        bad = self._host(eq)        # QUANTIZED structure != f32 contract
        bad = jax.tree.map(lambda x: x, bad)
        with pytest.raises(ValueError, match='structure'):
            # a half-tree is neither the f32 contract nor our own output
            eq.swap_params({'nope': np.zeros((2, 2), np.float32)})


# --- wrapper / C-ABI keys ----------------------------------------------------

def test_capi_serve_start_parses_dtype():
    from cxxnet_tpu import capi

    class NetStub:
        def serve_start(self, **kw):
            self.kw = kw

    stub = NetStub()
    capi.net_serve_start(stub, 'buckets=1:4;dtype=int8')
    assert stub.kw['dtype'] == 'int8'
    assert stub.kw['buckets'] == '1,4'


def test_capi_lm_serve_parses_dtype(tmp_path):
    from cxxnet_tpu import capi
    svc = capi.lm_serve_start(
        'vocab=64;d_model=32;heads=4;d_ff=48;stages=2;slots=2;pages=32;'
        'page_size=8;max_prompt=12;max_new=6;dtype=bf16')
    try:
        assert svc.engine.serve_dtype == 'bf16'
        assert svc.engine.cfg.dtype == jnp.bfloat16
        prompt = np.arange(5, dtype=np.int32)
        toks = capi.lm_serve_generate(svc, memoryview(prompt.tobytes()),
                                      5, 4)
        off = np.asarray(T.generate(
            svc.engine.params, prompt[None], 4, svc.engine.cfg))[0]
        np.testing.assert_array_equal(toks, off[:len(toks)])
    finally:
        capi.lm_serve_stop(svc)


def test_online_pipeline_serves_quantized_tier(tmp_path):
    """task=online reuses the serve.* keys — OnlineConfig.dtype must
    actually reach the colocated PredictEngine (the trainer+server-on-
    one-chip memory-pressure scenario is exactly what the tier is for)."""
    from cxxnet_tpu import capi
    from tests.test_online import MLP_CONF, ListIter, _make_batches

    net = capi.net_create('cpu', MLP_CONF)
    net.set_param('seed', 2)
    net.init_model()
    capi.net_online_start(
        net, ListIter(_make_batches(6, seed=2)),
        f'model_dir={tmp_path}/m;rounds=1;save_every=5;reload=0.02;'
        f'buckets=4:8;watchdog_deadline=30;dtype=int8')
    try:
        eng = net._online.engine
        assert eng.serve_dtype == 'int8'
        assert any(isinstance(l, Q.QuantLeaf)
                   for l in jax.tree.leaves(
                       eng.params,
                       is_leaf=lambda n: isinstance(n, Q.QuantLeaf)))
        rows = np.random.RandomState(0).randn(4, 1, 1, 16)\
            .astype(np.float32)
        out = capi.net_online_predict(net, memoryview(rows.tobytes()),
                                      rows.shape)
        assert out.shape == (4,)
        capi.net_online_wait(net)
    finally:
        capi.net_online_stop(net)


def test_wrapper_serve_start_dtype(tmp_path):
    from tests.test_serve import make_net
    net = make_net(seed=3)
    net.serve_start(buckets='1,4', dtype='int8', warm=False)
    try:
        assert net._engine.serve_dtype == 'int8'
        rng = np.random.RandomState(11)
        out = net.serve_scores(rng.randn(3, 1, 1, 8).astype(np.float32))
        assert out.shape[0] == 3
    finally:
        net.serve_stop()
