"""Pipeline / expert / composed-parallelism tests on the 8-device CPU mesh.

Every distributed program is validated against a single-device oracle:
same math, no mesh.  The composed TransformerLM step checks both the
forward loss and the parameter update (i.e. the gradients, including the
replica-tying psums) to oracle SGD.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from cxxnet_tpu.models import transformer as tfm
from cxxnet_tpu.parallel.moe import moe_ffn_local, moe_ffn_reference
from cxxnet_tpu.parallel.pipeline import (pipeline_stage_loop,
                                          split_microbatches)


def _devices(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f'need {n} devices, have {len(devs)}')
    return devs[:n]


# --- pipeline -------------------------------------------------------------

def test_pipeline_matches_sequential():
    S, M, mb, d = 4, 8, 2, 16
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(S, d).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(M * mb, d).astype(np.float32))

    def stage(p, h):
        return jnp.tanh(h @ p['w'] + p['b'])

    mesh = Mesh(np.asarray(_devices(S)), ('pipe',))
    fn = shard_map(
        functools.partial(pipeline_stage_loop, stage, axis_name='pipe',
                          num_stages=S),
        mesh=mesh,
        in_specs=({'w': P('pipe'), 'b': P('pipe')}, P()),
        out_specs=P(), check_vma=False)
    got = fn({'w': ws, 'b': bs}, split_microbatches(x, M))
    got = got.reshape(M * mb, d)

    ref = x
    for i in range(S):
        ref = jnp.tanh(ref @ ws[i] + bs[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_flow():
    S, M, mb, d = 2, 4, 2, 8
    rng = np.random.RandomState(1)
    ws = jnp.asarray(rng.randn(S, d, d).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.randn(M * mb, d).astype(np.float32))
    mesh = Mesh(np.asarray(_devices(S)), ('pipe',))

    def stage(p, h):
        return jnp.tanh(h @ p)

    def loss_local(ws_local, xs):
        out = pipeline_stage_loop(stage, ws_local, xs,
                                  axis_name='pipe', num_stages=S)
        return (out ** 2).mean()

    def body(ws_in, xs):
        return jax.grad(lambda w: loss_local(w, xs))(ws_in)

    fn = shard_map(body, mesh=mesh, in_specs=(P('pipe'), P()),
                   out_specs=P('pipe'), check_vma=False)
    g = fn(ws, split_microbatches(x, M))

    def ref_loss(ws):
        h = x
        for i in range(S):
            h = jnp.tanh(h @ ws[i])
        return (h ** 2).mean()

    # each pipe rank's autodiff sums both ranks' identical local losses
    ref = jax.grad(ref_loss)(ws) * S
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# --- expert parallelism ---------------------------------------------------

def test_moe_all_to_all_matches_reference():
    n, e, t, d, f = 4, 8, 32, 16, 24
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(n * t, d).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
    w1 = jnp.asarray(rng.randn(e, d, f).astype(np.float32) * 0.2)
    w2 = jnp.asarray(rng.randn(e, f, d).astype(np.float32) * 0.2)
    mesh = Mesh(np.asarray(_devices(n)), ('data',))
    # ample capacity (>= local tokens) so no token is dropped and the
    # sharded program must agree with the dense oracle exactly
    cf = float(e)
    fn = shard_map(
        functools.partial(moe_ffn_local, axis_name='data',
                          capacity_factor=cf),
        mesh=mesh,
        in_specs=(P('data'), P(), P('data'), P('data')),
        out_specs=(P('data'), {'balance_loss': P(), 'drop_frac': P()}),
        check_vma=False)
    got, got_aux = fn(x, gate_w, w1, w2)
    assert float(got_aux['drop_frac']) == 0.0
    # oracle shard-by-shard (capacity is per-shard in the sharded run)
    # same per-expert capacity as the sharded run: capacity is computed
    # from local token count and GLOBAL expert count in both cases
    refs = [moe_ffn_reference(x[i * t:(i + 1) * t], gate_w, w1, w2,
                              capacity_factor=cf)[0]
            for i in range(n)]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.concatenate(refs)),
                               rtol=1e-4, atol=1e-5)


def test_moe_drops_over_capacity():
    # capacity 1 with all tokens routed to one expert: only 1 kept
    d, f = 4, 8
    x = jnp.ones((6, d), jnp.float32)
    gate_w = jnp.zeros((d, 2), jnp.float32).at[:, 0].set(1.0)
    w1 = jnp.ones((2, d, f), jnp.float32)
    w2 = jnp.ones((2, f, d), jnp.float32)
    out, aux = moe_ffn_reference(x, gate_w, w1, w2, capacity_factor=1.0 / 3)
    nonzero_rows = (np.abs(np.asarray(out)).sum(-1) > 0).sum()
    assert nonzero_rows == 1
    # 5 of 6 tokens dropped; all routed to expert 0 of 2 -> balance = 2*1*1
    np.testing.assert_allclose(float(aux['drop_frac']), 5.0 / 6, atol=1e-6)
    assert float(aux['balance_loss']) > 1.5


# --- composed transformer step -------------------------------------------

def _make_inputs(cfg, batch, seed=3):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len))
    labels = rng.randint(0, cfg.vocab_size, (batch, cfg.seq_len))
    return jnp.asarray(tokens, jnp.int32), jnp.asarray(labels, jnp.int32)


@pytest.mark.parametrize('pp,dp,sp,tp,experts', [
    (2, 2, 2, 1, 0),    # pipeline + data + ring-attention sequence
    (2, 2, 2, 1, 4),    # + switch-MoE experts over the data axis
    (2, 1, 1, 4, 0),    # pipeline + 4-way tensor parallel
])
def test_transformer_step_matches_oracle(pp, dp, sp, tp, experts):
    # ample MoE capacity: the sharded run routes per (data, seq) shard
    # per microbatch while the oracle routes the whole batch, so only a
    # drop-free setting is exactly comparable
    cfg = tfm.TransformerConfig(
        vocab_size=32, d_model=16, num_heads=4, d_ff=32,
        num_stages=pp, seq_len=16, num_experts=experts,
        num_microbatches=2, attn='ring',
        capacity_factor=float(max(experts, 1) * 8),
        # the sharded run computes the balance loss per shard, the oracle
        # over the whole batch — only the weight-0 loss is exactly equal;
        # the aux-loss path has its own dedicated tests below
        balance_loss_weight=0.0)
    mesh = tfm.build_transformer_mesh(8, pp, dp, sp, tp,
                                      devices=_devices(8))
    rng = np.random.RandomState(4)
    params = tfm.init_params(rng, cfg)
    batch = 4
    tokens, labels = _make_inputs(cfg, batch)

    step = tfm.make_train_step(cfg, mesh, lr=0.1)
    new_params, loss, aux = step(params, tokens, labels)
    if experts:
        assert float(aux['balance_loss']) >= 0.99   # >= 1 at uniform
        assert 0.0 <= float(aux['drop_frac']) <= 1.0

    ref_loss = tfm.reference_loss(params, tokens, labels, cfg)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-4, atol=1e-5)

    ref_grads = jax.grad(
        lambda p: tfm.reference_loss(p, tokens, labels, cfg))(params)
    ref_new = jax.tree.map(lambda w, g: w - 0.1 * g, params, ref_grads)
    flat_got = jax.tree.leaves_with_path(new_params)
    flat_ref = dict(jax.tree.leaves_with_path(ref_new))
    for path, got in flat_got:
        ref = flat_ref[path]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-4,
            err_msg=f'param mismatch at {jax.tree_util.keystr(path)}')


def test_transformer_loss_decreases():
    cfg = tfm.TransformerConfig(vocab_size=16, d_model=16, num_heads=2,
                                d_ff=32, num_stages=2, seq_len=8,
                                num_microbatches=2)
    mesh = tfm.build_transformer_mesh(8, 2, 2, 2, 1, devices=_devices(8))
    rng = np.random.RandomState(5)
    params = tfm.init_params(rng, cfg)
    tokens, _ = _make_inputs(cfg, 4)
    labels = tokens   # learnable target: predict the input token
    step = tfm.make_train_step(cfg, mesh, lr=0.2)
    losses = []
    for _ in range(10):
        params, loss, _aux = step(params, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_remat_matches_no_remat():
    """cfg.remat recomputes block activations in backward; the math must
    be identical — same loss AND same updated params on the full 4-axis
    mesh (collectives replay under jax.checkpoint)."""
    kw = dict(vocab_size=32, d_model=16, num_heads=4, d_ff=32,
              num_stages=2, seq_len=16, num_microbatches=2, attn='ring')
    mesh = tfm.build_transformer_mesh(8, 2, 1, 2, 2, devices=_devices(8))
    rng = np.random.RandomState(11)
    params = tfm.init_params(rng, tfm.TransformerConfig(**kw))
    tokens, labels = _make_inputs(tfm.TransformerConfig(**kw), 4)
    outs = {}
    for remat in (False, True):
        cfg = tfm.TransformerConfig(remat=remat, **kw)
        step = tfm.make_train_step(cfg, mesh, lr=0.1)
        new_params, loss, _aux = step(jax.tree.map(jnp.copy, params),
                                      tokens, labels)
        outs[remat] = (new_params, float(loss))
    assert outs[False][1] == pytest.approx(outs[True][1], rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), rtol=1e-6),
        outs[False][0], outs[True][0])


def test_local_attn_rejected_on_seq_mesh():
    cfg = tfm.TransformerConfig(num_stages=2, attn='local')
    mesh = tfm.build_transformer_mesh(8, 2, 2, 2, 1, devices=_devices(8))
    with pytest.raises(ValueError, match='block-diagonal'):
        tfm.make_train_step(cfg, mesh)


def test_moe_balance_loss_fights_collapse():
    """With the Switch aux loss weighted in, a gate initialized to send
    every token to one expert spreads out; with weight 0 it stays
    collapsed (single-device oracle, differentiable-through-P_e check)."""
    d, f, e, t = 8, 16, 4, 64
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(t, d).astype(np.float32))
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)   # feature 0 always positive
    w1 = jnp.asarray(rng.randn(e, d, f).astype(np.float32) * 0.2)
    w2 = jnp.asarray(rng.randn(e, f, d).astype(np.float32) * 0.2)
    gate0 = jnp.zeros((d, e), jnp.float32).at[0, 0].set(4.0)

    def max_route_frac(gate_w):
        probs = jax.nn.softmax(x @ gate_w, axis=-1)
        sel = jax.nn.one_hot(jnp.argmax(probs, -1), e)
        return float(sel.mean(0).max())

    def run(weight):
        gate_w = gate0
        for _ in range(50):
            def loss(gw):
                out, aux = moe_ffn_reference(x, gw, w1, w2,
                                             capacity_factor=2.0)
                return (out ** 2).mean() + weight * aux['balance_loss']
            gate_w = gate_w - 1.0 * jax.grad(loss)(gate_w)
        return max_route_frac(gate_w)

    assert max_route_frac(gate0) == 1.0          # starts collapsed
    assert run(0.0) > 0.9, 'control: no pressure, stays collapsed'
    assert run(1.0) < 0.6, 'aux loss failed to spread experts'


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Sharded orbax checkpointing of the 4D-parallel transformer: save
    after training, restore onto a fresh mesh layout, bitwise-equal
    params, and training continues from the restored state."""
    from cxxnet_tpu.nnet.sharded_ckpt import (latest_step, restore_sharded,
                                              save_sharded)
    cfg = tfm.TransformerConfig(vocab_size=16, d_model=16, num_heads=2,
                                d_ff=32, num_stages=2, seq_len=8,
                                num_microbatches=2)
    mesh = tfm.build_transformer_mesh(8, 2, 2, 2, 1, devices=_devices(8))
    rng = np.random.RandomState(6)
    params = tfm.init_params(rng, cfg)
    tokens, _ = _make_inputs(cfg, 4)
    step = tfm.make_train_step(cfg, mesh, lr=0.2)
    for _ in range(3):
        params, loss, _aux = step(params, tokens, tokens)
    save_sharded(str(tmp_path / 'ck'), 2, params)
    assert latest_step(str(tmp_path / 'ck')) == 2

    fresh = tfm.init_params(np.random.RandomState(99), cfg)
    like = tfm.abstract_params(fresh, cfg, mesh)
    restored, got_step = restore_sharded(str(tmp_path / 'ck'), like)
    assert got_step == 2
    for (pa, a), (pb, b) in zip(jax.tree.leaves_with_path(params),
                                jax.tree.leaves_with_path(restored)):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues from the restored state identically
    p1, l1, _ = step(params, tokens, tokens)
    p2, l2, _ = step(restored, tokens, tokens)
    assert float(l1) == float(l2)


def test_param_shapes_matches_init_params():
    """param_shapes (the allocation-free resume target) must track
    init_params exactly."""
    for experts in (0, 4):
        cfg = tfm.TransformerConfig(vocab_size=16, d_model=16, num_heads=2,
                                    d_ff=32, num_stages=2, seq_len=8,
                                    num_experts=experts)
        live = tfm.init_params(np.random.RandomState(0), cfg)
        shapes = tfm.param_shapes(cfg)
        la = jax.tree.leaves_with_path(live)
        lb = dict(jax.tree.leaves_with_path(shapes))
        assert len(la) == len(lb)
        for path, leaf in la:
            assert lb[path].shape == leaf.shape, path
            assert lb[path].dtype == leaf.dtype, path


def test_bench_transformer_throughput_smoke(monkeypatch, capsys):
    """bench.py's transformer mode end-to-end at toy size: the scan-in-jit
    K-vs-1 quotient path must emit one valid JSON line with positive
    tokens/sec (the on-chip run reuses this exact code at GPT-2-small
    size)."""
    import json as _json

    import bench

    monkeypatch.setenv('CXXNET_BENCH_STEPS', '3')
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, num_heads=2,
                                d_ff=64, num_stages=2, seq_len=16,
                                attn='local', causal=True,
                                num_microbatches=1, dtype=jnp.float32)
    assert bench._transformer_throughput(
        cfg, batch=2, metric='transformer_tokens_per_sec_per_chip',
        baseline=1.0) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = _json.loads(line)
    assert out['metric'] == 'transformer_tokens_per_sec_per_chip'
    assert out['unit'] == 'tokens/sec'
    assert out['value'] and out['value'] > 0


def test_multi_train_step_matches_mesh_step():
    """The mirror-contract guard: make_multi_train_step (scanned
    reference_loss + SGD) applied for ONE step must produce the same loss
    and updated params as make_train_step on the composed pp2-dp2-sp2
    mesh (the gradient tie makes that the gradient of the same
    global-mean loss) — if an optimizer change lands in _make_step_body
    but not in the multi-step loop (or vice versa), this is the test
    that breaks."""
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, num_heads=2,
                                d_ff=32, num_stages=2, seq_len=8,
                                num_microbatches=2, dtype=jnp.float32)
    mesh = tfm.build_transformer_mesh(8, 2, 2, 2, 1, devices=_devices(8))
    rng = np.random.RandomState(7)
    params_a = tfm.init_params(np.random.RandomState(0), cfg)
    params_b = tfm.init_params(np.random.RandomState(0), cfg)
    tok = jnp.asarray(rng.randint(0, 32, (4, 8)), jnp.int32)
    lab = jnp.asarray(rng.randint(0, 32, (4, 8)), jnp.int32)

    step = tfm.make_train_step(cfg, mesh, lr=0.05)
    new_a, loss_a, _ = step(params_a, tok, lab)

    multi = tfm.make_multi_train_step(cfg, 1, lr=0.05)
    new_b, loss_b = multi(params_b, tok[None], lab[None])

    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
    for (pa, a), (pb, b) in zip(jax.tree.leaves_with_path(new_a),
                                jax.tree.leaves_with_path(new_b)):
        assert pa == pb
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
