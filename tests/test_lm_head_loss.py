"""The chunked head-and-loss (layers/sequence.chunked_nll_mean, used by
``lm_head_loss``) against a plain, unchunked float32 log-softmax written
here: the loss, the gradient of every head's input and of the weight; and
what its hand-written passes save, compute and are called in a trace."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
# the public door (jax.ad_checkpoint) has only the printing form in jax 0.9
from jax._src.ad_checkpoint import saved_residuals

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cxxnet_tpu.layers import ForwardContext, NodeSpec         # noqa: E402
from cxxnet_tpu.layers.sequence import (LMHeadLossLayer,       # noqa: E402
                                        chunked_nll_mean)

BATCH, SEQ, WIDTH = 2, 32, 64
VOCAB = 200                    # 1.5625 x 128, as the cell's 19,360 is 151.25
WEIGHTS = (1.0, 0.3)


def _layer(heads, chunk_tokens):
    layer = LMHeadLossLayer('head')
    for key, val in dict(vocab_held=VOCAB, batch_size=BATCH,
                         head_weight=','.join(map(str, WEIGHTS[:heads])),
                         chunk_tokens=chunk_tokens).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(WIDTH, 1, SEQ)] * heads)
    return layer


def _inputs(heads, dtype, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), heads + 2)
    ins = [jax.random.normal(k, (BATCH, 1, SEQ, WIDTH)).astype(dtype)
           for k in ks[:heads]]
    w = 0.2 * jax.random.normal(ks[-2], (WIDTH, VOCAB))
    labels = jax.random.randint(ks[-1], (BATCH, heads * SEQ), 0, VOCAB)
    # the first and the last row of the vocabulary, in every head
    labels = labels.at[0, ::SEQ].set(0).at[1, 1::SEQ].set(VOCAB - 1)
    return w, ins, labels.astype(jnp.float32)


def _plain(layer, w, ins, labels, mask):
    """The same loss over whole float32 logits, left to autodiff."""
    per_inst = 0.0
    for k, (x, weight) in enumerate(zip(ins, layer.head_weight)):
        with jax.default_matmul_precision('highest'):
            logits = x[:, 0].astype(jnp.float32) @ w
        logp = jax.nn.log_softmax(logits, axis=-1)
        y = labels[:, k * SEQ:(k + 1) * SEQ].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        per_inst = per_inst + weight * jnp.mean(nll, axis=-1)
    if mask is not None:
        per_inst = per_inst * mask
    return jnp.sum(per_inst) * layer.scale


@pytest.mark.parametrize('masked', [False, True], ids=['all', 'one_off'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('heads', [1, 2])
@pytest.mark.parametrize('chunk_tokens', [SEQ, 8, 12],
                         ids=['whole', 'chunk8', 'gcd4'])
def test_loss_and_gradients_match_plain_log_softmax(chunk_tokens, heads,
                                                    dtype, masked):
    """Value, d/d hidden of every head and d/d wmat.  float32 to 1e-6;
    bf16 products inside the band ``test_bfloat16_program_is_inside_a_band``
    holds the program to (within 0.05 of the reference's scale, and well off
    exact, so that the products did run in bf16)."""
    layer = _layer(heads, chunk_tokens)
    w, ins, labels = _inputs(heads, dtype)
    mask = jnp.asarray([1.0, 0.0]) if masked else None
    ctx = ForwardContext(is_train=True)
    got = jax.value_and_grad(
        lambda w, ins: layer.loss({'wmat': w}, ins, labels, ctx, mask),
        argnums=(0, 1))(w, ins)
    want = jax.value_and_grad(
        lambda w, ins: _plain(layer, w, ins, labels, mask),
        argnums=(0, 1))(w, ins)
    assert got[1][1][0].dtype == ins[0].dtype and got[1][0].dtype == w.dtype
    got, want = (jax.tree.map(lambda a: np.asarray(a, np.float32), t)
                 for t in (got, want))
    if dtype == 'float32':
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)
        return
    assert abs(got[0] - want[0]) < 0.05 * abs(want[0])
    errs = [np.abs(a - b).max() / np.abs(b).max()
            for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1]))]
    assert 1e-4 < max(errs) < 0.05, errs
    if masked:                       # the masked instance gets no gradient
        assert all(not g[1].any() for g in got[1][1])


def test_labels_at_both_ends_of_a_vocabulary_that_fills_no_tile():
    """Every label the first or the last row of a 200-row vocabulary: the
    compare that picks the label sees the padded lanes of neither."""
    w, ins, _ = _inputs(1, 'float32')
    labels = jnp.tile(jnp.asarray([0, VOCAB - 1], jnp.int32), SEQ // 2)
    labels = jnp.stack([labels, labels[::-1]])
    logp = jax.nn.log_softmax(ins[0][:, 0] @ w, axis=-1)
    want = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)[..., 0],
                     axis=-1)
    got = chunked_nll_mean(ins[0], w, labels, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    dw = jax.grad(lambda w: jnp.sum(chunked_nll_mean(ins[0], w, labels, 8)))(w)
    dw_want = jax.grad(lambda w: -jnp.sum(jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(ins[0][:, 0] @ w, axis=-1), labels[..., None],
        -1)[..., 0], axis=-1)))(w)
    np.testing.assert_allclose(dw, dw_want, atol=1e-6)


def test_what_is_saved_is_the_log_sum_exp():
    """Between the passes: the arguments and one float32 ``(batch, seq)``
    log-sum-exp a head.  No array with a vocabulary axis but the weight."""
    layer = _layer(2, 8)
    w, ins, labels = _inputs(2, 'bfloat16')
    ctx = ForwardContext(is_train=True)
    saved = [aval for aval, _ in saved_residuals(
        lambda w, ins: layer.loss({'wmat': w}, ins, labels, ctx), w, ins)]
    with_vocab = [a for a in saved if VOCAB in a.shape]
    assert with_vocab and all(a.shape == (WIDTH, VOCAB) for a in with_vocab)
    lse = [a for a in saved
           if a.shape == (BATCH, SEQ) and a.dtype == jnp.float32]
    assert len(lse) == 2, saved


def test_backward_pass_is_products_and_one_elementwise_pass():
    """The backward pass as lowered: no scatter, no gather, and no
    reduction at all (neither a row maximum nor a row sum: the log-sum-exp
    came with the residuals; the weight's gradient is a product)."""
    w, ins, labels = _inputs(1, 'bfloat16')
    labels = labels.astype(jnp.int32)
    _, pull = jax.vjp(lambda x, w: chunked_nll_mean(x, w, labels, 8),
                      ins[0], w)
    text = jax.jit(pull).lower(jnp.ones((BATCH,))).as_text()
    assert text.count('stablehlo.dot_general') == 3      # logits, dx, dW
    for op in ('scatter', 'gather', 'stablehlo.reduce', 'sort'):
        assert op not in text, op
    # one slab a chunk, written in the products' dtype
    assert re.search(r'stablehlo.convert .*tensor<2x8x200xf32>\) -> '
                     r'tensor<2x8x200xbf16>', text)
    whole = jax.jit(jax.grad(
        lambda x, w: jnp.sum(chunked_nll_mean(x, w, labels, 8)),
        argnums=(0, 1))).lower(ins[0], w).as_text()
    assert 'scatter' not in whole and 'gather' not in whole


def test_both_passes_carry_the_conf_layers_scope_in_the_compiled_step():
    """``device_time_by_scope`` and ``net.head_loss_ms_per_step`` join a
    device event to its conf layer through the compiled step's text: the
    hand-written backward pass's instructions are under the layer's scope
    like a differentiated layer's."""
    from benchmark.references import glm_moe_lite as R
    from test_glm_moe_lite import TINY, _batch, _pairs, _trainer
    from cxxnet_tpu.utils.profiler import hlo_op_names, scope_of
    tr = _trainer(_pairs(TINY, seed=1, silent=1))
    tr.update_staged(tr.stage_batch(_batch(R.build_graph(_pairs(TINY)))[1]))
    found = {}
    for op_name in hlo_op_names(tr.step_program_text()).values():
        scope, which = scope_of(op_name)
        if scope == 'l13_lm_head_loss_head' and 'dot_general' in op_name:
            found[which] = found.get(which, 0) + 1
    assert found.get('fwd', 0) >= 1 and found.get('bwd', 0) >= 3, found
