"""The one LRN (``layers/norm.lrn``): an O(local_size) window sum the compiler
can fuse, float32 inside, ``x`` its only residual.  The reference here is the
naive loop over channels, in float32; nothing below runs a kernel."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import ForwardContext, NodeSpec, create_layer
from cxxnet_tpu.layers.base import get_layer_type
from cxxnet_tpu.layers.norm import lrn
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string

ALPHA, BETA, KNORM = 0.01, 0.75, 1.0
SIZES = [3, 4, 5]                        # 4: an even window, one more above
CHANNELS = [3, 5, 24, 64, 96, 192, 256]  # 3: fewer channels than the window


def naive_lrn(x, nsize, alpha=ALPHA, beta=BETA, knorm=KNORM):
    """One channel at a time, its window summed by itself; float32.  The
    loop over channels is a ``lax.map`` (a Python loop over 256 channels
    takes half a minute to differentiate)."""
    c = x.shape[-1]
    lo = (nsize - 1) // 2
    sq = jnp.pad(x * x, [(0, 0)] * (x.ndim - 1) + [(lo, nsize - 1 - lo)])

    def channel(j):
        window = jax.lax.dynamic_slice_in_dim(sq, j, nsize, axis=-1)
        norm = knorm + alpha / nsize * jnp.sum(window, axis=-1)
        return jnp.take(x, j, axis=-1) * norm ** -beta

    return jnp.moveaxis(jax.lax.map(channel, jnp.arange(c)), 0, -1)


def numpy_lrn(x, nsize, alpha=ALPHA, beta=BETA, knorm=KNORM):
    """The same in numpy, the window clipped at the ends, not padded."""
    x = np.asarray(x, np.float32)
    c = x.shape[-1]
    lo = (nsize - 1) // 2
    out = np.zeros_like(x)
    for j in range(c):
        a, b = max(0, j - lo), min(c, j + (nsize - 1 - lo) + 1)
        norm = knorm + alpha / nsize * np.sum(x[..., a:b] ** 2, axis=-1)
        out[..., j] = x[..., j] * norm ** -beta
    return out


def _x(c, seed=0, lead=(2, 3, 2)):
    rng = np.random.RandomState(seed + c)
    return jnp.asarray(rng.randn(*lead, c).astype(np.float32) * 3.0)


def _grads(fn, x, w):
    return jax.grad(lambda v: jnp.sum(fn(v).astype(jnp.float32) * w))(x)


@pytest.mark.parametrize('c', CHANNELS)
@pytest.mark.parametrize('nsize', SIZES)
def test_forward_matches_the_naive_loop(nsize, c):
    x = _x(c)
    want = numpy_lrn(x, nsize)
    np.testing.assert_allclose(lrn(x, nsize, ALPHA, BETA, KNORM), want,
                               rtol=1e-5, atol=1e-6)
    # the reference the gradient tests differentiate is this one too
    np.testing.assert_allclose(naive_lrn(x, nsize), want, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize('c', CHANNELS)
@pytest.mark.parametrize('nsize', SIZES)
def test_gradient_matches_autodiff_of_the_naive_loop(nsize, c):
    x, w = _x(c, 1), _x(c, 2)
    got = _grads(lambda v: lrn(v, nsize, ALPHA, BETA, KNORM), x, w)
    want = _grads(lambda v: naive_lrn(v, nsize), x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('nsize,c', [(5, 24), (5, 96), (5, 256), (4, 64),
                                     (3, 192), (5, 3)])
def test_bf16_input_rounds_the_float32_result_once(nsize, c):
    """bf16 in and out, float32 between: output and ``dx`` are the float32
    results of the same bf16 values, rounded to bf16 (one ulp of room for
    the sum's order)."""
    x = _x(c, 3).astype(jnp.bfloat16)
    w = _x(c, 4).astype(jnp.bfloat16)
    x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)
    out = lrn(x, nsize, ALPHA, BETA, KNORM)
    dx = _grads(lambda v: lrn(v, nsize, ALPHA, BETA, KNORM), x, w32)
    assert out.dtype == dx.dtype == jnp.bfloat16
    ulp = 2.0 ** -7                      # bf16 keeps eight bits
    for got, want in ((out, naive_lrn(x32, nsize)),
                      (dx, _grads(lambda v: naive_lrn(v, nsize), x32, w32))):
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   rtol=ulp, atol=1e-6)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_the_only_residual_is_x(dtype):
    """What the backward pass keeps is ``x`` in its own dtype: no float32
    array of its size (a stored norm), nothing else."""
    from jax._src.ad_checkpoint import saved_residuals
    x = _x(96).astype(dtype)
    res = saved_residuals(lambda v: lrn(v, 5, ALPHA, BETA, KNORM), x)
    kept = [(aval.shape, aval.dtype) for aval, _ in res]
    assert kept == [(x.shape, dtype)], res
    # and the backward rule computes from it alone: its jaxpr reads x and g
    _, vjp = jax.vjp(lambda v: lrn(v, 5, ALPHA, BETA, KNORM), x)
    consts = jax.make_jaxpr(vjp)(x).jaxpr.constvars
    assert [(v.aval.shape, v.aval.dtype) for v in consts
            if v.aval.shape == x.shape] == [(x.shape, dtype)]


def test_under_jit_and_vmap():
    x = _x(24, 5)
    f = jax.jit(lambda v: lrn(v, 5, ALPHA, BETA, KNORM))
    np.testing.assert_allclose(f(x), naive_lrn(x, 5), rtol=1e-5, atol=1e-6)
    per_row = jax.vmap(lambda v: lrn(v, 5, ALPHA, BETA, KNORM))(x)
    np.testing.assert_allclose(per_row, naive_lrn(x, 5), rtol=1e-5,
                               atol=1e-6)
# --- in the net --------------------------------------------------------------

# --- in the net ---------------------------------------------------------------

CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[1->2] = max_pooling
  kernel_size = 2
  stride = 2
layer[2->3] = lrn
  local_size = 5
layer[3->4] = conv:c2
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[4->5] = flatten
layer[5->6] = fullc:fc
  nhidden = 4
layer[6->6] = softmax
netconfig = end
input_shape = 1,8,8
batch_size = 8
eta = 0.1
metric = error
"""


def _step(extra='dev = cpu\n'):
    tr = NetTrainer(parse_config_string(CONF + extra))
    tr.init_model()
    rng = np.random.RandomState(0)
    batch = DataBatch(rng.rand(8, 1, 8, 8).astype(np.float32),
                      rng.randint(0, 4, (8, 1)).astype(np.float32))
    data, label, extra_, mask = tr.stage_batch(batch)[:4]
    key = jax.random.fold_in(tr._rng, 1)
    return tr, tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra_, mask, key,
        tr.epoch_counter, tr.round, do_update=True, norm=())


def _primitives_under(lowered, scope):
    """Names of what was traced inside ``scope``, (forward, backward), from
    the lowered program's location table."""
    table = lowered.as_text(debug_info=True)
    found = re.findall(
        r'loc\("jit\(train_step\)/(transpose\()?jvp\(%s\)\)?/([^"]+)"'
        % scope, table)
    return ({p for t, p in found if not t}, {p for t, p in found if t})


def test_step_lowers_lrn_to_plain_xla():
    """conv -> pool -> lrn -> conv: inside ``l02_lrn`` there is no custom
    call, no cumsum, no windowed reduction and no reshape to (rows, c);
    the forward is elementwise alone, the backward's one dot is the band;
    the compiled program has no custom call under the layer's name."""
    _, lowered = _step()
    fwd, bwd = _primitives_under(lowered, 'l02_lrn')
    assert {'slice', 'mul', 'add', 'pow'} <= fwd, fwd
    assert {'slice', 'mul', 'pow', 'div'} <= bwd, bwd
    assert [p for p in bwd if p.endswith('dot_general')], bwd
    banned = ('custom_call', 'pallas_call', 'cumsum', 'reduce_window',
              'reshape', 'conv_general_dilated')
    assert not [p for p in fwd | bwd if any(b in p for b in banned)]
    assert not [p for p in fwd if 'dot_general' in p], fwd
    for line in lowered.compile().as_text().splitlines():
        if 'l02_lrn' in line:
            assert 'custom-call' not in line and 'custom_call' not in line


@pytest.mark.parametrize('value', ['1', '0'])
def test_use_pallas_does_not_reach_lrn(monkeypatch, value):
    """``use_pallas`` forces or forbids kernels elsewhere; the LRN layer
    lowers to the same program whatever it says."""
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    layer = create_layer(get_layer_type('lrn'))
    layer.set_param('local_size', '5')
    layer.infer_shapes([NodeSpec(96, 3, 3)])
    x = _x(96, 6)

    def text():
        return jax.jit(lambda v: layer.forward(
            {}, [v], ForwardContext(is_train=True))[0]).lower(x).as_text()

    auto = text()
    monkeypatch.setenv('CXXNET_PALLAS', value)
    assert text() == auto
    assert 'custom_call' not in auto


def test_batch_sharded_over_eight_devices_equals_one_device():
    """Under a mesh the layer is batch-sharded elementwise work with no
    fall-back: output and gradient equal the one-device ones."""
    devices = jax.devices()
    assert len(devices) >= 8
    mesh = Mesh(np.array(devices[:8]), ('data',))
    x, w = _x(96, 7, lead=(16, 3, 3)), _x(96, 8, lead=(16, 3, 3))

    def both(v, u):
        fn = lambda t: lrn(t, 5, ALPHA, BETA, KNORM)  # noqa: E731
        return fn(v), _grads(fn, v, u)

    one_out, one_dx = jax.jit(both)(x, w)
    shard = NamedSharding(mesh, P('data'))
    out, dx = jax.jit(both)(jax.device_put(x, shard),
                            jax.device_put(w, shard))
    assert len(out.sharding.device_set) == 8
    np.testing.assert_array_equal(out, one_out)
    # the band's dot blocks two rows a device otherwise than sixteen on one
    np.testing.assert_allclose(dx, one_dx, rtol=1e-5, atol=1e-6)


def test_step_on_the_mesh_carries_the_same_lrn():
    """The data-parallel step program traces the LRN the one-device one
    does: the same primitives under its scope, no fall-back."""
    _, one = _step()
    tr, mesh_step = _step('dev = cpu:0-7\n')
    assert tr._mesh.devices.size == 8
    assert _primitives_under(mesh_step, 'l02_lrn') \
        == _primitives_under(one, 'l02_lrn')
