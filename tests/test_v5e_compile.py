"""Compiles for a described v5e, no chip attached (the on-chip-measurement
guide's third rehearsal): what the main path hands the TPU's compiler at the
benchmark's widths, and that the names this repo gives it are the names the
compiler keeps.  All such compiles live in this one file: the worker that
runs it loads the TPU's library and holds it.  The topology is described
inside a fixture, never at import, and the tests skip where it cannot be."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cxxnet_tpu.layers.norm import lrn


@pytest.fixture(scope='module')
def one_chip():
    import os
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says "not here"
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


# the four LRN layers of the benchmark's cells at their batch: AlexNet's
# l03_lrn and l07_lrn (b1024), GoogLeNet's l003_lrn and l008_lrn (b256)
@pytest.mark.parametrize('shape', [(1024, 27, 27, 96), (1024, 13, 13, 256),
                                   (256, 56, 56, 64), (256, 56, 56, 192)])
def test_lrn_is_no_custom_call_on_the_v5e(one_chip, shape):
    """Forward and backward of the one LRN compile for the chip with no
    Mosaic kernel, under the layer's scope, and hand no float32 array of
    ``x``'s size from one instruction to the next: the norm is recomputed,
    not kept."""
    def loss(x):
        with jax.named_scope('l03_lrn'):
            y = lrn(x, 5, 1e-4, 0.75, 1.0)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    assert 'tpu_custom_call' not in hlo
    assert 'transpose(jvp(l03_lrn))' in hlo
    entry = hlo[hlo.index('ENTRY'):]
    dims = ','.join(map(str, shape))
    assert not re.findall(r'= \(?f32\[%s\]' % dims, entry)
