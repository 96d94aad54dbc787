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


def _instructions(lines):
    """(opcode, result type) of each instruction line of a computation."""
    for line in lines:
        m = re.match(r'\s+(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][a-z\-]*)\(', line)
        if m:
            yield m.group(2), m.group(1)


def test_head_loss_is_products_and_fused_passes_on_the_v5e(one_chip):
    """``lm_head_loss`` at the benchmark cell's shape (one 8,192-token
    sequence of 2,048 bf16, 19,360 vocabulary rows, 1,024 tokens a chunk),
    loss and gradients: no gather, scatter or sort anywhere; a chunk's
    forward hands one float32 ``(1024, 19360)`` array from one instruction
    to the next (the logits, to the pass that sums them) and its backward
    none (the logits' product writes their gradient, in bf16)."""
    from cxxnet_tpu.layers import ForwardContext, NodeSpec
    from cxxnet_tpu.layers.sequence import LMHeadLossLayer
    layer = LMHeadLossLayer('head')
    for key, val in dict(vocab_held=19360, batch_size=1,
                         chunk_tokens=1024).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(2048, 1, 8192)])

    def loss(w, x, labels):
        with jax.named_scope('l19_lm_head_loss'):
            return layer.loss({'wmat': w}, [x], labels,
                              ForwardContext(is_train=True))

    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        s((2048, 19360), jnp.float32), s((1, 1, 8192, 2048), jnp.bfloat16),
        s((1, 8192), jnp.float32)).compile().as_text()
    assert 'transpose(jvp(l19_lm_head_loss))' in hlo
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r'(?:ENTRY )?%([\w.\-]+) \(.*\{$', line)
        if m:
            name = 'ENTRY' if line.startswith('ENTRY') else m.group(1)
        comps.setdefault(name, []).append(line)
    assert not [op for lines in comps.values() for op, _ in
                _instructions(lines) if op in ('gather', 'scatter', 'sort')]
    bodies = re.findall(r'body=%([\w.\-]+)', hlo)
    assert len(bodies) == 2                 # the chunks forward, and backward
    handed = sorted(sum(
        'f32[1024,19360]' in kind or 'f32[1,1024,19360]' in kind
        for op, kind in _instructions(comps[where])
        if op not in ('get-tuple-element', 'bitcast', 'tuple', 'parameter'))
        for where in bodies + ['ENTRY'])
    assert handed == [0, 0, 1], handed
    assert 'bf16[1024,19360]' in hlo


# --- the sequence layers' kernels at GLM-4.7-Flash's widths (PR 29) ----------

def _steer_onto_the_chip_path(monkeypatch):
    """``ops/attention`` asks ``jax.default_backend()``, which is the CPU
    here: the test steers it, the program has no option for it."""
    from cxxnet_tpu.ops import attention
    monkeypatch.setattr(attention, '_use_flash',
                        lambda q, k, v, spmd: spmd == 1)


def test_blocked_attention_compiles_at_the_published_heads(one_chip,
                                                           monkeypatch):
    """20 heads of 192 + 64 query/key and 256 value dims over 8,192
    positions: the Pallas flash kernels, forward and both backward ones,
    are taken by Mosaic at the block sizes ``ops/attention`` gives them, and
    no (heads, seq, seq) array is in the program."""
    from cxxnet_tpu.ops.attention import causal_attention
    _steer_onto_the_chip_path(monkeypatch)

    def loss(q, k, v):
        with jax.named_scope('l03_mla'):
            o = causal_attention(q, k, v, 1.0 / 16.0)
        return jnp.sum(o.astype(jnp.float32))

    x = jax.ShapeDtypeStruct((1, 20, 8192, 256), jnp.bfloat16,
                             sharding=one_chip)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert hlo.count('tpu_custom_call') >= 3
    assert '8192,8192' not in hlo


def _grouped_products(lines):
    return [l for l in lines if 'tpu_custom_call' in l
            and l.lstrip().startswith('%ragged-dot-none')]


def _tilings(calls):
    """``ragged_dot_tiling`` of each grouped product's line, as written."""
    return [re.search(r'ragged_dot_tiling="([\d,]+)"', l).group(1)
            for l in calls]


def _tilings_of_the_rule(rows, d, f):
    """``grouped_tiling``'s tilings of gate and up (``d x f``) and of down
    (``f x d``) over 8 held experts, as the compiled text writes them."""
    from cxxnet_tpu.parallel import moe
    up, down = (moe.grouped_tiling(rows, 8, d, f),
                moe.grouped_tiling(rows, 8, f, d))
    assert up == (256, 512, f) and down == (256, f, 512)
    return [','.join(map(str, tiling)) for tiling in (up, down)]


# the bounded buffer of both LM cells (8,192 rows, 8 held experts) at GLM's
# and Laguna's widths, a narrower pair the same rule serves, and the worst
# case's whole buffer at GLM's (4,096 rows a group: the compiler's own tiling)
@pytest.mark.parametrize('rows,d,f', [(8192, 2048, 1536), (8192, 3072, 1024),
                                      (8192, 1024, 512), (4096, 4096, 1408),
                                      (32768, 2048, 1536)])
def test_grouped_expert_products_compile_at_the_published_widths(one_chip,
                                                                 rows, d, f):
    """8 held experts' three grouped products and their gradients: each
    forward product carries the tiling ``grouped_tiling`` gives its shape to
    the compiler, and so do its two transposes (the gradient to the rows and
    the gradient to the weights); where the rule gives none the compiler's
    own 512 stands.  The loss leaves the down product's result unread, so 8
    of the 9 are compiled."""
    from cxxnet_tpu.parallel import moe

    def loss(xs, wg, wu, wd, sizes):
        return jnp.sum(moe.grouped_swiglu(xs, wg, wu, wd,
                                          sizes).astype(jnp.float32))

    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        s((rows, d), jnp.bfloat16), s((8, d, f), jnp.float32),
        s((8, d, f), jnp.float32), s((8, f, d), jnp.float32),
        s((8,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    if rows == 32768:
        assert moe.grouped_tiling(rows, 8, d, f) is None
        assert moe.grouped_tiling(rows, 8, f, d) is None
        up = down = '512,512,512'
    else:
        up, down = _tilings_of_the_rule(rows, d, f)
    found = sorted(_tilings(_grouped_products(
        compiled.as_text().splitlines())))
    # gate and up: the product and both transposes; down: the transposes
    assert found == sorted([up] * 6 + [down] * 2)


def _conf_without_iterators(*path):
    """A conf's pairs with the data, eval and pred sections and ``dev`` left
    out: what builds the net and its step, nothing that reads a file."""
    import os
    from cxxnet_tpu.utils.config import parse_config_file
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pairs, skipping = [], False
    for k, v in parse_config_file(os.path.join(root, 'example', *path)):
        skipping = skipping or k in ('data', 'eval', 'pred')
        if not skipping and k != 'dev':
            pairs.append((k, v))
        skipping = skipping and (k, v) != ('iter', 'end')
    return pairs


def _described(tr, one_chip):
    """The trainer's parameters and optimizer state as shapes on the
    described chip, and a maker of further such shapes."""
    from cxxnet_tpu.updater import init_opt_state
    arg = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: arg(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(tr.net.init_params,
                                    jax.random.PRNGKey(0)))
    opt = on_chip(jax.eval_shape(
        lambda p: init_opt_state(tr.net_cfg.updater_type, p), params))
    return params, opt, arg


@pytest.fixture(scope='module')
def glm_step(one_chip):
    """The example conf's training step, compiled for one v5e chip from
    shapes alone (about a minute: once for the tests that read it)."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import attention
    tr = NetTrainer(_conf_without_iterators('LM', 'GLM-4.7-Flash.ep8.conf')
                    + [('dev', 'cpu')])
    tr.init_net()
    params, opt, arg = _described(tr, one_chip)
    seq = 8192
    with pytest.MonkeyPatch.context() as patch:
        # ops/attention asks jax.default_backend(), which is the CPU here:
        # the test steers it, the program has no option for it
        patch.setattr(attention, '_use_flash',
                      lambda q, k, v, spmd: spmd == 1)
        # update_period = 1: the step takes no accumulator (None)
        compiled = tr._train_step_fn._jit.lower(
            params, opt, None,
            arg((1, 1, 1, seq + 1), jnp.int32),
            arg((1, 2 * seq), jnp.float32), (), arg((1,), jnp.float32),
            arg((2,), jnp.uint32), 0, 0, do_update=True).compile()
    return compiled, params


def test_the_whole_step_fits_the_chip(glm_step):
    """706.5 M parameters at 12 bytes each (the master and Adam's two
    moments: ``update_period = 1`` keeps no accumulator, PR 32) plus the
    step's temporaries, the gradients among them, stay under the chip's
    16.9e9 bytes with room for the forward program.  Read 11.14e9 at PR 34
    (an expert layer's full buffer is planned inside a conditional of its
    own), 11.78e9 at PR 32; with the accumulator 14.30e9.  11.02e9 at PR 35;
    with ``k_nope`` and ``v`` from two products it read 11.65e9: the
    scheduler then ran the main head's loss after the MTP module's expert
    layer had been recomputed, and both heads' float32 ``dW`` (0.51e9)
    stood beside that layer's whole-buffer backward branch (PERF.md 6)."""
    import numpy as np
    compiled, params = glm_step
    m = compiled.memory_analysis()
    state = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) * 12
    assert state == 706_518_848 * 12
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= state          # the state is donated
    assert m.argument_size_in_bytes < state + 2 ** 20   # and nothing else
    assert live < 11.5e9, live / 2 ** 30           # 16.9e9 on the chip


@pytest.mark.parametrize('event', ['flash_attention', 'flash_mha_bwd_dq',
                                   'flash_mha_bwd_dkv'])
def test_the_kernels_the_benchmark_reads_keep_their_names(glm_step, event):
    """``kernels.flash_*_roofline_pct`` join a trace's device events to
    JAX's three flash kernels by the start of the custom call's name
    (``benchmark/scope_times.kernel_ms``, PERF.md 3): in the step the
    chip runs, every Mosaic call under a latent-attention layer is named
    so, and each of the three names is there."""
    from cxxnet_tpu.utils import profiler
    hlo = glm_step[0].as_text()
    scopes = profiler.hlo_op_names(hlo)
    kernels = {}
    for line in hlo.splitlines():
        m = profiler._HLO_INSTRUCTION.match(line)
        if m and 'tpu_custom_call' in line:
            kernels[m.group(1)] = profiler.scope_of(scopes[m.group(1)])[0]
    names = ('flash_attention', 'flash_mha_bwd_dq', 'flash_mha_bwd_dkv')
    in_mla = [n for n, scope in kernels.items() if '_mla' in scope]
    assert in_mla and all(n.startswith(names) for n in in_mla), in_mla
    mine = [n for n in in_mla if n.startswith(event)]
    # 6 latent-attention layers: the forward kernel in the forward pass and
    # in the backward's recomputation, dq and dkv once a layer
    assert len(mine) == (12 if event == 'flash_attention' else 6), mine


def _computations(hlo):
    """computation name -> its instruction lines, from a compiled text."""
    from cxxnet_tpu.utils import profiler
    found, lines = {}, None
    for line in hlo.splitlines():
        m = profiler._HLO_COMPUTATION.match(line)
        if m:
            lines = found.setdefault(m.group(1), [])
        elif lines is not None and profiler._HLO_INSTRUCTION.match(line):
            lines.append(line)
    return found


def _expert_layer_branches(hlo, pas, layers):
    """Of each expert layer's conditional of one pass, as instruction lines:
    (the body of the blocks' loop, the bounded branch, all the lines the
    branch that holds the loop reaches)."""
    comps = _computations(hlo)
    scope = {'fwd': r'/jvp\(l\d+_moe_\w+\)/',
             'bwd': r'/transpose\(jvp\(l\d+_moe_\w+\)\)/'}[pas]
    conds = [l for l in hlo.splitlines() if ' conditional(' in l
             and re.search(r'op_name="jit\(train_step\)' + scope, l)]
    assert len(conds) == layers, conds
    for line in conds:
        names = re.search(r'branch_computations=\{([^}]*)\}', line).group(1)
        blocks, bounded = re.findall(r'%([\w.\-]+)', names)  # false, true
        loops = [m for l in comps[blocks]
                 for m in re.findall(r' while\(.*body=%([\w.\-]+)', l)]
        assert len(loops) == 1, loops          # the blocks, and nothing else
        yield comps[loops[0]], comps[bounded], comps[blocks] + comps[loops[0]]


def _a_bounded_branch_and_the_blocks(hlo, pas, products, layers, rows, d, f):
    """Both branches hold the same ``products`` grouped products over
    ``rows`` rows, the second inside its loop; every one on the tiling
    ``grouped_tiling`` gives the forward product it is or transposes: gate
    and up ``d x f``, down ``f x d``, two to one in either pass."""
    up, down = _tilings_of_the_rule(rows, d, f)
    want = sorted([up] * (2 * products // 3) + [down] * (products // 3))
    for body, bounded, reached in _expert_layer_branches(hlo, pas, layers):
        for lines in (body, bounded):
            calls = _grouped_products(lines)
            assert len(calls) == products, len(calls)
            assert sorted(_tilings(calls)) == want
            by_rows = [l for l in calls if re.search(rf'= f32\[{rows},', l)]
            assert len(by_rows) == products - (3 if pas == 'bwd' else 0)
        assert not _grouped_products(
            [l for l in reached if l not in body])     # none outside the loop


@pytest.mark.parametrize('pas,products', [('fwd', 3), ('bwd', 9)])
def test_an_expert_layer_compiles_a_bounded_and_a_whole_branch(glm_step, pas,
                                                               products):
    """Each of the five expert layers holds one conditional a pass (the
    recomputation's forward one has no reader and is gone): the same
    grouped products in both branches, over the first ``bounded_rows`` rows
    in the one the benchmark's cell runs, and in the one that drops nothing
    at any imbalance inside a loop over blocks of as many rows (PR 36:
    before, over all 32,768 at once; now no float array of 32,768 rows is
    anywhere in the step).  A step runs 12 of the 24 a layer, so a count of
    executed products is 60 as before.  Every one on the tiling the rule
    gives its shape (PR 37), which is not the tile ``bounded_rows`` rounds
    to."""
    from cxxnet_tpu.parallel import moe
    hlo = glm_step[0].as_text()
    assert moe.bounded_rows(32768, 8, 64, 8192) == 8192
    _a_bounded_branch_and_the_blocks(hlo, pas, products, 5, 8192, 2048, 1536)
    assert not re.findall(r'(?:f32|bf16)\[32768,', hlo)


def test_no_kernel_of_the_step_lost_its_name(glm_step):
    """The Mosaic calls of the whole step are the three flash kernels and
    the grouped products with their metadata, by the names the benchmark's
    readers join on: 24 + 120 + 30."""
    import collections
    from cxxnet_tpu.utils import profiler
    known = ('flash_attention', 'flash_mha_bwd_dq', 'flash_mha_bwd_dkv',
             'ragged-dot-none', 'ragged-dot-metadata')
    found = collections.Counter()
    for line in glm_step[0].as_text().splitlines():
        m = profiler._HLO_INSTRUCTION.match(line)
        if m and 'tpu_custom_call' in line:
            found[next((k for k in known if m.group(1).startswith(k)),
                       m.group(1))] += 1
    assert found == dict(zip(known, (12, 6, 6, 120, 30))), found


# --- latent attention, head-major from product to kernel to product (PR 35) --

def _outside_fusions(hlo):
    """The instruction lines of every computation that runs as written: the
    entry, loop bodies and branches, not what a fusion calls."""
    comps = _computations(hlo)
    fused = set(re.findall(r' fusion\(.*?calls=%([\w.\-]+)', hlo))
    return [line for name, lines in comps.items() if name not in fused
            for line in lines]


def test_no_pass_moves_an_array_between_projections_and_kernels(glm_step):
    """``q``, ``k``, ``v`` and ``o`` are written ``(batch, heads, seq,
    dim)`` by the product or the concat that makes them: the step holds no
    ``copy`` or ``transpose`` instruction of its own (outside a fusion)
    whose result is a ``(1, 8192, 20, *)`` or ``(1, 20, 8192, *)`` bf16
    array, forward, recomputation or backward, and a ``slice`` only of the
    last axis of a head-major product's output (``v`` and ``k_nope`` out of
    ``[k_nope | v]``, which the kernels and the concat read as arrays of
    their own).  (The compiler's prefetches, ``copy-start`` /
    ``copy-done``, are another opcode.)"""
    mover = re.compile(r'= bf16\[1,(?:8192,20|20,8192),\d+\]\S* '
                       r'(?:copy|slice|transpose)\(')
    last_axis = re.compile(r' slice\(%fusion[.\d]*\), slice=\{\[0:1\], '
                           r'\[0:20\], \[0:8192\], \[\d+:\d+\]\}')
    moved = [line.strip()[:160]
             for line in _outside_fusions(glm_step[0].as_text())
             if mover.search(line) and not last_axis.search(line)]
    assert not moved, moved


@pytest.mark.parametrize('event', ['flash_attention', 'flash_mha_bwd_dq',
                                   'flash_mha_bwd_dkv'])
def test_a_kernel_reads_what_a_product_or_the_concat_wrote(glm_step, event):
    """Each flash kernel's ``q`` and ``k`` (its first two operands) is the
    output of the concat that joins the rotated part, and its ``v`` that
    of a product's fusion, whole or a slice of its last axis (``[k_nope |
    v]`` is one product), looked at through the compiler's prefetches and
    bitcasts: no copy or transpose fusion stands between."""
    from cxxnet_tpu.utils import profiler
    lines = {}
    for line in glm_step[0].as_text().splitlines():
        m = profiler._HLO_INSTRUCTION.match(line)
        if m:
            lines[m.group(1)] = line
    through = re.compile(
        r' (?:copy-done|copy-start|bitcast|slice)\(%([\w.\-]+)[,)]')

    def maker(name):
        while m := through.search(lines[name]):
            name = m.group(1)
        return lines[name]

    calls = [line for name, line in lines.items()
             if name.startswith(event) and 'tpu_custom_call' in line]
    assert len(calls) == (12 if event == 'flash_attention' else 6)
    for call in calls:
        operands = re.search(r' custom-call\(([^)]*)\)', call).group(1)
        q, k, v = (maker(name) for name in
                   re.findall(r'%([\w.\-]+)', operands)[:3])
        for made in (q, k):
            assert ' fusion(' in made and re.search(
                r'op_name="[^"]*/concatenate"', made), made
        assert ' fusion(' in v and 'kind=kOutput' in v and re.search(
            r'op_name="[^"]*bsr,rhd->bhsd/dot_general"', v), v


# the benchmark's two CNN confs (train step and evaluation forward) and the
# two other ImageNet examples (train step): at batch 8 no gate of the program
# would pick a kernel on the chip either (the fc8 eval matmul asks m >= 128)
@pytest.mark.parametrize('conf,program', [
    ('ImageNet.conf', 'train'), ('ImageNet.conf', 'eval'),
    ('GoogLeNet.conf', 'train'), ('GoogLeNet.conf', 'eval'),
    ('Inception-BN.conf', 'train'), ('VGG16.conf', 'train')])
def test_the_cnn_programs_hold_no_custom_call_on_the_v5e(one_chip, conf,
                                                         program):
    """PR 28's finding held for whole programs: one custom call in a CNN
    step cost 24-34% through the layouts it forced on its neighbours.  The
    step and the evaluation forward compile for the chip to XLA's own
    instructions, no Mosaic kernel among them."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    batch = 8
    tr = NetTrainer(_conf_without_iterators('ImageNet', conf)
                    + [('batch_size', str(batch)), ('dev', 'cpu')])
    tr.init_net()
    params, opt, arg = _described(tr, one_chip)
    c, y, x = tr.net_cfg.input_shape
    data = arg((batch, c, y, x), tr.compute_dtype)
    if program == 'train':
        compiled = tr._train_step_fn._jit.lower(
            params, opt, None, data, arg((batch, 1), jnp.float32), (),
            arg((batch,), jnp.float32), arg((2,), jnp.uint32), 0, 0,
            do_update=True).compile()
    else:
        compiled = tr._forward_fn._jit.lower(
            params, data, (), 0, nodes=tuple(tr._eval_node_ids)).compile()
    hlo = compiled.as_text()
    # XLA's own custom calls (a gather's packed indices) are not Mosaic's
    assert 'tpu_custom_call' not in hlo
    assert 'convolution(' in hlo


# --- Laguna-S-2.1's step: grouped, windowed attention and the blocks (PR 36) --

@pytest.fixture(scope='module')
def laguna_step(one_chip):
    """``example/LM/Laguna-S-2.1.ep32.conf``'s training step, compiled for
    one v5e chip from shapes alone (about a minute)."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import attention
    tr = NetTrainer(_conf_without_iterators('LM', 'Laguna-S-2.1.ep32.conf')
                    + [('dev', 'cpu')])
    tr.init_net()
    params, opt, arg = _described(tr, one_chip)
    seq = 8192
    with pytest.MonkeyPatch.context() as patch:
        # ops/attention asks jax.default_backend(), which is the CPU here:
        # the test steers it, the program has no option for it
        patch.setattr(attention, '_use_splash',
                      lambda q, k, v, spmd: spmd == 1)
        compiled = tr._train_step_fn._jit.lower(
            params, opt, None,
            arg((1, 1, 1, seq + 1), jnp.int32),
            arg((1, seq), jnp.float32), (), arg((1,), jnp.float32),
            arg((2,), jnp.uint32), 0, 0, do_update=True).compile()
    return compiled, params


def test_the_laguna_step_fits_the_chip(laguna_step):
    """811.0 M parameters at 12 bytes each resident and the step's
    temporaries (the float32 gradients, one layer's activations, the
    kernels' buffers) under the chip's 15.75 GiB with room for the forward
    program: the compiler's plan, printed.  Read 11.98e9 bytes (11.15 GiB)
    at PR 36; with the expert layer's overflow branch over all 81,920 rows
    the plan was some 5e9 more and fitted no chip."""
    import numpy as np
    compiled, params = laguna_step
    m = compiled.memory_analysis()
    state = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) * 12
    assert state == 811_018_240 * 12
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f'the Laguna step\'s plan: {live} bytes, {live / 2 ** 30:.3f} GiB '
          f'(state {state / 2 ** 30:.3f}, temporaries '
          f'{m.temp_size_in_bytes / 2 ** 30:.3f})')
    assert m.alias_size_in_bytes >= state          # the state is donated
    assert live < 13.0e9 < 15.75 * 2 ** 30, live / 2 ** 30


def _kernels_by_scope(hlo):
    """Mosaic call -> (its conf layer's scope, its line), from the text as
    ``step_program_text`` hands it out (an instruction a line)."""
    from cxxnet_tpu.obs.programs import an_instruction_a_line
    from cxxnet_tpu.utils import profiler
    hlo = an_instruction_a_line(hlo)
    scopes = profiler.hlo_op_names(hlo)
    found = {}
    for line in hlo.splitlines():
        m = profiler._HLO_INSTRUCTION.match(line)
        if m and 'tpu_custom_call' in line:
            found[m.group(1)] = (profiler.scope_of(scopes[m.group(1)])[0],
                                 line)
    return found


@pytest.mark.parametrize('step', ['glm_step', 'laguna_step', 'solar_step'])
def test_the_grouped_products_are_filed_under_their_moe_layer(step, request):
    """XLA writes ``op_name="ragged-dot-none"`` over a grouped product's
    path, so the call names no scope of its own; ``profiler.hlo_program``
    files it where most of what it reads and of what reads it is filed (PR
    38: the benchmark's rule since PR 33).  Every one of the compiled step's
    lands under an expert layer, so ``profile_dir=`` prints the products
    under ``l*_moe*`` and not under ``other``."""
    kernels = _kernels_by_scope(request.getfixturevalue(step)[0].as_text())
    products = {n: scope for n, (scope, _) in kernels.items()
                if n.startswith('ragged-dot-none')}
    assert len(products) >= 48, len(products)
    assert all('_moe_' in scope for scope in products.values()), products


@pytest.mark.parametrize('event,calls', [('splash_mqa_fwd', 10),
                                         ('splash_mqa_dq', 5),
                                         ('splash_mqa_dkv', 5)])
def test_the_gqa_kernels_keep_their_names(laguna_step, event, calls):
    """``kernels.gqa_*_roofline_pct`` join a trace's device events to the
    block-sparse kernels by the start of the custom call's name
    (``benchmark/attention_costs.KERNELS``): every Mosaic call under a
    ``gqa`` layer's scope is named so, five layers' forward kernel twice
    (the recomputation) and dq and dkv once."""
    kernels = _kernels_by_scope(laguna_step[0].as_text())
    names = ('splash_mqa_fwd', 'splash_mqa_dq', 'splash_mqa_dkv')
    in_gqa = [n for n, (scope, _) in kernels.items() if '_gqa' in scope]
    assert in_gqa and all(n.startswith(names) for n in in_gqa), in_gqa
    assert len([n for n in in_gqa if n.startswith(event)]) == calls
    others = {n for n in kernels if n not in in_gqa}
    assert all(n.startswith('ragged-dot') for n in others), others


def test_a_window_layers_kernels_get_a_block_sparse_grid(laguna_step):
    """The mask is laid over the grid of blocks when the step is traced: a
    kernel's first operand is the table of key blocks to fetch, ``s8[1,
    query blocks, key blocks a query block]``.  A window layer's has as many
    key blocks a query block as reach over window + block - 1 keys (2 of 16
    at 512: the other 14 are neither fetched nor computed), a full layer's
    all of them; in the forward, the dq and, transposed, the dkv kernel."""
    from cxxnet_tpu.ops import attention
    kernels = _kernels_by_scope(laguna_step[0].as_text())
    seen = {}
    for name, (scope, line) in kernels.items():
        if '_gqa' not in scope:
            continue
        table = re.search(r'operand_layout_constraints=\{s8\[1,(\d+),(\d+)\]',
                          line)
        assert table, line[:300]
        windowed = scope in ('l04_gqa_attn1', 'l06_gqa_attn2',
                             'l08_gqa_attn3')
        kind = name.split('_')[2].split('.')[0]            # fwd, dq, dkv
        seen.setdefault((windowed, kind), set()).add(
            tuple(int(g) for g in table.groups()))
    bq, bkv, _ = attention.SPLASH_BLOCKS_WINDOW
    reach = -(-(512 + bq - 1) // bkv)                  # key blocks a q block
    assert seen[True, 'fwd'] == seen[True, 'dq'] == {(8192 // bq, reach)}
    assert reach * bkv <= 2 * 512 < 8192
    # dkv walks the query blocks of a key block: as few
    (dkv,) = seen[True, 'dkv']
    assert dkv[0] * dkv[1] <= (8192 // bkv) * -(-(512 + bkv - 1) // bq)
    fq, fkv, _ = attention.SPLASH_BLOCKS_FULL
    assert seen[False, 'fwd'] == seen[False, 'dq'] == {(8192 // fq,
                                                        8192 // fkv)}


@pytest.mark.parametrize('pas,products', [('fwd', 3), ('bwd', 9)])
def test_no_assignment_row_array_stands_in_an_expert_layers_branch(
        laguna_step, pas, products):
    """81,920 assignments of 3,072-wide rows: the bounded branch works on
    ``bounded_rows`` = 8,192 rows and the branch that drops nothing on
    blocks of as many in a loop, and nowhere in the step is there a float
    array with a row an assignment (the sorted order and its masks are
    integers)."""
    from cxxnet_tpu.parallel import moe
    hlo = laguna_step[0].as_text()
    assert moe.bounded_rows(8192 * 10, 8, 256, 8192) == 8192
    _a_bounded_branch_and_the_blocks(hlo, pas, products, 4, 8192, 3072, 1024)
    assert not re.findall(r'(?:f32|bf16)\[81920,', hlo)


# --- Solar-Open2-250B's step: the chunked delta rule beside NoPE GQA ---------

@pytest.fixture(scope='module')
def solar_step(one_chip):
    """``example/LM/Solar-Open2-250B.ep40tp4.conf``'s training step, compiled
    for one v5e chip from shapes alone (about a minute and a half)."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import attention, delta_rule
    tr = NetTrainer(_conf_without_iterators('LM',
                                            'Solar-Open2-250B.ep40tp4.conf')
                    + [('dev', 'cpu')])
    tr.init_net()
    params, opt, arg = _described(tr, one_chip)
    seq = 8192
    with pytest.MonkeyPatch.context() as patch:
        # ops/attention and ops/delta_rule ask jax.default_backend(), which
        # is the CPU here: the test steers them, the program has no option
        # for it
        patch.setattr(attention, '_use_splash',
                      lambda q, k, v, spmd: spmd == 1)
        patch.setattr(delta_rule, '_use_kernel', lambda q, v, spmd: spmd == 1)
        compiled = tr._train_step_fn._jit.lower(
            params, opt, None,
            arg((1, 1, 1, seq + 1), jnp.int32),
            arg((1, seq), jnp.float32), (), arg((1,), jnp.float32),
            arg((2,), jnp.uint32), 0, 0, do_update=True).compile()
    return compiled, params


def test_the_solar_step_fits_the_chip(solar_step):
    """905.8 M parameters at 12 bytes each resident (10.12 GiB) and the
    step's temporaries under the chip's 15.75 GiB: the compiler's plan,
    printed.  Read 15.74e9 bytes (14.66 GiB; the chip's peak read 14.64
    GiB, PERF.md 5); the peak lies in the last expert layer's backward
    through the blocks of its buffer, not in a delta layer."""
    import numpy as np
    compiled, params = solar_step
    m = compiled.memory_analysis()
    state = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) * 12
    assert state == 905_766_576 * 12
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f'the Solar step\'s plan: {live} bytes, {live / 2 ** 30:.3f} GiB '
          f'(state {state / 2 ** 30:.3f}, temporaries '
          f'{m.temp_size_in_bytes / 2 ** 30:.3f})')
    assert m.alias_size_in_bytes >= state          # the state is donated
    assert live < 15.9e9 < 15.75 * 2 ** 30, live / 2 ** 30


@pytest.mark.parametrize('event,calls', [('splash_mqa_fwd', 2),
                                         ('splash_mqa_dq', 1),
                                         ('splash_mqa_dkv', 1),
                                         ('delta_rule_fwd', 2),
                                         ('delta_rule_bwd', 1)])
def test_the_solar_kernels_keep_their_names(solar_step, event, calls):
    """The one softmax layer's Mosaic calls are the block-sparse kernels
    ``kernels.gqa_*_roofline_pct`` read, and each of the three delta
    layers' are the delta rule's kernels ``kernels.delta_rule_ms_per_step``
    reads: the forward twice (the recomputation) and the backward once.
    Every other Mosaic call of the step is a grouped product."""
    kernels = _kernels_by_scope(solar_step[0].as_text())
    layer, names = (('_gqa', ('splash_mqa_fwd', 'splash_mqa_dq',
                              'splash_mqa_dkv'))
                    if event.startswith('splash') else
                    ('_kda', ('delta_rule_fwd', 'delta_rule_bwd')))
    mine = {n: scope for n, (scope, _) in kernels.items() if layer in scope}
    assert mine and all(n.startswith(names) for n in mine), mine
    scopes = sorted(set(mine.values()))
    assert len(scopes) == (1 if layer == '_gqa' else 3), scopes
    for scope in scopes:
        assert len([n for n, at in mine.items()
                    if at == scope and n.startswith(event)]) == calls, scope
    others = {n for n, (scope, _) in kernels.items()
              if '_gqa' not in scope and '_kda' not in scope}
    assert others and all(n.startswith('ragged-dot') for n in others), others


def test_no_scan_or_solve_stands_in_a_delta_layer(solar_step):
    """On the kernels the delta layers hold no loop over chunks and no
    batched triangular solve (the XLA form's ``lax.scan``, its transpose
    and ``lax.linalg.triangular_solve``, which the TPU's compiler rewrites
    as ``InvertDiagBlocksLowerTriangular`` and fusions that keep its
    ``op_name``), in any pass."""
    from cxxnet_tpu.obs.programs import an_instruction_a_line
    from cxxnet_tpu.utils import profiler
    hlo = an_instruction_a_line(solar_step[0].as_text())
    scopes = profiler.hlo_op_names(hlo)
    found = []
    for line in hlo.splitlines():
        m = profiler._HLO_INSTRUCTION.match(line)
        op = scopes.get(m.group(1), '') if m else ''
        if '_kda' in profiler.scope_of(op)[0] and (
                ' while(' in line or 'triangular' in op + line):
            found.append(line[:200])
    assert not found, found
