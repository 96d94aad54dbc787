"""Layer framework core: hyperparameters, node specs, registry, base class.

TPU-native redesign of the reference layer system
(``src/layer/layer.h:31-373``, ``src/layer/param.h:15-138``):

* Layers are **pure functions** over JAX arrays — `forward(params, inputs,
  ctx)` returns outputs with no in-place node mutation.  Backward passes come
  from `jax.grad` through the whole net (verified layer-by-layer against
  NumPy references in the pairtest harness, see ``layers/pairtest.py``), so
  everything stays inside one jitted, XLA-fusable train step.
* Activations use NHWC layout (TPU-friendly); the reference's NCHW
  ``(batch, channel, y, x)`` shapes appear only at the config/checkpoint
  boundary.  Matrices are plain ``(batch, len)``.
* The integer layer-type ids are the reference's stable on-disk ids
  (``src/layer/layer.h:284-314``) and are preserved for checkpoint interop.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# stable layer-type ids (on-disk format) — src/layer/layer.h:284-314
kSharedLayer = 0
kFullConnect = 1
kSoftmax = 2
kRectifiedLinear = 3
kSigmoid = 4
kTanh = 5
kSoftplus = 6
kFlatten = 7
kDropout = 8
kConv = 10
kMaxPooling = 11
kSumPooling = 12
kAvgPooling = 13
kLRN = 15
kBias = 17
kConcat = 18
kXelu = 19
kCaffe = 20
kReluMaxPooling = 21
kMaxout = 22
kSplit = 23
kInsanity = 24
kInsanityPooling = 25
kL2Loss = 26
kMultiLogistic = 27
kChConcat = 28
kPRelu = 29
kBatchNorm = 30
kFixConnect = 31
# sequence layers (layers/sequence.py); no reference counterpart, ids of
# this repo's own, stable on disk like the rest
kEmbedding = 40
kRMSNorm = 41
kMLA = 42
kSwiGLU = 43
kMoE = 44
kMTPJoin = 45
kLMHeadLoss = 46
kSeqSlice = 48
kGQA = 49
kKDA = 50
kPairTestGap = 1024

_NAME2TYPE = {
    'fullc': kFullConnect, 'fixconn': kFixConnect, 'bias': kBias,
    'softmax': kSoftmax, 'relu': kRectifiedLinear, 'sigmoid': kSigmoid,
    'tanh': kTanh, 'softplus': kSoftplus, 'flatten': kFlatten,
    'dropout': kDropout, 'conv': kConv, 'relu_max_pooling': kReluMaxPooling,
    'max_pooling': kMaxPooling, 'sum_pooling': kSumPooling,
    'avg_pooling': kAvgPooling, 'lrn': kLRN, 'concat': kConcat,
    'xelu': kXelu, 'maxout': kMaxout, 'split': kSplit,
    'insanity': kInsanity, 'insanity_max_pooling': kInsanityPooling,
    'l2_loss': kL2Loss, 'multi_logistic': kMultiLogistic,
    'ch_concat': kChConcat, 'prelu': kPRelu, 'batch_norm': kBatchNorm,
    'embedding': kEmbedding, 'rmsnorm': kRMSNorm, 'mla': kMLA,
    'swiglu': kSwiGLU, 'moe': kMoE, 'mtp_join': kMTPJoin,
    'lm_head_loss': kLMHeadLoss, 'seq_slice': kSeqSlice, 'gqa': kGQA,
    'kda': kKDA,
}
_TYPE2NAME = {v: k for k, v in _NAME2TYPE.items()}
_TYPE2NAME[kMaxPooling] = 'max_pooling'  # keep canonical names on collision


def get_layer_type(type_str: str) -> int:
    """String → stable integer type id (``GetLayerType``, layer.h:322-361)."""
    if type_str.startswith('share'):
        return kSharedLayer
    if type_str.startswith('pairtest-'):
        rest = type_str[len('pairtest-'):]
        master, _, slave = rest.partition('-')
        slave = slave.split(':')[0]
        return kPairTestGap * get_layer_type(master) + get_layer_type(slave)
    if type_str in _NAME2TYPE:
        return _NAME2TYPE[type_str]
    if type_str == 'caffe':
        # reference plugin enum 20 (plugin/caffe_adapter-inl.hpp): wraps
        # live caffe::Layer objects — rejected scope on a TPU stack (see
        # PARITY.md), reported distinctly from a typo'd layer name
        raise ValueError(
            "layer type 'caffe' (reference plugin enum 20) is an "
            'unsupported plugin: it adapts in-process caffe::Layer objects '
            'and has no TPU equivalent')
    raise ValueError(f'unknown layer type: "{type_str}"')


def layer_type_name(type_id: int) -> str:
    if type_id >= kPairTestGap:
        return (f'pairtest-{layer_type_name(type_id // kPairTestGap)}'
                f'-{layer_type_name(type_id % kPairTestGap)}')
    if type_id == kSharedLayer:
        return 'share'
    return _TYPE2NAME.get(type_id, f'<type{type_id}>')


@dataclasses.dataclass
class LayerParam:
    """Shared layer hyperparameters (``src/layer/param.h:15-110``)."""

    num_hidden: int = 0
    init_sigma: float = 0.01
    init_sparse: int = 10
    init_uniform: float = -1.0
    init_bias: float = 0.0
    num_channel: int = 0
    random_type: int = 0          # 0 gaussian, 1 xavier/uniform, 2 kaiming
    num_group: int = 1
    kernel_height: int = 0
    kernel_width: int = 0
    stride: int = 1
    pad_y: int = 0
    pad_x: int = 0
    no_bias: int = 0
    temp_col_max: int = 64 << 18
    silent: int = 0
    num_input_channel: int = 0
    num_input_node: int = 0
    # conv MXU-lowering experiment knob (beyond reference):
    # auto | native (lax.conv) | im2col (patches GEMM, shallow inputs) |
    # split (per-group convs instead of feature_group_count)
    conv_lowering: str = 'auto'
    # μ-cuDNN-style conv microbatching (beyond reference): split the
    # conv's batch axis into this many sequential slices to bound the
    # layer's live workspace; bitwise-equal to unsplit by construction
    # (layers/conv.microbatched_conv) and priced by grafttune's
    # LedgerGate as a mem_inv knob
    micro_batch: int = 1

    def set_param(self, name: str, val: str) -> None:
        if name == 'init_sigma':
            self.init_sigma = float(val)
        if name == 'init_uniform':
            self.init_uniform = float(val)
        if name == 'init_bias':
            self.init_bias = float(val)
        if name == 'init_sparse':
            self.init_sparse = int(val)
        if name == 'random_type':
            table = {'gaussian': 0, 'uniform': 1, 'xavier': 1, 'kaiming': 2}
            if val not in table:
                raise ValueError(f'invalid random_type {val}')
            self.random_type = table[val]
        if name == 'nhidden':
            self.num_hidden = int(val)
        if name == 'nchannel':
            self.num_channel = int(val)
        if name == 'ngroup':
            self.num_group = int(val)
        if name == 'kernel_size':
            self.kernel_height = self.kernel_width = int(val)
        if name == 'kernel_height':
            self.kernel_height = int(val)
        if name == 'kernel_width':
            self.kernel_width = int(val)
        if name == 'stride':
            self.stride = int(val)
        if name == 'pad':
            self.pad_y = self.pad_x = int(val)
        if name == 'pad_y':
            self.pad_y = int(val)
        if name == 'pad_x':
            self.pad_x = int(val)
        if name == 'no_bias':
            self.no_bias = int(val)
        if name == 'silent':
            self.silent = int(val)
        if name == 'temp_col_max':
            self.temp_col_max = int(val) << 18
        if name == 'conv_lowering':
            if val not in ('auto', 'native', 'im2col', 'split', 's2d'):
                raise ValueError(f'conv_lowering: unknown mode {val}')
            self.conv_lowering = val
        if name == 'micro_batch':
            if int(val) < 1:
                raise ValueError(f'micro_batch: must be >= 1, got {val}')
            self.micro_batch = int(val)

    def rand_init_weight(self, rng: jax.Array, shape: Tuple[int, ...],
                         in_num: int, out_num: int,
                         dtype=jnp.float32) -> jax.Array:
        """Weight init matching ``RandInitWeight`` (param.h:113-138):
        gaussian(0, init_sigma) / xavier-uniform sqrt(3/(in+out)) /
        kaiming gaussian sqrt(2/fan)."""
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(rng, shape, dtype)
        if self.random_type == 1:
            a = math.sqrt(3.0 / (in_num + out_num))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(rng, shape, dtype, minval=-a, maxval=a)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = math.sqrt(2.0 / self.num_hidden)
            else:
                sigma = math.sqrt(
                    2.0 / (self.num_channel * self.kernel_width * self.kernel_height))
            return sigma * jax.random.normal(rng, shape, dtype)
        raise ValueError(f'unsupported random_type {self.random_type}')


class NodeSpec:
    """Logical per-instance shape of a node: ``(c, y, x)``.

    Mirrors the reference node shape contract (``layer/layer.h:31-71``):
    matrices are ``(1, 1, len)`` and stored as 2-D ``(batch, len)`` arrays;
    images are stored NHWC as ``(batch, y, x, c)``.
    """

    __slots__ = ('c', 'y', 'x')

    def __init__(self, c: int, y: int, x: int):
        self.c, self.y, self.x = int(c), int(y), int(x)

    @property
    def is_mat(self) -> bool:
        return self.c == 1 and self.y == 1

    @property
    def flat_size(self) -> int:
        return self.c * self.y * self.x

    def batch_shape(self, batch: int) -> Tuple[int, ...]:
        if self.is_mat:
            return (batch, self.x)
        return (batch, self.y, self.x, self.c)

    def __repr__(self):
        return f'NodeSpec(c={self.c}, y={self.y}, x={self.x})'

    def __eq__(self, other):
        return (self.c, self.y, self.x) == (other.c, other.y, other.x)


def as_mat(x: jax.Array) -> jax.Array:
    """FlatTo2D view: collapse all non-batch dims (``layer.h:63-66``).

    4-D nodes flatten in the reference's NCHW element order so downstream
    fully-connected weights keep the same column meaning.
    """
    if x.ndim == 2:
        return x
    if x.ndim == 4:
        b = x.shape[0]
        return jnp.transpose(x, (0, 3, 1, 2)).reshape(b, -1)
    return x.reshape(x.shape[0], -1)


@dataclasses.dataclass
class ForwardContext:
    """Per-apply context threaded through layer forwards."""

    is_train: bool
    rng: Optional[jax.Array] = None          # base key; fold per layer index
    layer_index: int = -1
    round: int = 0                           # training round (insanity anneal)
    max_round: int = 1
    # activation dtype for the MXU path (bfloat16 for mixed precision);
    # params and loss stay float32, matmuls accumulate in float32
    compute_dtype: object = jnp.float32
    # device count of the mesh this trace runs under: auto-enabled Pallas
    # paths stand down when > 1 (an opaque pallas_call has no GSPMD
    # sharding rule, so the partitioner would gather the full sharded
    # activation around it)
    spmd_devices: int = 1

    def layer_rng(self) -> jax.Array:
        if self.rng is None:
            raise ValueError('layer requires rng but none was provided')
        return jax.random.fold_in(self.rng, self.layer_index)


Params = Dict[str, jax.Array]


class Layer:
    """Base class for all layers.

    Unlike the reference's stateful ``ILayer`` (mutating nodes in place,
    visitor-based weight access), layers here are parameter *descriptions*:
    ``init_params`` produces a dict pytree and ``forward`` is pure.  Field
    names ('wmat', 'bias', ...) match the reference visitor field names so
    tag-scoped hyperparameters (``wmat:lr``) and checkpoint blobs line up.
    """

    type_name: str = ''
    type_id: int = -1
    # fields that participate in weight decay / tag-scoped lr ('wmat'/'bias')
    param_fields: Sequence[str] = ()
    # sequence layers (layers/sequence.py): the layer's forward is
    # checkpointed in a training step; it returns step statistics beside
    # its outputs (``forward_with_stats``); its input is integer token ids
    recompute = False
    has_stats = False
    takes_token_ids = False

    def __init__(self, name: str = ''):
        self.name = name
        self.param = LayerParam()

    # --- configuration ----------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        self.param.set_param(name, val)

    # --- shape inference --------------------------------------------------
    def infer_shapes(self, in_specs: List[NodeSpec]) -> List[NodeSpec]:
        """Compute output specs; also records input geometry hyperparams
        (num_input_node / num_input_channel) like ``InitConnection``."""
        raise NotImplementedError

    # --- parameters -------------------------------------------------------
    def init_params(self, rng: jax.Array, in_specs: List[NodeSpec],
                    dtype=jnp.float32) -> Params:
        return {}

    # --- compute ----------------------------------------------------------
    def forward(self, params: Params, inputs: List[jax.Array],
                ctx: ForwardContext) -> List[jax.Array]:
        raise NotImplementedError

    # loss layers override; returns per-batch summed loss (pre-scaling)
    def loss(self, params: Params, inputs: List[jax.Array],
             labels: jax.Array, ctx: ForwardContext) -> jax.Array:
        raise NotImplementedError(f'{self.type_name} is not a loss layer')

    @property
    def is_loss(self) -> bool:
        return False

    def allow_sharing(self) -> bool:
        """Whether this layer can be referenced by ``share[tag]``."""
        return bool(self.param_fields)

    def __repr__(self):
        return f'{type(self).__name__}(name={self.name!r})'


LAYER_REGISTRY: Dict[int, type] = {}


def register_layer(cls):
    """Class decorator: register under its stable type id."""
    LAYER_REGISTRY[cls.type_id] = cls
    return cls


def create_layer(type_id: int, name: str = '') -> Layer:
    """Factory (``CreateLayer_``, layer_impl-inl.hpp:36-76)."""
    if type_id >= kPairTestGap:
        from .pairtest import PairTestLayer
        return PairTestLayer(type_id // kPairTestGap, type_id % kPairTestGap,
                             name=name)
    cls = LAYER_REGISTRY.get(type_id)
    if cls is None:
        raise ValueError(
            f'CreateLayer: unknown/unsupported layer type {type_id} '
            f'({layer_type_name(type_id)})')
    return cls(name=name)
