"""Functional neural-net graph executor.

The TPU-native replacement for the reference's per-device mutable replica
(``src/nnet/neural_net-inl.hpp:22-250``): where the reference allocates node
tensors and sweeps Forward/Backprop over connections in place, this builds a
**pure function** of ``(params, batch, labels, rng)`` that XLA compiles into
one fused program.  Backward comes from ``jax.grad`` of the summed loss —
per-layer hand-written gradients are unnecessary because every loss layer's
scalar is constructed so its autodiff gradient equals the reference's
hand-set one (see layers/loss.py).

Layout: activations are NHWC; the input node accepts NCHW host batches
(the reference/data-pipeline layout) and transposes once on device.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..layers import ForwardContext, NodeSpec, create_layer
from ..layers.base import kSharedLayer, Layer
from ..layers.common import SplitLayer
from ..layers.loss import LossLayerBase
from .net_config import NetConfig

Params = Dict[str, Dict[str, jax.Array]]


class LabelInfo:
    """Named label-field views over the raw label matrix
    (``layer/layer.h:77-121`` + slicing at ``nnet_impl-inl.hpp:271-285``)."""

    def __init__(self, label_mat, name_map: Dict[str, int],
                 ranges: List[tuple]):
        self._mat = label_mat
        self._name_map = name_map
        self._ranges = ranges

    def field(self, name: str):
        if name not in self._name_map:
            raise KeyError(f'unknown label target = {name}')
        a, b = self._ranges[self._name_map[name]]
        return self._mat[:, a:b]


class Net:
    """A compiled-graph view of a NetConfig."""

    def __init__(self, cfg: NetConfig):
        self.cfg = cfg
        self.layers: List[Layer] = []
        self.layer_primary: List[int] = []   # index of the params owner
        # instantiate layers; shared entries alias the primary layer object
        # (neural_net-inl.hpp:216-250)
        for i, info in enumerate(cfg.layers):
            if info.type == kSharedLayer:
                primary = cfg.layers[info.primary_layer_index]
                layer = self.layers[info.primary_layer_index]
                if not layer.allow_sharing():
                    raise ValueError(
                        f'layer {primary.name} does not allow sharing')
                self.layers.append(layer)
                self.layer_primary.append(info.primary_layer_index)
            else:
                self.layers.append(create_layer(info.type, name=info.name))
                self.layer_primary.append(i)
        # configure: global defaults first, then layer-scoped pairs
        # (neural_net-inl.hpp:252-264)
        for i, layer in enumerate(self.layers):
            if self.layer_primary[i] != i:
                continue
            for name, val in cfg.defcfg:
                layer.set_param(name, val)
            for name, val in cfg.layercfg[i]:
                layer.set_param(name, val)
        # split layers need their fan-out before shape inference
        for i, info in enumerate(cfg.layers):
            if isinstance(self.layers[i], SplitLayer):
                self.layers[i].set_num_outputs(len(info.nindex_out))
        self.layer_scopes = self._layer_scopes()
        self._infer_shapes()
        self._build_sibling_fusion()
        self._build_blockdiag_fusion()
        # sequence nets (layers/sequence.py): node 0 holds integer token ids
        # when every layer that reads it says so
        readers0 = [self.layers[i] for i, info in enumerate(cfg.layers)
                    if 0 in info.nindex_in]
        self.takes_token_ids = bool(readers0) and all(
            l.takes_token_ids for l in readers0)

    def _layer_scopes(self) -> List[str]:
        """One ``jax.named_scope`` name per conf layer, from the conf
        alone: index, type and the conf's layer name where it gives one
        (``l03_lrn``, ``l00_conv_c1``).  Unique by the index, the same in
        every run, and free of ``/ ( )``, which the profiler's ``op_name``
        paths (``jit(f)/jvp(l03_lrn)/...``) use as separators
        (utils/profiler.device_time_by_scope reads them back)."""
        width = max(2, len(str(len(self.cfg.layers) - 1)))
        return [re.sub(r'[^A-Za-z0-9_]', '_', '_'.join(
            [f'l{i:0{width}d}', self.layers[i].type_name]
            + ([info.name] if info.name else [])))
            for i, info in enumerate(self.cfg.layers)]

    # --- horizontal fusion ------------------------------------------------
    def _build_sibling_fusion(self) -> None:
        """Group sibling 1x1 convolutions for horizontally fused execution.

        Inception-style towers launch several small 1x1 convs off the same
        trunk node (``concat_layer-inl.hpp:55-78`` context); each is a
        skinny matmul whose output-channel count (16..96) underfills the
        128-wide MXU.  Executing one conv with the weights concatenated
        along the output axis and splitting the result is mathematically
        identical per output channel (each column's contraction is
        unchanged) and fills the systolic array.  Eligibility: ungrouped
        1x1, stride 1, no padding, single in/out, homogeneous bias-ness.
        Disable with ``fuse_siblings = 0``.
        """
        from ..layers.conv import ConvolutionLayer
        enabled = 1
        tp = 1
        for name, val in self.cfg.defcfg:
            if name == 'fuse_siblings':
                enabled = int(val)
            if name == 'tensor_parallel':
                tp = int(val)
        self._sibling_groups: Dict[int, List[int]] = {}
        if not enabled or tp > 1:
            # under tensor parallelism the member wmats are sharded on
            # exactly the axis fusion concatenates (mesh.py
            # P(None,None,None,'model')), and member widths don't align
            # to shard boundaries — fusing would force GSPMD to
            # all-gather what the col/row pairing keeps sharded
            return
        groups: Dict[tuple, List[int]] = {}
        for i, info in enumerate(self.cfg.layers):
            layer = self.layers[i]
            if not isinstance(layer, ConvolutionLayer):
                continue
            p = layer.param
            if (p.kernel_height, p.kernel_width, p.stride, p.pad_y,
                    p.pad_x, p.num_group) != (1, 1, 1, 0, 0, 1):
                continue
            if len(info.nindex_in) != 1 or len(info.nindex_out) != 1:
                continue
            groups.setdefault((info.nindex_in[0], p.no_bias), []).append(i)
        for (node, _), members in groups.items():
            if len(members) < 2:
                continue
            # the grouping is sound only if the input node keeps ONE value
            # across the group's span: the config language allows in-place
            # rewrites (layer[a->a] = ...), after which a later member
            # would legally read the REWRITTEN value while the fused conv
            # ran on the old one.  Reject the group if any layer within
            # [first, last] member positions writes the node.
            lo, hi = members[0], members[-1]
            rewritten = any(
                node in self.cfg.layers[w].nindex_out
                for w in range(lo, hi + 1))
            if rewritten:
                continue
            for m in members:
                self._sibling_groups[m] = members

    def _fused_sibling_outputs(self, params: Params, x, members: List[int]):
        """One 1x1 conv over the concatenated weights, split back into the
        member layers' outputs (same order)."""
        widths = [self.layers[m].param.num_channel for m in members]
        w = jnp.concatenate(
            [self._layer_params(params, m)['wmat'] for m in members],
            axis=3).astype(x.dtype)
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding=((0, 0), (0, 0)),
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        if self.layers[members[0]].param.no_bias == 0:
            b = jnp.concatenate(
                [self._layer_params(params, m)['bias'] for m in members]
            ).astype(x.dtype)
            out = out + b
        out = out.astype(x.dtype)
        splits = np.cumsum(widths)[:-1]
        return jnp.split(out, splits, axis=-1)

    # --- cross-input block-diagonal fusion --------------------------------
    def _build_blockdiag_fusion(self) -> None:
        """Fuse convolutions that read DIFFERENT inputs into one wide conv
        with a block-diagonal weight.

        Sibling fusion (above) only reaches convs sharing a trunk node; the
        remaining narrow convs in an inception module (the 3x3/5x5 tower
        convs, the pool projection) each consume their own reduce output,
        so their 16..128-wide outputs underfill the 128-lane MXU per pass
        no matter the batch (BASELINE.md "Why GoogLeNet sits at MFU 0.15").
        Concatenating the inputs channel-wise and embedding each member's
        weight as a diagonal block (smaller kernels zero-padded spatially
        into the group's max kernel, with input padding grown to match)
        computes exactly the same outputs while filling the array — at the
        cost of the zero blocks' redundant FLOPs, which is why this is
        OFF by default and flipped per measured receipt only.

        ``fuse_blockdiag = in3a_3x3+in3a_5x5;in3b_3x3+in3b_5x5`` names the
        groups explicitly (layer names, ``+`` within a group, ``;`` between
        groups).  Members must be ungrouped single-in/single-out convs with
        equal stride and bias-ness, equal input spatial dims, and a shared
        ``2*pad - kernel`` extent on each axis (which makes the padded
        output grids coincide).  Because config order may interleave a
        member's producers between the members (the builder emits reduce
        convs lazily), the execution order is re-scheduled to make group
        members contiguous; a node-version simulation validates that the
        reorder preserves the reference's sequential in-place semantics
        (``layer[a->a]`` rewrites) exactly, and raises otherwise.
        """
        from ..layers.conv import ConvolutionLayer
        spec_str, tp = '0', 1
        for name, val in self.cfg.defcfg:
            if name == 'fuse_blockdiag':
                spec_str = str(val).strip()
            if name == 'tensor_parallel':
                tp = int(val)
        self._blockdiag_groups: Dict[int, List[int]] = {}
        self._exec_order: List[int] = list(range(len(self.cfg.layers)))
        if spec_str in ('', '0'):
            return
        if tp > 1:
            # unlike sibling fusion (default-on, silently skipped), this
            # spec is explicit opt-in: refusing loudly keeps a "fused"
            # receipt from actually measuring the unfused plan
            raise ValueError(
                'fuse_blockdiag is incompatible with tensor_parallel>1 '
                '(member wmats are sharded on the output-channel axis the '
                'fusion concatenates); remove one of the two settings')
        reads, writes = self._node_version_maps()
        if spec_str == 'auto' or spec_str.startswith('auto:'):
            # auto:<maxwidth> — one candidate group per concat layer: the
            # member convs feeding it whose output width <= maxwidth
            # (the MXU-underfilling towers).  Groups that fail any
            # eligibility/schedule check are skipped, not fatal — auto
            # must hold on arbitrary nets.  Default maxwidth 96: <128
            # lanes AND at/below the narrowest width class the GoogLeNet
            # breakdown receipt can indict.
            if ':' in spec_str:
                try:
                    maxw = int(spec_str.split(':', 1)[1])
                except ValueError:
                    raise ValueError(
                        f'fuse_blockdiag: bad auto maxwidth in '
                        f'{spec_str!r} — use auto or auto:<int>') from None
            else:
                maxw = 96
            for members in self._auto_blockdiag_candidates(
                    ConvolutionLayer, writes, maxw):
                self._register_blockdiag_group(
                    members, ConvolutionLayer, reads, writes, strict=False)
        else:
            byname: Dict[str, int] = {}
            for i, info in enumerate(self.cfg.layers):
                if info.name and info.name not in byname:
                    byname[info.name] = i
            for gspec in spec_str.split(';'):
                names = [s.strip() for s in gspec.split('+') if s.strip()]
                if len(names) < 2:
                    raise ValueError(
                        f'fuse_blockdiag: group {gspec!r} needs >=2 '
                        f'layer names')
                members = []
                for nm in names:
                    if nm not in byname:
                        raise ValueError(
                            f'fuse_blockdiag: no layer named {nm!r}')
                    members.append(byname[nm])
                self._register_blockdiag_group(
                    sorted(members), ConvolutionLayer, reads, writes,
                    strict=True)
        self._verify_blockdiag_final(reads, writes)
        # a fusion receipt must be able to tell "measured" from "never
        # engaged": with the knob set, say what actually formed (lands in
        # the committed bench .log next to the receipt JSON)
        groups = self._blockdiag_group_set()
        print(f'fuse_blockdiag={spec_str}: {len(groups)} group(s) formed'
              + ('' if groups else ' — NO fusion engaged'),
              file=sys.stderr)

    def _blockdiag_group_set(self):
        """The distinct groups (each member maps to its whole group)."""
        return {tuple(g) for g in self._blockdiag_groups.values()}

    def _register_blockdiag_group(self, members, conv_cls, reads, writes,
                                  strict: bool) -> None:
        """Validate + schedule one group; ``strict`` raises on failure
        (explicit specs fail loud), else the group is skipped."""
        try:
            self._check_blockdiag_group(members, conv_cls, reads, writes)
            new_order = self._reorder_contiguous(
                self._exec_order, members, reads, writes)
            for m in members:
                if m in self._blockdiag_groups:
                    raise ValueError(
                        f'fuse_blockdiag: layer '
                        f'{self.cfg.layers[m].name!r} appears in two '
                        f'groups')
        except ValueError:
            if strict:
                raise
            return
        self._exec_order = new_order
        for m in members:
            self._blockdiag_groups[m] = members

    def _auto_blockdiag_candidates(self, conv_cls, writes, maxw: int):
        """One candidate group per concat layer: the convs producing its
        input nodes (through in-place activations) with output width
        <= maxw, not already sibling-fused."""
        producer: Dict[int, int] = {}     # node -> conv layer writing v1
        for i, layer in enumerate(self.layers):
            if isinstance(layer, conv_cls):
                for (n, v) in writes[i]:
                    if v == 1:
                        producer[n] = i
        for i, info in enumerate(self.cfg.layers):
            if self.layers[i].type_name not in ('concat', 'ch_concat'):
                continue
            members = []
            for n in info.nindex_in:
                m = producer.get(n)
                if (m is None or m in self._sibling_groups
                        or m in self._blockdiag_groups):
                    continue
                if self.layers[m].param.num_channel <= maxw:
                    members.append(m)
            if len(members) >= 2:
                yield sorted(members)

    def _node_version_maps(self):
        """Per-layer (node, version) read/write sets under the sequential
        config-order semantics; versions count in-place rewrites."""
        ver: Dict[int, int] = {}
        reads, writes = [], []
        for info in self.cfg.layers:
            reads.append(frozenset((n, ver.get(n, 0))
                                   for n in info.nindex_in))
            w = set()
            for n in info.nindex_out:
                ver[n] = ver.get(n, 0) + 1
                w.add((n, ver[n]))
            writes.append(frozenset(w))
        return reads, writes

    def _check_blockdiag_group(self, members, conv_cls, reads, writes):
        layers = [self.layers[m] for m in members]
        infos = [self.cfg.layers[m] for m in members]
        for m, l, info in zip(members, layers, infos):
            if not isinstance(l, conv_cls):
                raise ValueError(
                    f'fuse_blockdiag: layer {info.name!r} is not a conv')
            if (l.param.num_group != 1 or len(info.nindex_in) != 1
                    or len(info.nindex_out) != 1):
                raise ValueError(
                    f'fuse_blockdiag: {info.name!r} must be an ungrouped '
                    f'1-in/1-out conv')
            if m in self._sibling_groups:
                # explicit blockdiag spec wins: dissolve the sibling group
                for s in self._sibling_groups.pop(m):
                    self._sibling_groups.pop(s, None)
        p0 = layers[0].param
        for l, info in zip(layers[1:], infos[1:]):
            p = l.param
            if p.stride != p0.stride or p.no_bias != p0.no_bias:
                raise ValueError(
                    f'fuse_blockdiag: {info.name!r} stride/bias mismatch')
            if (2 * p.pad_y - p.kernel_height
                    != 2 * p0.pad_y - p0.kernel_height
                    or 2 * p.pad_x - p.kernel_width
                    != 2 * p0.pad_x - p0.kernel_width):
                raise ValueError(
                    f'fuse_blockdiag: {info.name!r} output grid mismatch '
                    f'(2*pad-kernel must match across the group)')
        s0 = self.node_specs[infos[0].nindex_in[0]]
        for info in infos[1:]:
            s = self.node_specs[info.nindex_in[0]]
            if (s.y, s.x) != (s0.y, s0.x):
                raise ValueError(
                    f'fuse_blockdiag: {info.name!r} input spatial mismatch')
        # chain fusion is semantically different (members run on the
        # group's shared pre-state): no member may feed another member
        member_writes = frozenset().union(*(writes[m] for m in members))
        for m, info in zip(members, infos):
            if reads[m] & member_writes:
                raise ValueError(
                    f'fuse_blockdiag: {info.name!r} consumes another '
                    f'member\'s output — chain fusion is not supported')

    def _verify_blockdiag_final(self, reads, writes) -> None:
        """Cross-group safety net: a LATER group's reorder re-schedules the
        whole order and could split an earlier group's members apart — and
        the per-layer version validator cannot see that, because the fused
        execution reads ALL member inputs at the first member's exec
        position (not each member's own).  Re-verify every group against
        the FINAL order: members contiguous, every input version produced
        before the group starts, and no rewriter of an input node runs
        before the group starts."""
        pos = {l: k for k, l in enumerate(self._exec_order)}
        for members in self._blockdiag_group_set():
            names = [self.cfg.layers[m].name for m in members]
            ps = sorted(pos[m] for m in members)
            if ps != list(range(ps[0], ps[-1] + 1)):
                raise ValueError(
                    f'fuse_blockdiag: groups {names} were torn apart by a '
                    'later group\'s reorder — no safe combined schedule; '
                    'reorder or split the group specs')
            start = ps[0]
            need = set().union(*(reads[m] for m in members))
            for l in range(len(self.cfg.layers)):
                for (n, v) in writes[l]:
                    for (n2, v2) in need:
                        if n != n2:
                            continue
                        if v <= v2 and pos[l] >= start:
                            raise ValueError(
                                f'fuse_blockdiag: group {names} input is '
                                'not produced before the fused execution '
                                'point in the combined schedule')
                        if v > v2 and pos[l] < start:
                            raise ValueError(
                                f'fuse_blockdiag: group {names} would read '
                                'a stale in-place-rewritten input in the '
                                'combined schedule')

    def _reorder_contiguous(self, order, members, reads, writes):
        """Move the non-member layers between the group's members out of
        the way (dependents after, independents before), then verify the
        new order replays the exact same node-version reads/writes as
        config order."""
        pos = {l: k for k, l in enumerate(order)}
        lo = min(pos[m] for m in members)
        hi = max(pos[m] for m in members)
        seg = [order[k] for k in range(lo, hi + 1) if order[k] not in members]
        # version-aware dependence closure: the members (plus anything
        # transitively forced after them) form a "moved-later" set; a
        # segment layer must follow it iff it (a) reads a version the set
        # writes, (b) rewrites a node past a version the set still reads,
        # or (c) writes a later version of a node the set writes.  Node
        # versions give the direction — a producer of a member's input
        # writes an EARLIER version and correctly stays in front.
        after: List[int] = []
        after_reads = set().union(*(reads[m] for m in members))
        after_writes = set().union(*(writes[m] for m in members))
        before: List[int] = []
        for l in seg:
            true_dep = bool(set(reads[l]) & after_writes)
            anti_dep = any(n1 == n2 and v2 < v1
                           for (n1, v1) in writes[l]
                           for (n2, v2) in after_reads)
            ww_dep = any(n1 == n2 and v2 < v1
                         for (n1, v1) in writes[l]
                         for (n2, v2) in after_writes)
            if true_dep or anti_dep or ww_dep:
                after.append(l)
                after_reads |= set(reads[l])
                after_writes |= set(writes[l])
            else:
                before.append(l)
        new_order = (order[:lo] + before + sorted(members, key=pos.get)
                     + after + order[hi + 1:])
        # full semantic validation: every layer must read/write the same
        # node versions as in config order
        ver: Dict[int, int] = {}
        for l in new_order:
            info = self.cfg.layers[l]
            got_r = frozenset((n, ver.get(n, 0)) for n in info.nindex_in)
            got_w = set()
            for n in info.nindex_out:
                ver[n] = ver.get(n, 0) + 1
                got_w.add((n, ver[n]))
            if got_r != reads[l] or frozenset(got_w) != writes[l]:
                raise ValueError(
                    'fuse_blockdiag: no safe schedule exists for group '
                    f'{[self.cfg.layers[m].name for m in members]} — layer '
                    f'{info.name or l!r} would observe different node '
                    'versions after the reorder')
        return new_order

    def _fused_blockdiag_outputs(self, params: Params, values,
                                 members: List[int]):
        """One conv over channel-concatenated inputs and a block-diagonal
        weight, split back into the member layers' outputs."""
        infos = [self.cfg.layers[m] for m in members]
        layers = [self.layers[m] for m in members]
        xs = [values[info.nindex_in[0]] for info in infos]
        x = jnp.concatenate(xs, axis=-1)
        kh = max(l.param.kernel_height for l in layers)
        kw = max(l.param.kernel_width for l in layers)
        p0 = layers[0].param
        ph = p0.pad_y + (kh - p0.kernel_height) // 2
        pw = p0.pad_x + (kw - p0.kernel_width) // 2
        cins = [v.shape[-1] for v in xs]
        couts = [l.param.num_channel for l in layers]
        w = jnp.zeros((kh, kw, sum(cins), sum(couts)), x.dtype)
        ci = co = 0
        for l, m, cin in zip(layers, members, cins):
            wm = self._layer_params(params, m)['wmat'].astype(x.dtype)
            oh = (kh - l.param.kernel_height) // 2
            ow = (kw - l.param.kernel_width) // 2
            w = w.at[oh:oh + l.param.kernel_height,
                     ow:ow + l.param.kernel_width,
                     ci:ci + cin, co:co + l.param.num_channel].set(wm)
            ci += cin
            co += l.param.num_channel
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=(p0.stride, p0.stride),
            padding=((ph, ph), (pw, pw)),
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        if p0.no_bias == 0:
            b = jnp.concatenate(
                [self._layer_params(params, m)['bias'] for m in members]
            ).astype(x.dtype)
            out = out + b
        out = out.astype(x.dtype)
        splits = np.cumsum(couts)[:-1]
        return jnp.split(out, splits, axis=-1)

    # --- shape inference --------------------------------------------------
    def _infer_shapes(self) -> None:
        cfg = self.cfg
        specs: List[Optional[NodeSpec]] = [None] * cfg.num_nodes
        c, y, x = cfg.input_shape
        if c * y * x == 0:
            raise ValueError('must set input_shape before building the net')
        specs[0] = NodeSpec(c, y, x)
        # extra data nodes in_1..in_k
        for k in range(cfg.extra_data_num):
            ec, ey, ex = cfg.extra_shape[3 * k:3 * k + 3]
            specs[1 + k] = NodeSpec(ec, ey, ex)
        for i, info in enumerate(cfg.layers):
            ins = []
            for j in info.nindex_in:
                if specs[j] is None:
                    raise ValueError(
                        f'layer {i} consumes node {j} before it is produced')
                ins.append(specs[j])
            outs = self.layers[i].infer_shapes(ins)
            if len(outs) != len(info.nindex_out):
                raise ValueError(
                    f'layer {i} ({self.layers[i].type_name}): produced '
                    f'{len(outs)} outputs, expected {len(info.nindex_out)}')
            for j, spec in zip(info.nindex_out, outs):
                if specs[j] is not None and j not in info.nindex_in:
                    if specs[j] != spec:
                        raise ValueError(f'node {j} shape conflict')
                specs[j] = spec
        self.node_specs = specs

    # --- params -----------------------------------------------------------
    def init_params(self, rng: jax.Array, dtype=jnp.float32) -> Params:
        params: Params = {}
        cfg = self.cfg
        for i, info in enumerate(cfg.layers):
            if self.layer_primary[i] != i:
                continue
            ins = [self.node_specs[j] for j in info.nindex_in]
            p = self.layers[i].init_params(jax.random.fold_in(rng, i), ins,
                                           dtype)
            if p:
                params[str(i)] = p
        return params

    def _layer_params(self, params: Params, i: int):
        return params.get(str(self.layer_primary[i]), {})

    # --- forward / loss ---------------------------------------------------
    def _input_to_device_layout(self, batch, compute_dtype=jnp.float32):
        """Host batches arrive NCHW (c,y,x per instance); convert to the
        on-device layout (NHWC images, flat matrices) and activation dtype.
        Integer (uint8 pixel) batches are welcome — shipping raw bytes and
        casting on device quarters host->device traffic.  Token ids (a
        net whose first layers take them) stay the integers they are: a
        compute-type cast would fold ids above 256 together."""
        if self.takes_token_ids:
            if not jnp.issubdtype(batch.dtype, jnp.integer):
                batch = batch.astype(jnp.int32)
            return batch.reshape(batch.shape[0], -1)
        batch = batch.astype(compute_dtype)
        if batch.ndim == 2:
            spec = self.node_specs[0]
            if not spec.is_mat:
                # a conv-shaped net fed flat vectors dies later inside a
                # dot_general with a useless shape message — name the
                # actual fix here (hit via iter=mnist, whose default
                # input_flat=1 flattens, matching the reference)
                raise ValueError(
                    f'input batch is flat ({batch.shape[1]}-vectors) but '
                    f'input_shape expects {spec.c}x{spec.y}x{spec.x} '
                    f'images — set input_flat=0 on the data iterator or '
                    f'use a flat input_shape')
            return batch
        if batch.ndim == 4:
            spec = self.node_specs[0]
            if spec.is_mat:
                return batch.reshape(batch.shape[0], -1)
            if batch.shape[1:] != (spec.c, spec.y, spec.x):
                # a conv-shaped net fed mislaid data (classic: iter=mnist
                # keeps its reference default input_flat=1 and emits
                # (n,1,1,784)) dies later inside a dot_general/conv with
                # a useless shape message — name the actual fix here
                raise ValueError(
                    f'input batch {batch.shape[1:]} does not match '
                    f'input_shape {spec.c},{spec.y},{spec.x} — for '
                    f'iter=mnist set input_flat=0 to keep images unflat')
            return jnp.transpose(batch, (0, 2, 3, 1))
        raise ValueError(f'bad input batch rank {batch.ndim}')

    def forward(self, params: Params, batch, ctx: ForwardContext,
                labels: Optional[LabelInfo] = None, loss_mask=None,
                extra_data=None, capture=None,
                identity_layers=frozenset(), stats=None):
        """Run the graph.  Returns (node_values, total_loss).

        ``node_values[j]`` holds every node's final value (post loss-layer
        transforms, like the reference's in-place nodes).  ``total_loss`` is
        the sum of loss-layer scalars (0.0 if the graph has none or labels
        were not supplied).  ``extra_data`` feeds nodes ``in_1..in_k`` when
        ``extra_data_num`` is configured (NCHW host layout, like the input).

        ``capture`` (conv+BN fold support, nnet/fold.py): a dict whose
        keys are layer indices — each listed layer's input list is
        stored under its key before the layer runs.  ``identity_layers``
        replaces the listed 1-in layers with a pass-through (how the
        fold pass retires a folded BN without rewriting the graph
        indices the params tree is keyed by).

        ``stats``: a dict that receives what layers count beside their
        outputs (``Layer.has_stats``), keyed ``<scope>/<name>``.
        """
        cfg = self.cfg
        values: List[Optional[jax.Array]] = [None] * cfg.num_nodes
        with jax.named_scope('input'):
            values[0] = self._input_to_device_layout(batch,
                                                     ctx.compute_dtype)
        if cfg.extra_data_num:
            if extra_data is None or len(extra_data) < cfg.extra_data_num:
                raise ValueError(
                    f'net requires {cfg.extra_data_num} extra_data inputs '
                    f'(batch.extra_data) but got '
                    f'{0 if extra_data is None else len(extra_data)}')
            for k in range(cfg.extra_data_num):
                ex = extra_data[k]
                spec = self.node_specs[1 + k]
                if ex.ndim == 4 and not spec.is_mat:
                    ex = jnp.transpose(ex, (0, 2, 3, 1))
                elif ex.ndim > 2 and spec.is_mat:
                    ex = ex.reshape(ex.shape[0], -1)
                values[1 + k] = ex
        total_loss = jnp.asarray(0.0, jnp.float32)
        fused: Dict[int, jax.Array] = {}
        fused_bd: Dict[int, jax.Array] = {}
        for i in self._exec_order:
            info = cfg.layers[i]
            layer = self.layers[i]
            lctx = ForwardContext(is_train=ctx.is_train, rng=ctx.rng,
                                  layer_index=i, round=ctx.round,
                                  max_round=ctx.max_round,
                                  compute_dtype=ctx.compute_dtype,
                                  spmd_devices=ctx.spmd_devices)
            lp = self._layer_params(params, i)
            ins = [values[j] for j in info.nindex_in]
            if capture is not None and i in capture:
                capture[i] = ins
            with jax.named_scope(self.layer_scopes[i]):
                if isinstance(layer, LossLayerBase) and labels is not None:
                    total_loss = total_loss + layer.loss(
                        lp, ins, labels.field(layer.target), lctx, loss_mask)
                if i in identity_layers:
                    outs = [ins[0]]
                elif i in self._sibling_groups:
                    if i not in fused:   # first member: run the fused conv
                        members = self._sibling_groups[i]
                        for m, v in zip(members, self._fused_sibling_outputs(
                                params, ins[0], members)):
                            fused[m] = v
                    outs = [fused[i]]
                elif i in self._blockdiag_groups:
                    if i not in fused_bd:   # first member in exec order
                        members = self._blockdiag_groups[i]
                        for m, v in zip(members, self._fused_blockdiag_outputs(
                                params, values, members)):
                            fused_bd[m] = v
                    outs = [fused_bd[i]]
                elif layer.has_stats or (layer.recompute and ctx.is_train):
                    outs = self._sequence_layer_outputs(layer, lp, ins, lctx,
                                                        stats, i)
                else:
                    outs = layer.forward(lp, ins, lctx)
            for j, v in zip(info.nindex_out, outs):
                values[j] = v
        return values, total_loss

    def _sequence_layer_outputs(self, layer, lp, ins, lctx, stats, i: int):
        """A sequence layer's outputs: checkpointed in a training step
        where the layer asks for it (its inputs are what is saved, its
        inside is recomputed in the backward pass), its statistics handed
        to ``stats`` under the layer's scope."""
        def run(lp, ins):
            if layer.has_stats:
                return layer.forward_with_stats(lp, ins, lctx)
            return layer.forward(lp, ins, lctx), {}
        if layer.recompute and lctx.is_train:
            run = jax.checkpoint(run)
        outs, found = run(lp, ins)
        if stats is not None:
            for name, value in found.items():
                stats[f'{self.layer_scopes[i]}/{name}'] = value
        return outs

    def node_index(self, name: str) -> int:
        """Resolve a node by name or ``top[-k]`` syntax
        (``nnet_impl-inl.hpp:200-223``)."""
        if name.startswith('top[-') and name.endswith(']'):
            k = int(name[5:-1])
            return self.cfg.layers[-k].nindex_out[-1] if k > 0 else -1
        if name in self.cfg.node_name_map:
            return self.cfg.node_name_map[name]
        raise ValueError(f'unknown node name {name}')

    def make_label_info(self, label_mat) -> LabelInfo:
        return LabelInfo(label_mat, self.cfg.label_name_map,
                         self.cfg.label_range)
