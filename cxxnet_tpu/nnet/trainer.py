"""The trainer: jitted train/eval steps over a device mesh.

TPU-native replacement for ``CXXNetThreadTrainer``
(``src/nnet/nnet_impl-inl.hpp:16-455``).  Where the reference runs one
pthread + model replica per GPU and syncs gradients through mshadow-ps
Push/PullReq, here a single jitted train step is partitioned over a
``jax.sharding.Mesh``: the batch is sharded along the ``data`` axis,
parameters are replicated, and XLA inserts the ICI all-reduce for the
gradients (the WFBP comm/compute overlap of ``async_updater-inl.hpp`` is
subsumed by XLA's latency-hiding scheduler).  The optimizer runs on-device
inside the same program — the TPU analogue of ``update_on_server``.

Reference semantics preserved:
* ``update_period`` — gradients accumulate across k minibatches; the
  optimizer applies on the k-th (``nnet_impl:149-150,181-184``),
* ``epoch_counter`` counts optimizer updates and drives LR schedules, and is
  saved in checkpoints,
* metrics: ``metric = error`` / ``metric[label,node] = logloss`` config
  forms; train metrics from forward outputs when ``eval_train=1``; eval
  excludes ``num_batch_padd`` padded instances,
* model file layout (``SaveModel``, nnet_impl:82-87): NetConfig +
  epoch_counter (int64) + length-prefixed blob of per-layer weights.
"""

from __future__ import annotations

import collections
import os
import re
import struct
from typing import BinaryIO, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..layers import ForwardContext
from ..obs import span
from ..obs.step_record import StepSeries
from ..parallel.mesh import (batch_sharding, build_mesh, param_shardings,
                             replicated_sharding)
from ..updater import (apply_updates, create_updater_hyper, init_opt_state)
from ..utils.backend import cpu_pinned, meet_backend
from ..utils.metric import MetricSet
from . import checkpoint
from .net import Net
from .net_config import NetConfig

ConfigEntry = Tuple[str, str]


def _apply_input_norm(data, norm):
    """Device-side input normalization for raw uint8 batches
    (``device_normalize=1``): the augment stage's ``(x - mean) * scale``
    (``iter_augment_proc-inl.hpp:199-231``) applied inside the jitted
    step.  ``norm`` is ``()`` (host already normalized — no-op) or a
    ``(mean, scale)`` pair of device arrays; the pytree structure keys
    the jit cache, so the two paths compile separately.  f32 math before
    the net's compute-dtype cast, same rounding order as the host path."""
    if not norm:
        return data
    mean, scale = norm
    return (data.astype(jnp.float32) - mean) * scale


def _over_layers(stats):
    """Per-layer step statistics (``<scope>/<name>``, ``Net.forward``)
    folded over the layers: the worst layer of a ``*max_over_mean`` (its
    largest) and of a ``*_min`` (its smallest), the mean of anything else."""
    fold = {'max_over_mean': jnp.max, '_min': jnp.min}
    by_name = {}
    for key, value in stats.items():
        by_name.setdefault(key.split('/', 1)[1], []).append(value)
    return {name: next((f for end, f in fold.items() if name.endswith(end)),
                       jnp.mean)(jnp.stack(v))
            for name, v in by_name.items()}


def parse_devices(val: str) -> List[int]:
    """Ordinals of ``dev = tpu:0-3`` / ``dev = gpu:0,2`` / ``dev = cpu``
    (``nnet_impl-inl.hpp:31-55``); they index ``jax.devices()``."""
    if ':' not in val:
        return []
    devs = val.split(':', 1)[1]
    m = re.match(r'^(\d+)-(\d+)$', devs)
    if m:
        return list(range(int(m.group(1)), int(m.group(2)) + 1))
    return [int(t) for t in devs.split(',') if t]


class DeviceConfigError(ValueError):
    """``dev=`` names a device kind or ordinal this process does not
    have."""


def select_devices(dev: str, all_devs) -> list:
    """The devices ``dev=`` asks for, out of ``all_devs``.

    The kind must be the backend the process runs on, and every ordinal
    must exist: a conf that says ``tpu`` never trains on the CPU without
    saying so, and ``tpu:0-3`` on one chip is an error, not a one-device
    mesh.  The one exemption is a process pinned to ``JAX_PLATFORMS=cpu``,
    which may run any ``dev=`` on the CPU devices (ordinals wrap), so the
    same confs drive the CPU correctness runs."""
    ordinals = parse_devices(dev)
    if cpu_pinned():
        ordinals = [i % len(all_devs) for i in ordinals]
    else:
        kind = dev.split(':', 1)[0].strip().lower()
        backend = all_devs[0].platform
        if kind and kind != backend:
            raise DeviceConfigError(
                f'dev = {dev}: asks for a {kind!r} device but this process '
                f'runs on the {backend!r} backend ({all_devs[0]}); fix '
                f'dev=, or pin JAX_PLATFORMS=cpu to run any dev= on the '
                f'CPU on purpose')
        bad = [i for i in ordinals if i >= len(all_devs)]
        if bad:
            raise DeviceConfigError(
                f'dev = {dev}: ordinal(s) {bad} out of range, the '
                f'{backend!r} backend has {len(all_devs)} device(s)')
    # de-dup, order kept (dev=tpu:0-3 wrapped onto one CPU device)
    return [all_devs[i] for i in dict.fromkeys(ordinals)] or [all_devs[0]]


class NetTrainer:
    """Config-driven trainer (INetTrainer surface, ``nnet/nnet.h:18-92``)."""

    def __init__(self, cfg: Optional[List[ConfigEntry]] = None):
        self.batch_size = 100
        self.update_period = 1
        self.sample_counter = 0
        self.eval_train = 1
        self.epoch_counter = 0
        self.seed = 0
        self.round = 0
        self.max_round = 1
        self.tensor_parallel = 1
        self.test_on_server = 0
        self.inference_only = 0    # skip optimizer-state allocation (serve)
        self.pred_buckets = None   # closed batch-size ladder for predict
        self.nan_action = 'none'
        self.nan_breaker = 0       # consecutive non-finite losses -> raise
        self.nan_streak = 0        # current consecutive non-finite count
        self._pending_loss = None  # (step, device loss) deferred one step
        self._loss_listeners: List = []   # add_loss_listener
        self._step_avals = None    # the first dispatched step's arguments
        # what the newest steps counted beside their loss (device scalars,
        # nothing fetched in the step loop): read by step_stats()
        self._step_stats = collections.deque(maxlen=512)
        # the newest dispatches' intervals and what each train.dispatch
        # span carries (obs/step_record.py)
        self._steps = StepSeries()
        self.compute_dtype = jnp.float32
        self.dev = ''              # the dev= value; '' = default device
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        self.eval_nodes: List[Tuple[str, int]] = []
        self.cfg: List[ConfigEntry] = []
        self.net_cfg = NetConfig()
        self.net: Optional[Net] = None
        self.params = None
        self.opt_state = None
        self.grad_acc = None
        self._mesh: Optional[Mesh] = None
        self._train_step_fn = None
        self._forward_fn = None
        self._pending_train_eval = None
        self._ones_mask_cache: Dict[int, object] = {}
        self._stack_jit = None     # device-side batch stacker (scanned loop)
        self._norm_dev = {}        # per-spec staged (mean, scale) consts
        if cfg:
            for name, val in cfg:
                self.set_param(name, val)

    # --- configuration ----------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        if name == 'dev':
            self.dev = val
        if name == 'batch_size':
            self.batch_size = int(val)
        if name == 'update_period':
            self.update_period = int(val)
        if name == 'eval_train':
            self.eval_train = int(val)
        if name == 'seed':
            self.seed = int(val)
        if name == 'max_round':
            self.max_round = int(val)
        if name == 'tensor_parallel':
            self.tensor_parallel = int(val)
        if name == 'test_on_server':
            self.test_on_server = int(val)
        if name == 'inference_only':
            # serving-path trainers hold params only: no optimizer moments
            # or grad accumulator are ever allocated (serve/engine.py)
            self.inference_only = int(val)
        if name == 'pred_buckets':
            # bound the predict compile cache: every predict/extract batch
            # is padded to the smallest bucket that fits (oversize splits
            # into max-bucket chunks), so ad-hoc wrapper/C-ABI callers with
            # arbitrary batch sizes trace at most len(buckets) programs
            # (doc/serving.md).  Empty/0 disables.
            from ..utils.bucketing import parse_buckets
            v = val.strip()
            self.pred_buckets = None if v in ('', '0', 'none') \
                else parse_buckets(v)
        if name == 'nan_action':
            if val not in ('none', 'skip', 'halt'):
                raise ValueError(
                    f'nan_action must be none|skip|halt, got {val}')
            self.nan_action = val
        if name == 'nan_breaker':
            self.nan_breaker = int(val)
        if name == 'use_pallas':
            # process-wide tri-state read by ops.pallas_kernels.pallas_mode:
            # 1 = force every pallas path, 0 = disable even the measured
            # winners, auto (default) = per-op profitability gates
            if val.strip().lower() == 'auto':
                os.environ.pop('CXXNET_PALLAS', None)
            else:
                os.environ['CXXNET_PALLAS'] = val
        if name == 'compute_type':
            table = {'float32': jnp.float32, 'bfloat16': jnp.bfloat16,
                     'float16': jnp.float16}
            if val not in table:
                raise ValueError(f'unknown compute_type {val}')
            self.compute_dtype = table[val]
        if name == 'metric' or name.startswith('metric['):
            # forms: metric / metric[field] / metric[field,node]; the node
            # part may itself contain brackets (top[-1]), so split on the
            # first comma and strip the outermost brackets only
            if name == 'metric':
                field, node = 'label', ''
            else:
                # strip exactly one outer bracket: the node part may itself
                # end in one (metric[extra,top[-1]])
                inner = name[len('metric['):]
                if inner.endswith(']'):
                    inner = inner[:-1]
                field, _, node = inner.partition(',')
            self.metric.add_metric(val, field)
            self.train_metric.add_metric(val, field)
            self.eval_nodes.append((node, 0 if node else -1))
        self.cfg.append((name, val))

    # --- construction -----------------------------------------------------
    def _build_mesh(self) -> Mesh:
        # in a multi-process jax.distributed world, jax.devices() spans
        # every host, but this trainer must pick devices THIS process
        # can feed (host data is device_put from here) — so both the
        # default and an explicit dev= list index the LOCAL device set
        # there (the per-worker view, matching the reference's
        # one-worker-per-host deployment); gradients cross hosts at the
        # elastic/ps layer, not through the mesh
        meet_backend()     # the CLI's first touch of the backend, spanned
        all_devs = (jax.local_devices() if jax.process_count() > 1
                    else jax.devices())
        devs = select_devices(self.dev, all_devs)
        return build_mesh(devs, tp=self.tensor_parallel)

    def _resolve_eval_nodes(self) -> List[int]:
        out = []
        last = self.net.cfg.layers[-1].nindex_out[-1]
        for name, _ in self.eval_nodes:
            out.append(last if name == '' else self.net.node_index(name))
        return out

    def init_net(self) -> None:
        """Build Net + updater hypers from the accumulated config."""
        self.net_cfg.configure(self.cfg)
        self.net = Net(self.net_cfg)
        self._mesh = self._build_mesh()
        self._eval_node_ids = self._resolve_eval_nodes()
        # per-weight tag-scoped hyperparameters
        self.hypers: Dict[str, Dict[str, object]] = {}
        for i, layer in enumerate(self.net.layers):
            if self.net.layer_primary[i] != i:
                continue
            fields = layer.param_fields
            if not fields:
                continue
            self.hypers[str(i)] = {
                tag: create_updater_hyper(self.net_cfg.updater_type, tag,
                                          self.net_cfg.defcfg,
                                          self.net_cfg.layercfg[i])
                for tag in fields}
        self._rng = jax.random.PRNGKey(self.seed)
        self._compile_steps()

    def init_model(self) -> None:
        with span('net.init_model', 'train') as sp:
            self.init_net()
            self.params = self.net.init_params(
                jax.random.fold_in(self._rng, 0xC0FFEE))
            self._post_params_init()
            leaves = jax.tree.leaves(self.params)
            sp.attrs.update(leaves=len(leaves),
                            bytes=sum(int(x.nbytes) for x in leaves))

    def _post_params_init(self) -> None:
        shardings = param_shardings(self.net, self.params, self._mesh)
        put = lambda tree: jax.tree.map(  # noqa: E731
            jax.device_put, tree, shardings)
        self.params = put(self.params)
        if self.inference_only:
            # serving holds params only — roughly 1/3 the device memory of
            # a momentum trainer, 1/4 of Adam; update() refuses below
            self.opt_state = None
            self.grad_acc = None
            return
        opt = init_opt_state(self.net_cfg.updater_type, self.params)
        self.opt_state = {k: put(v) for k, v in opt.items()}
        self.grad_acc = (self._zero_accumulator()
                         if self.update_period > 1 else None)
        self._record_state()

    def _zero_accumulator(self):
        return jax.tree.map(
            lambda p: jax.device_put(jnp.zeros_like(p), p.sharding),
            self.params)

    def _record_state(self) -> None:
        from ..obs import record_event
        record_event('train.state', 'train', **self.resident_state_bytes())

    def _require_empty(self, acc, doing: str) -> None:
        """Dropping an accumulator that still holds gradients would lose
        them without a word: raise instead."""
        if any(bool(jnp.any(g != 0)) for g in jax.tree.leaves(acc)):
            raise RuntimeError(
                f'{doing}: the gradient accumulator holds unapplied '
                f'gradients, which update_period = 1 has nowhere to keep; '
                f'change update_period on an accumulation boundary')

    def _sync_accumulator(self, period: int) -> None:
        """Make ``grad_acc`` what a step of ``update_period = period``
        carries (``update_period`` may be set at any time).  Only a period
        above 1 has anything to carry between steps: at period 1
        ``grad_acc`` is ``None``, an empty pytree, so the step programs
        keep their positional signature and carry, read and zero-fill
        nothing of a parameter's size but the parameter and its optimizer
        state.  Allocated at the first step after the period rose above 1,
        dropped at the first after it fell to 1; the jitted step retraces
        on the new pytree."""
        want = period > 1
        if want == (self.grad_acc is not None):
            return
        if want:
            self.grad_acc = self._zero_accumulator()
        else:
            self._require_empty(
                self.grad_acc, f'update_period fell to 1 at step '
                f'{self.sample_counter}')
            self.grad_acc = None
        self._record_state()

    def resident_state_bytes(self) -> Dict[str, int]:
        """Logical bytes of what the trainer keeps on the device between
        steps (a replicated leaf counts once): the ``train.state`` hub
        event's three fields."""
        size = lambda tree: sum(  # noqa: E731
            int(x.nbytes) for x in jax.tree.leaves(tree))
        return {'param_bytes': size(self.params),
                'opt_state_bytes': size(self.opt_state),
                'accumulator_bytes': size(self.grad_acc)}

    def _norm_args(self, batch):
        """Device constants for a deferred-normalization batch: ``()`` when
        none needed (host-normalized float32, or raw uint8 bench data with
        no spec).  Keyed on the spec alone — raw data is usually uint8 but
        an active affine warp yields raw float32, which still needs the
        deferred (x-mean)*scale.  Built once — the spec is chain-constant."""
        spec = getattr(batch, 'norm_spec', None)
        if spec is None:
            return ()
        cached = self._norm_dev.get(id(spec))
        if cached is not None and cached[0] is spec:
            self._norm_dev[id(spec)] = self._norm_dev.pop(id(spec))  # LRU
            return cached[1]
        mean = spec.resolved_mean()
        sh = replicated_sharding(self._mesh)
        consts = (jax.device_put(jnp.asarray(mean), sh),
                  jax.device_put(jnp.float32(spec.scale), sh))
        # keyed per spec instance (train and eval chains may normalize
        # differently); the spec ref pins the id against reuse.  Bounded:
        # a trainer cycling many iterators must not pin every spec's
        # device consts for its lifetime
        if len(self._norm_dev) >= 8:
            self._norm_dev.pop(next(iter(self._norm_dev)))
        self._norm_dev[id(spec)] = (spec, consts)
        return consts

    def _shard_batch(self, data: np.ndarray, cast: bool = True):
        data = np.asarray(data)
        if data.dtype == np.float64:
            data = data.astype(np.float32)
        elif (cast and data.dtype == np.float32
              and self.compute_dtype == jnp.bfloat16):
            # ship activations at compute precision (host-side cast via
            # ml_dtypes): halves H2D traffic
            import ml_dtypes
            data = data.astype(ml_dtypes.bfloat16)
        return jax.device_put(jnp.asarray(data), batch_sharding(self._mesh))

    # --- jitted steps -----------------------------------------------------
    def _make_loss_fn(self):
        net = self.net
        eval_ids = self._eval_node_ids
        compute_dtype = self.compute_dtype
        max_round = self.max_round
        spmd = self._mesh.devices.size

        def loss_fn(params, data, label, extra, mask, rng, rnd, norm=()):
            data = _apply_input_norm(data, norm)
            ctx = ForwardContext(is_train=True, rng=rng, round=rnd,
                                 max_round=max_round,
                                 compute_dtype=compute_dtype,
                                 spmd_devices=spmd)
            stats = {}
            values, loss = net.forward(params, data, ctx,
                                       labels=net.make_label_info(label),
                                       loss_mask=mask, extra_data=extra,
                                       stats=stats)
            return loss, ([values[i] for i in eval_ids], _over_layers(stats))

        return loss_fn

    def _claim_programs(self) -> None:
        """Claim this trainer's ledger program names (obs/programs.py):
        every compiled executable registers its compile wall-ms + HLO
        cost/memory into the process-wide ProgramLedger, served on
        ``/programs`` and read back by :meth:`train_step_flops`."""
        from ..obs.programs import get_ledger
        led = get_ledger()
        self._prog_step = led.program('train.step')
        self._prog_forward = led.program('train.forward')
        self._prog_multi = led.program('train.multi_step')
        self._prog_multi_fwd = led.program('train.multi_forward')
        self._prog_grad = None        # claimed on first compile_grad_step
        self._prog_apply = None       # claimed on first compile_apply_grad

    def _compile_steps(self) -> None:
        updater_type = self.net_cfg.updater_type
        hypers = self.hypers
        loss_fn = self._make_loss_fn()
        self._claim_programs()

        nan_skip = self.nan_action == 'skip'

        def train_step(params, opt_state, grad_acc, data, label, extra, mask,
                       rng, epoch, rnd, do_update, norm=()):
            (loss, (evals, stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, data, label, extra, mask,
                                       rng, rnd, norm)
            # the scopes below and Net.forward's one per conf layer are
            # what utils/profiler.device_time_by_scope reads back
            if nan_skip:
                # failure detection beyond the reference's NaN-zeroing clip
                # (sgd_updater-inl.hpp:15-22): a non-finite loss — or a
                # finite loss whose backward overflowed (0*inf etc.) —
                # poisons the weights; drop this batch's contribution
                with jax.named_scope('nan_gate'):
                    ok = jnp.isfinite(loss)
                    for g in jax.tree.leaves(grads):
                        ok &= jnp.all(jnp.isfinite(g))
                    grads = jax.tree.map(
                        lambda g: jnp.where(ok, g, jnp.zeros_like(g)),
                        grads)
            if grad_acc is None:
                # update_period = 1 (_sync_accumulator): every step
                # applies, so the update reads the gradients themselves
                # and no float32 copy of the parameters is carried, added
                # into and zero-filled
                if not do_update:
                    raise ValueError(
                        'a step that does not apply needs an accumulator')
                with jax.named_scope('update'):
                    params, opt_state = apply_updates(
                        updater_type, hypers, params, grads, opt_state,
                        epoch)
                return params, opt_state, None, loss, evals, stats
            with jax.named_scope('grad_acc'):
                grad_acc = jax.tree.map(jnp.add, grad_acc, grads)
            if do_update:
                with jax.named_scope('update'):
                    params, opt_state = apply_updates(
                        updater_type, hypers, params, grad_acc, opt_state,
                        epoch)
                    grad_acc = jax.tree.map(jnp.zeros_like, grad_acc)
            return params, opt_state, grad_acc, loss, evals, stats

        net = self.net
        compute_dtype = self.compute_dtype
        max_round = self.max_round

        spmd = self._mesh.devices.size

        def forward_step(params, data, extra, rnd, nodes, norm=()):
            data = _apply_input_norm(data, norm)
            ctx = ForwardContext(is_train=False, rng=None, round=rnd,
                                 max_round=max_round,
                                 compute_dtype=compute_dtype,
                                 spmd_devices=spmd)
            values, _ = net.forward(params, data, ctx, extra_data=extra)
            # the nodes asked for and no other: what nobody reads is
            # never computed (every node of an 8k-token sequence would not
            # fit beside the optimizer state)
            return [values[i] for i in nodes]

        # ledger-routed jit (obs/programs.py): the plain jax.jit C++
        # dispatch, plus a /programs row per compiled signature
        self._train_step_fn = self._prog_step.jit(
            train_step, static_argnames=('do_update',),
            donate_argnums=(0, 1, 2))
        self._forward_fn = self._prog_forward.jit(
            forward_step, static_argnames=('nodes',))
        self._stack_jit = None     # mesh may have changed: rebuild lazily

    def compile_multi_step(self, n_steps: int, train_eval: bool = False):
        """Jitted ``n_steps``-training-step function: ONE dispatch runs the
        whole loop on device via ``lax.scan`` over the (params, opt_state,
        grad_acc) carry, cycling round-robin through a leading-axis stack
        of pre-staged batches.

        Exists because every dispatch costs host time that a scanned
        loop pays once per K steps (``steps_per_dispatch``), and because
        a scanned inner loop is the natural production shape when the
        input pipeline pre-stages batch stacks; bench.py also times
        through it (K-vs-1 quotient).
        Counterpart of the reference's tight in-process hot loop
        (``nnet_impl-inl.hpp:141-185``), which never pays a per-step
        dispatch boundary either.

        Composes with the production constraints the per-step path
        carries (the ExecutionPlan contract, doc/trainer.md):

        * ``update_period = P > 1`` — the gradient accumulator rides the
          scan carry; step ``t`` adds its grads and the optimizer applies
          (and the epoch counter advances) only when
          ``(sc0 + t + 1) % P == 0`` — the EXACT per-step cadence, so
          windows need not align with accumulation boundaries (a partial
          accumulation carries across dispatches through the trainer's
          live ``grad_acc``).  At ``update_period = 1`` there is no
          accumulator: ``grad_acc`` is ``None`` in and out, and every step
          applies its own gradients.
        * ``train_eval=True`` — each step's eval-node outputs ride the
          scan's stacked ys, so ``eval_train=1`` train metrics cost ONE
          host readback per dispatch instead of one per step
          (:meth:`update_staged_window` defers it one dispatch, mirroring
          the per-step deferred readback).

        Returns ``fn(params, opt_state, grad_acc, data_stack, label_stack,
        base_rng, epoch0, sc0, mask_stack, rnd) -> (params, opt_state,
        grad_acc, losses, evals)`` with ``fn.n_steps`` / ``fn.train_eval``
        attached; drive it through :meth:`update_n_on_device` to keep
        trainer counters coherent (round-dependent layers and tail-batch
        masks follow the same semantics as the per-step :meth:`update`
        path: ``rnd`` is traced, ``mask_stack`` rides the batch stack).

        Step ``t`` derives its dropout key as ``fold_in(base_rng,
        1 + (sc0 + t) * 131 + rnd)`` — the EXACT key the per-step
        :meth:`update_staged` path computes at sample counter ``sc0+t``,
        so a K-step dispatch is bitwise-identical to K per-step
        dispatches even for stochastic nets; ``losses`` is the full
        ``(n_steps,)`` per-step loss vector so the divergence gate sees
        every step, not just the last.
        """
        loss_fn = self._make_loss_fn()
        updater_type = self.net_cfg.updater_type
        hypers = self.hypers
        nan_skip = self.nan_action == 'skip'
        period = max(1, int(self.update_period))

        def multi_step(params, opt_state, grad_acc, data_stack, label_stack,
                       base_rng, epoch0, sc0, mask_stack, rnd, norm=()):
            nstack = data_stack.shape[0]

            def body(carry, t):
                params, opt_state, grad_acc, epoch = carry
                data = jax.lax.dynamic_index_in_dim(
                    data_stack, t % nstack, keepdims=False)
                label = jax.lax.dynamic_index_in_dim(
                    label_stack, t % nstack, keepdims=False)
                mask = jax.lax.dynamic_index_in_dim(
                    mask_stack, t % nstack, keepdims=False)
                rng = jax.random.fold_in(base_rng, 1 + (sc0 + t) * 131 + rnd)
                (loss, (evals, _)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, data, label, (), mask,
                                           rng, rnd, norm)
                if nan_skip:
                    with jax.named_scope('nan_gate'):
                        ok = jnp.isfinite(loss)
                        for g in jax.tree.leaves(grads):
                            ok &= jnp.all(jnp.isfinite(g))
                        grads = jax.tree.map(
                            lambda g: jnp.where(ok, g, jnp.zeros_like(g)),
                            grads)
                ys = (loss, tuple(evals) if train_eval else ())
                if grad_acc is None:
                    # update_period = 1, as the per-step program: the
                    # update reads the gradients, nothing rides the carry
                    # beside the parameters and the optimizer state
                    if period != 1:
                        raise ValueError(
                            f'a window compiled for update_period = '
                            f'{period} needs an accumulator')
                    with jax.named_scope('update'):
                        params, opt_state = apply_updates(
                            updater_type, hypers, params, grads, opt_state,
                            epoch)
                    return (params, opt_state, None, epoch + 1), ys
                # accumulate-then-apply, exactly as the per-step path
                with jax.named_scope('grad_acc'):
                    grad_acc = jax.tree.map(jnp.add, grad_acc, grads)

                def _apply(args):
                    p, o, g, e = args
                    p, o = apply_updates(updater_type, hypers, p, g, o, e)
                    return p, o, jax.tree.map(jnp.zeros_like, g), e + 1

                with jax.named_scope('update'):
                    params, opt_state, grad_acc, epoch = jax.lax.cond(
                        (sc0 + t + 1) % period == 0, _apply,
                        lambda args: args,
                        (params, opt_state, grad_acc, epoch))
                return (params, opt_state, grad_acc, epoch), ys

            (params, opt_state, grad_acc, _), (losses, evals) = jax.lax.scan(
                body, (params, opt_state, grad_acc, epoch0),
                jnp.arange(n_steps))
            return params, opt_state, grad_acc, losses, evals

        # one ledger entry per (K, train_eval) window shape.  steps=1,
        # NOT n_steps: the window is a lax.scan and XLA cost analysis
        # counts a While body ONCE, so the reported flops already ARE
        # one step's — dividing by K would under-report MFU K-fold
        wrapped = self._prog_multi.jit(
            multi_step, donate_argnums=(0, 1, 2),
            key=f'k{n_steps}{"e" if train_eval else ""}')

        def multi_fn(params, opt_state, grad_acc, data_stack, label_stack,
                     base_rng, epoch0, sc0, mask_stack, rnd, norm=()):
            return wrapped(params, opt_state, grad_acc, data_stack,
                           label_stack, base_rng, epoch0, sc0,
                           mask_stack, rnd, norm)

        multi_fn.n_steps = n_steps
        multi_fn.train_eval = train_eval
        multi_fn.update_period = period
        return multi_fn

    def compile_multi_forward(self, n_steps: int):
        """Jitted ``n_steps``-forward-only function (the pred/extract/
        evaluate compute path — ``is_train=False``, no grads, no
        optimizer): ONE dispatch scans over a pre-staged batch stack and
        returns a f32 checksum of the top node, whose fetch is the
        completion barrier.  Same rationale as :meth:`compile_multi_step`;
        used by ``bench.py eval_alexnet`` to time eval throughput
        at net level (the fc8-class Pallas forward gate —
        ``ops.pallas_kernels.fullc_use_pallas`` — only ever engages on
        this path)."""
        net = self.net
        compute_dtype = self.compute_dtype
        max_round = self.max_round
        spmd = self._mesh.devices.size
        top = net.cfg.layers[-1].nindex_out[-1]

        def multi_fwd(params, data_stack, rnd, norm=()):
            nstack = data_stack.shape[0]

            def body(acc, t):
                data = jax.lax.dynamic_index_in_dim(
                    data_stack, t % nstack, keepdims=False)
                data = _apply_input_norm(data, norm)
                ctx = ForwardContext(is_train=False, rng=None, round=rnd,
                                     max_round=max_round,
                                     compute_dtype=compute_dtype,
                                     spmd_devices=spmd)
                values, _ = net.forward(params, data, ctx)
                return acc + jnp.sum(values[top].astype(jnp.float32)), None

            acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(n_steps))
            return acc

        # steps=1 for the same reason as compile_multi_step: the scan
        # body is counted once by XLA cost analysis
        wrapped = self._prog_multi_fwd.jit(multi_fwd, key=f'k{n_steps}')

        def fwd_fn(params, data_stack, rnd=0, norm=()):
            return wrapped(params, data_stack, rnd, norm)

        fwd_fn.n_steps = n_steps
        return fwd_fn

    def compile_grad_step(self):
        """Jitted ``(params, data, label, extra, mask, rng, rnd, norm)
        -> (loss, grads)``: the forward/backward of ``train_step``
        WITHOUT the optimizer apply or accumulator — the elastic
        multi-host runtime (``parallel/elastic.py``) computes one
        gradient contribution per micro-shard of the global batch,
        exchanges them across hosts, and applies the fixed-order
        combination through :meth:`compile_apply_grad`.  Nothing is
        donated: params are reused across every shard of a step."""
        loss_fn = self._make_loss_fn()

        def grad_step(params, data, label, extra, mask, rng, rnd,
                      norm=()):
            (loss, _aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, data, label, extra, mask,
                                       rng, rnd, norm)
            return loss, grads

        if self._prog_grad is None:
            from ..obs.programs import get_ledger
            self._prog_grad = get_ledger().program('train.grad_step')
        return self._prog_grad.jit(grad_step)

    def compile_apply_grad(self):
        """Jitted ``(params, opt_state, grads, epoch) -> (params,
        opt_state)``: ONE optimizer step over an already-combined
        gradient tree.  The elastic runtime feeds it the cross-host
        shard sum — every host applies the identical bytes, so the
        replicated params stay bitwise equal with no broadcast."""
        updater_type = self.net_cfg.updater_type
        hypers = self.hypers

        def apply_grad(params, opt_state, grads, epoch):
            params, opt_state = apply_updates(
                updater_type, hypers, params, grads, opt_state, epoch)
            return params, opt_state

        if self._prog_apply is None:
            from ..obs.programs import get_ledger
            self._prog_apply = get_ledger().program('train.apply_grad')
        return self._prog_apply.jit(apply_grad, donate_argnums=(0, 1))

    def shard_batch_stack(self, stack: np.ndarray, cast: bool = True):
        """Stage a (nstack, batch, ...) stack of batches on device with the
        batch axis (axis 1) sharded over the mesh's data axis."""
        stack = np.asarray(stack)
        if stack.dtype == np.float64:
            stack = stack.astype(np.float32)
        elif (cast and stack.dtype == np.float32
              and self.compute_dtype == jnp.bfloat16):
            import ml_dtypes
            stack = stack.astype(ml_dtypes.bfloat16)
        sh = NamedSharding(self._mesh, P(None, 'data'))
        return jax.device_put(jnp.asarray(stack), sh)

    def update_n_on_device(self, multi_fn, data_stack, label_stack,
                           n_steps: int = None, mask_stack=None, norm=(),
                           train_eval=None):
        """Run a :meth:`compile_multi_step` function over pre-staged stacks,
        keeping epoch/sample counters coherent.  ``n_steps`` defaults to —
        and must match — the step count baked into ``multi_fn`` at compile
        time, so the counters can never desynchronize from the steps
        actually executed.  ``mask_stack`` (nstack, batch) defaults to
        all-ones (no tail-batch pads).  ``norm``: stacks of RAW (un-
        normalized) pixels from a ``device_normalize=1`` chain need the
        deferred (mean, scale) device constants — pass
        ``trainer._norm_args(batch)`` of any batch carrying the chain's
        spec; the default () means the stack is already normalized.
        ``train_eval``: a ``(label_infos, ns)`` pair (one per step) when
        ``multi_fn`` was compiled with ``train_eval=True`` — the stacked
        eval-node outputs then feed ``train_metric`` exactly as K per-step
        readbacks would, deferred one dispatch.  Returns the last loss
        (device scalar — fetching it is a real completion barrier)."""
        if self.inference_only:
            raise RuntimeError(
                'trainer was built inference_only=1 (no optimizer state); '
                'it can predict/evaluate but not train')
        compiled = getattr(multi_fn, 'n_steps', None)
        if n_steps is None:
            n_steps = compiled
        elif compiled is not None and n_steps != compiled:
            raise ValueError(
                f'n_steps={n_steps} does not match the step count '
                f'{compiled} compiled into multi_fn')
        if mask_stack is None:
            mask_stack = self._ones_mask_stack(data_stack.shape[:2])
        sc0 = self.sample_counter
        old_pending = self._pending_train_eval
        self._pending_train_eval = None
        # the accumulation cadence BAKED INTO the compiled body, not the
        # live config — a multi_fn compiled before an update_period tweak
        # applies the optimizer on its compile-time cadence, and the
        # accumulator and the host epoch counter follow the same one
        period = getattr(multi_fn, 'update_period',
                         max(1, self.update_period))
        self._sync_accumulator(period)
        with span('train.launch', 'train', k=n_steps, update=sc0) as launch:
            (self.params, self.opt_state, self.grad_acc, losses, evals) = \
                multi_fn(self.params, self.opt_state, self.grad_acc,
                         data_stack, label_stack, self._rng,
                         self.epoch_counter, sc0, mask_stack, self.round,
                         norm)
        self._steps.launch_ns = launch.dur_ns
        if period == 1:
            self.epoch_counter += n_steps
        else:
            # optimizer applications this window — same cadence the scan
            # body's in-graph counter followed
            self.epoch_counter += sum(
                1 for t in range(n_steps) if (sc0 + t + 1) % period == 0)
        self.sample_counter += n_steps
        if train_eval is not None:
            label_infos, ns = train_eval
            # window-shaped pending (dict-tagged): one readback per
            # dispatch, drained one dispatch late like the per-step path
            self._pending_train_eval = {
                'losses': losses, 'evals': evals,
                'infos': label_infos, 'ns': ns}
        if old_pending is not None:
            self._drain_train_eval(old_pending)
        self._gate_losses(losses, sc0)
        for listener in self._loss_listeners:
            for t in range(n_steps):
                listener(losses[t])
        return losses[-1]

    def _gate_losses(self, losses, sc0: int) -> None:
        """Divergence gate over a scanned dispatch's per-step losses.
        Only when something can act on them (halt / breaker / NaN
        injection — same arming rule as ``_observe_loss``) does this
        fetch the loss vector (ONE host sync per K-step dispatch, the
        scanned path's analogue of the per-step deferred check); every
        step feeds ``_check_loss`` so ``nan_at_step``-style events and
        consecutive-NaN streaks land on the exact step index."""
        from ..runtime import faults
        plan = faults.active_plan()
        inject = plan is not None and plan.has_nan_events()
        if self.nan_action != 'halt' and not self.nan_breaker and not inject:
            return
        for t, loss in enumerate(np.asarray(losses)):
            self._check_loss(sc0 + t, loss)

    def _ones_mask_stack(self, shape):
        """Cached on-device all-ones (nstack, batch) loss-mask stack for
        :meth:`update_n_on_device` — the common no-pad case costs no
        per-call H2D transfer."""
        key = ('stack',) + tuple(shape)
        cached = self._ones_mask_cache.get(key)
        if cached is None:
            cached = self.shard_batch_stack(
                np.ones(shape, np.float32), cast=False)
            self._ones_mask_cache[key] = cached
        return cached

    def _device_stack(self, arrays):
        """Stack already-staged per-batch device arrays (batch axis
        sharded over ``data``) into the (nstack, batch, ...) layout
        :meth:`compile_multi_step` scans — a device-side op, so the
        per-batch async H2D transfers :meth:`stage_batch` enqueued are
        never re-shipped over the host link."""
        if self._stack_jit is None:
            sh = NamedSharding(self._mesh, P(None, 'data'))
            # lint: allow(jit-ledger): trivial on-device restage (one stack op, no flops worth a ledger row); shapes bounded by the K ladder
            self._stack_jit = jax.jit(lambda *xs: jnp.stack(xs),
                                      out_shardings=sh)
        return self._stack_jit(*arrays)

    def update_staged_window(self, multi_fn, staged_list):
        """Drive one :meth:`compile_multi_step` dispatch over a window of
        K batches staged by :meth:`stage_batch` — the production scanned
        hot loop (``steps_per_dispatch``, doc/trainer.md).  The staged
        handles' async H2D transfers overlap earlier dispatches; here
        they are stacked on device and the whole window runs as ONE
        program: zero per-step dispatch/link RTT.  Tail-batch loss masks
        ride the stack, so ``round_batch=0`` pad rows stay out of the
        gradients exactly as on the per-step path.  Counters, LR
        schedule, dropout keys and the divergence gate all match K
        per-step calls bitwise.  Returns the window's last loss (device
        scalar)."""
        if self.inference_only:
            raise RuntimeError(
                'trainer was built inference_only=1 (no optimizer state); '
                'it can predict/evaluate but not train')
        if len(staged_list) != multi_fn.n_steps:
            raise ValueError(
                f'window of {len(staged_list)} batches does not match the '
                f'step count {multi_fn.n_steps} compiled into multi_fn')
        for s in staged_list:
            if s[2]:
                raise ValueError(
                    'scanned dispatch does not carry extra_data '
                    '(attachtxt chains); use the per-step path')
        train_eval = None
        armed = bool(self.eval_train and len(self.train_metric))
        if armed and not getattr(multi_fn, 'train_eval', False):
            raise ValueError(
                'eval_train=1 with train metrics needs a multi_fn compiled '
                'with train_eval=True, or the window\'s metrics are lost')
        with span('train.dispatch', 'train', k=multi_fn.n_steps,
                  update=self.sample_counter) as dispatch:
            self._steps.begin(dispatch)
            if getattr(multi_fn, 'train_eval', False):
                infos = [_HostLabelInfo(s[4], self.net_cfg.label_name_map,
                                        self.net_cfg.label_range)
                         for s in staged_list]
                ns = [s[5] - s[6] for s in staged_list]
                train_eval = (infos, ns)
            data_stack = self._device_stack([s[0] for s in staged_list])
            label_stack = self._device_stack([s[1] for s in staged_list])
            mask_stack = self._device_stack([s[3] for s in staged_list])
            last = self.update_n_on_device(
                multi_fn, data_stack, label_stack, mask_stack=mask_stack,
                norm=staged_list[0][7], train_eval=train_eval)
        self._steps.end(dispatch.dur_ns)
        return last

    # --- training ---------------------------------------------------------
    def start_round(self, round_: int) -> None:
        self.round = round_
        self._steps.reset()
        if self.test_on_server:
            bad = self.check_weight_consistency()
            if bad:
                raise RuntimeError(
                    f'{bad} weight tensors diverged across replicas')

    def check_weight_consistency(self) -> int:
        """``test_on_server`` analog (``async_updater-inl.hpp:144-154``).

        The reference had every worker fetch the server's weight copy at
        round start and compare.  Here there is no server: the invariant is
        that every device holding a replica of the same parameter shard
        agrees bitwise (catching nondeterministic collectives or sharding
        bugs).  Returns the number of mismatching tensors; mismatches are
        reported on stderr like the reference's CheckWeight_.
        """
        import sys
        bad = 0
        for lk, fields in self.params.items():
            for fk, arr in fields.items():
                seen: Dict[str, np.ndarray] = {}
                for sh in arr.addressable_shards:
                    key = str(sh.index)
                    d = np.asarray(sh.data)
                    if key in seen:
                        if not np.array_equal(seen[key], d, equal_nan=True):
                            bad += 1
                            sys.stderr.write(
                                f'weight inconsistent: layer {lk} field {fk} '
                                f'(device {sh.device})\n')
                            break
                    else:
                        seen[key] = d
        return bad

    def stage_batch(self, batch):
        """Begin the async host->device staging of a batch: every
        ``device_put`` here only ENQUEUES its transfer, so calling this
        for batch i+1 before dispatching batch i's step overlaps the host
        link with compute (the H2D half of the reference's prefetch
        design, ``iter_thread_buffer``; the device half is
        :meth:`update_staged`).  Returns an opaque handle for
        :meth:`update_staged`.  Safe because the batch adapters allocate
        fresh arrays per batch (io/iter_batch.py)."""
        with span('train.stage', 'train', rows=batch.batch_size) as sp:
            norm = self._norm_args(batch)
            # raw (uncentered) pixels must not be pre-cast to bf16: values
            # ~128 lose ~0.4% relative each, which mean-subtraction
            # amplifies ~100x.  uint8 ships as-is; raw f32 (affine path)
            # ships f32 and is centered on device before any compute-dtype
            # cast.
            data = self._shard_batch(batch.data, cast=not norm)
            label = self._shard_batch(batch.label, cast=False)
            extra = tuple(self._shard_batch(e) for e in batch.extra_data)
            # synthetic pad rows of a short tail batch (round_batch=0) carry
            # zero loss-mask so they contribute nothing to grads; real rows
            # — including round_batch=1 wrapped instances, which the
            # reference trains on (nnet_impl:141-170) — keep the reference's
            # per-instance 1/batch_size weight
            bs = batch.batch_size
            if batch.num_batch_padd \
                    and getattr(batch, 'pad_synthetic', False):
                mask = np.ones(bs, np.float32)
                mask[bs - batch.num_batch_padd:] = 0.0
                mask = self._shard_batch(mask, cast=False)
            else:
                mask = self._ones_mask(bs)
            host_label = (
                np.asarray(batch.label)
                if self.eval_train and len(self.train_metric) else None)
            sp.attrs.update(
                bytes=int(data.nbytes) + int(label.nbytes),
                cast=bool(data.dtype != getattr(batch.data, 'dtype', None)))
        return (data, label, extra, mask, host_label, bs,
                batch.num_batch_padd, norm)

    def update(self, batch) -> None:
        """One minibatch through forward/backward/(maybe) update —
        the reference hot loop (``nnet_impl:141-185``)."""
        self.update_staged(self.stage_batch(batch))

    def update_staged(self, staged) -> None:
        """Dispatch the training step for a batch staged by
        :meth:`stage_batch`."""
        if self.inference_only:
            raise RuntimeError(
                'trainer was built inference_only=1 (no optimizer state); '
                'it can predict/evaluate but not train')
        (data, label, extra, mask, host_label, bs, num_batch_padd,
         norm) = staged
        with span('train.dispatch', 'train', k=1,
                  update=self.sample_counter) as dispatch:
            self._steps.begin(dispatch)
            self._sync_accumulator(self.update_period)
            do_update = (self.sample_counter + 1) % self.update_period == 0
            rng = jax.random.fold_in(
                self._rng, 1 + self.sample_counter * 131 + self.round)
            old_pending = self._pending_train_eval
            self._pending_train_eval = None
            if self._step_avals is None:
                self._step_avals = (jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    (data, label, extra, mask, norm)), do_update)
            with span('train.launch', 'train', k=1,
                      update=self.sample_counter) as launch:
                (self.params, self.opt_state, self.grad_acc, loss, evals,
                 stats) = self._train_step_fn(
                     self.params, self.opt_state, self.grad_acc, data, label,
                     extra, mask, rng, self.epoch_counter, self.round,
                     do_update=do_update, norm=norm)
            self._steps.launch_ns = launch.dur_ns
            if stats:
                self._step_stats.append(dict(stats, loss=loss))
            self._observe_loss(loss)
            for listener in self._loss_listeners:
                listener(loss)
            if host_label is not None:
                # defer this step's metric readback one step: by the next
                # update() (or evaluate()) the values are already on host,
                # so no per-step device sync — the analogue of the
                # reference's reuse of already-copied eval nodes
                # (nnet_impl:174-180)
                label_info = _HostLabelInfo(host_label,
                                            self.net_cfg.label_name_map,
                                            self.net_cfg.label_range)
                self._pending_train_eval = (
                    loss, evals, label_info, bs - num_batch_padd)
            if old_pending is not None:
                self._drain_train_eval(old_pending)
            if do_update:
                self.epoch_counter += 1
            self.sample_counter += 1
        self._steps.end(dispatch.dur_ns)

    def step_program_text(self) -> str:
        """Compiled text of the per-step program as :meth:`update_staged`
        first dispatched it (shapes and shardings of that call's batch,
        the live parameters'), ``''`` before any step.  Its
        ``metadata={op_name=...}`` is where a profiler trace's events get
        their conf layer from (``utils/profiler.device_time_by_scope``).
        Compiles, or reads the persistent cache: not for the step loop."""
        if self._step_avals is None:
            return ''
        (data, label, extra, mask, norm), do_update = self._step_avals
        return self._train_step_fn.compiled_text(
            self.params, self.opt_state, self.grad_acc, data, label, extra,
            mask, jax.random.fold_in(self._rng, 0), self.epoch_counter,
            self.round, do_update=do_update, norm=norm)

    def step_stats(self, clear: bool = True) -> List[Dict[str, float]]:
        """What the newest steps (at most 512) counted beside their loss
        - a net with layers that count (``moe``: the share of assignments
        that landed on held experts, the largest held expert's load over
        the mean) - as one ``{name: value, 'loss': value}`` a step, oldest
        first.  The step loop only keeps the device scalars; the fetch is
        here.  ``[]`` for a net whose layers count nothing."""
        rows = [{k: float(v) for k, v in row.items()}
                for row in jax.device_get(list(self._step_stats))]
        if clear:
            self._step_stats.clear()
        return rows

    def add_loss_listener(self, listener) -> None:
        """Call ``listener(loss)`` with every dispatched training step's
        loss, still a device scalar (nothing is fetched here: a listener
        that converts it waits for the step): after each
        :meth:`update_staged`, and once per step of a scanned window, in
        step order.  For harnesses and monitors; the trainer's own gate
        is :meth:`_observe_loss`."""
        self._loss_listeners.append(listener)

    def remove_loss_listener(self, listener) -> None:
        self._loss_listeners.remove(listener)

    def _observe_loss(self, loss) -> None:
        """Host-side divergence gate over the step's loss.

        Extends the ``nan_action`` gate beyond per-batch ``skip`` (which
        only zeroes the poisoned gradients in-graph): ``halt`` raises
        ``DivergenceError`` with step/loss context on the first
        non-finite loss, and a nonzero ``nan_breaker`` is a
        consecutive-NaN circuit breaker — after k non-finite losses in a
        row the error raises regardless of ``nan_action``, so a
        supervisor can skip transient spikes but abort-and-restore on
        sustained divergence.  Only engages when something can act on
        the value (halt, a breaker, or an active NaN-injection fault
        plan).

        The check is deferred ONE step (the same idiom as the deferred
        train-metric readback above): this step's device value is
        stashed and the previous step's — materialized on host by now —
        is inspected, so the gate adds no per-step blocking sync.
        Divergence therefore surfaces one update late; callers settle
        the final pending value with :meth:`flush_divergence_check`."""
        from ..runtime import faults
        plan = faults.active_plan()
        inject = plan is not None and plan.has_nan_events()
        if self.nan_action != 'halt' and not self.nan_breaker and not inject:
            return
        prev, self._pending_loss = (self._pending_loss,
                                    (self.sample_counter, loss))
        if prev is not None:
            self._check_loss(*prev)

    def flush_divergence_check(self) -> None:
        """Settle the deferred divergence gate — call after a batch
        loop's last ``update``, or the final step's loss goes
        unchecked."""
        prev, self._pending_loss = self._pending_loss, None
        if prev is not None:
            self._check_loss(*prev)

    def reset_transient_state(self) -> None:
        """Clear per-step in-flight state a fault may have poisoned —
        the supervisor calls this before restoring a checkpoint.  Keeps
        the reset next to the state it protects: the deferred metric
        readback, the deferred divergence gate, and the NaN streak.
        Train metrics are not part of the exact-resume tree, so they are
        cleared too — replayed batches must not double-count (the
        recovered round reports metrics over the post-restore pass
        only)."""
        self._pending_train_eval = None
        self._pending_loss = None
        self.nan_streak = 0
        self.train_metric.clear()

    def _check_loss(self, step: int, loss) -> None:
        from ..runtime import faults
        lf = float(loss)
        plan = faults.active_plan()
        if plan is not None:
            lf = plan.on_loss(step, lf)
        if np.isfinite(lf):
            self.nan_streak = 0
            return
        self.nan_streak += 1
        if self.nan_action == 'halt' or (
                self.nan_breaker and self.nan_streak >= self.nan_breaker):
            raise faults.DivergenceError(step, lf, self.nan_streak)

    def flush_train_metrics(self) -> None:
        """Force the one-step-deferred train-metric readback (see
        ``update``); after this, ``train_metric`` reflects every update so
        far.  ``evaluate`` calls it implicitly."""
        if self._pending_train_eval is not None:
            pending, self._pending_train_eval = self._pending_train_eval, None
            self._drain_train_eval(pending)

    def _ones_mask(self, bs: int):
        """Cached on-device all-ones loss mask — the no-pad common case
        costs no per-step H2D transfer."""
        cached = self._ones_mask_cache.get(bs)
        if cached is None:
            cached = self._shard_batch(np.ones(bs, np.float32), cast=False)
            self._ones_mask_cache[bs] = cached
        return cached

    def _drain_train_eval(self, pending) -> None:
        # two spans a drain: the wait for the device and the copy to the
        # host (train.eval_fetch), then the metrics' numpy
        # (train.eval_score)
        if isinstance(pending, dict):
            # a scanned window's stacked eval outputs: ONE readback, then
            # the per-step host math in step order — bitwise the same
            # metric accumulation as K per-step drains
            rows = int(sum(pending['ns']))
            with span('train.eval_fetch', 'train', rows=rows):
                losses = np.asarray(pending['losses'])
                evals = [np.asarray(e) for e in pending['evals']]
            with span('train.eval_score', 'train', rows=rows):
                for t, (info, n) in enumerate(zip(pending['infos'],
                                                  pending['ns'])):
                    if self.nan_action == 'skip' \
                            and not np.isfinite(losses[t]):
                        continue
                    self.train_metric.add_eval([e[t][:n] for e in evals],
                                               info.slice(n))
            return
        loss, evals, label_info, n = pending
        with span('train.eval_fetch', 'train', rows=n):
            # a poisoned batch's NaN outputs would wreck the round's train
            # metrics along with the weights
            ok = self.nan_action != 'skip' or np.isfinite(float(loss))
            evals = [np.asarray(e)[:n] for e in evals] if ok else None
        if not ok:
            return
        with span('train.eval_score', 'train', rows=n):
            self.train_metric.add_eval(evals, label_info.slice(n))

    def update_on_device(self, data, label, norm=()) -> None:
        """One training step over batches already resident on device (jax
        arrays with the right shardings).  Used by benchmarks and by data
        pipelines that pre-stage batches to hide host->device latency.
        ``norm``: required (as from :meth:`_norm_args`) when ``data`` is
        RAW pixels from a ``device_normalize=1`` chain."""
        self._sync_accumulator(self.update_period)
        do_update = (self.sample_counter + 1) % self.update_period == 0
        rng = jax.random.fold_in(self._rng, 1 + self.sample_counter * 131 +
                                 self.round)
        (self.params, self.opt_state, self.grad_acc, _, _, _) = \
            self._train_step_fn(self.params, self.opt_state, self.grad_acc,
                                data, label, (), None, rng,
                                self.epoch_counter, self.round,
                                do_update=do_update, norm=norm)
        if do_update:
            self.epoch_counter += 1
        self.sample_counter += 1

    def train_step_flops(self, data=None, label=None,
                         analyzed_only=False) -> float:
        """HLO-estimated FLOPs of one full optimizer step (fwd + bwd +
        update).  Reads the LIVE program ledger first (obs/programs.py):
        any step this trainer already compiled — per-step or scanned
        window, whose While body XLA cost analysis counts once, so
        its flops are already per-step — answers for free,
        instead of lowering+compiling a throwaway program per call.
        Only when nothing has compiled yet (and ``data``/``label`` are
        given — the bench-facing signature) does it compile one probe,
        through the same ledger wrap so even the probe gets a
        ``/programs`` row.  ``analyzed_only=True`` never triggers the
        lazy AOT analysis — the render-thread spelling (/statusz
        providers), which reports 0.0 until some detailed reader has
        filled the entries.  Returns 0.0 when the backend exposes no
        cost model."""
        best = 0.0
        for prog in (self._prog_multi, self._prog_step):
            for e in prog.entries(analyze=not analyzed_only):
                if e.flops > 0:
                    # prefer the biggest per-step figure: the do_update
                    # (full optimizer) step dominates its no-update twin
                    best = max(best, e.flops / e.steps)
        if analyzed_only:
            return best
        if best > 0:
            return best
        if data is None or label is None:
            return 0.0
        rng = jax.random.fold_in(self._rng, 0)
        try:
            entry = self._train_step_fn.ensure_compiled(
                self.params, self.opt_state, self.grad_acc, data, label,
                (), None, rng, self.epoch_counter, self.round,
                do_update=True)
            return float(entry.flops) if entry is not None else 0.0
        except (AttributeError, KeyError, TypeError, ValueError,
                NotImplementedError, RuntimeError) as e:
            # backends without a cost model surface it many ways; record
            # the miss instead of swallowing it so a supervisor's failure
            # log shows why MFU reads 0
            from ..runtime import faults
            faults.global_failure_log().record(
                'cost_analysis', f'train_step_flops unavailable: {e!r}')
            return 0.0

    # --- evaluation / prediction ------------------------------------------
    def _forward_nodes_async(self, batch, node_ids: List[int]):
        """Launch the forward pass; returns device arrays (no readback)."""
        extra = tuple(self._shard_batch(e) for e in batch.extra_data)
        norm = self._norm_args(batch)
        # raw uncentered pixels: same no-bf16-precast rule as stage_batch
        return self._forward_fn(self.params,
                                self._shard_batch(batch.data, cast=not norm),
                                extra, self.round, nodes=tuple(node_ids),
                                norm=norm)

    def _forward_nodes(self, batch, node_ids: List[int]) -> List[np.ndarray]:
        return [np.asarray(v)
                for v in self._forward_nodes_async(batch, node_ids)]

    def evaluate(self, data_iter, name: str) -> str:
        """Run metrics over an iterator; returns the reference's stderr
        format ``\\tname-metric:value``.  Like the reference
        (``nnet_impl:224-245``), the pending train metrics are prepended
        (and cleared) when ``eval_train`` is set; ``data_iter=None``
        returns just the train part."""
        ret = ''
        self._steps.reset()
        self.flush_train_metrics()
        if self.eval_train and len(self.train_metric):
            ret += self.train_metric.print('train')
            self.train_metric.clear()
        rows = self.step_stats()
        for key in sorted(rows[0]) if rows else ():
            mean = sum(r[key] for r in rows) / len(rows)
            ret += f'\ttrain-{key}:{mean:g}'
        if data_iter is None:
            return ret
        self.metric.clear()
        # one-batch software pipeline: batch i+1's forward is enqueued
        # before batch i's outputs are read back, so the device computes
        # while the host blocks on the transfer (the reference's
        # eval-request copies overlap the same way, nnet_impl:232-241)
        pending = None

        def _consume(p):
            outs, label_info, n = p
            self.metric.add_eval([np.asarray(o)[:n] for o in outs],
                                 label_info.slice(n))

        for batch in data_iter:
            outs = self._forward_nodes_async(batch, self._eval_node_ids)
            n = batch.batch_size - batch.num_batch_padd
            label_info = _HostLabelInfo(np.asarray(batch.label),
                                        self.net_cfg.label_name_map,
                                        self.net_cfg.label_range)
            prev, pending = pending, (outs, label_info, n)
            if prev is not None:
                _consume(prev)
        if pending is not None:
            _consume(pending)
        return ret + self.metric.print(name)

    def _forward_node_bucketed(self, batch, nid: int) -> np.ndarray:
        """One node's host output with the batch split/padded onto the
        ``pred_buckets`` ladder (``utils/bucketing.py``): the jitted
        forward only ever sees bucket shapes, so a stream of arbitrary
        request sizes compiles at most ``len(pred_buckets)`` programs
        instead of one per novel shape.  Pad rows are sliced off before
        concatenation; returns all ``batch.batch_size`` rows (callers
        trim ``num_batch_padd`` exactly as on the unbucketed path)."""
        from ..utils.bucketing import chunk_plan, pad_rows
        ddim = int(self._mesh.shape['data'])
        bad = [b for b in self.pred_buckets if b % ddim]
        if bad:
            # same invariant PredictEngine enforces at construction: a
            # padded batch must shard evenly over the mesh data axis
            raise ValueError(
                f'pred_buckets {bad} do not divide the mesh data axis '
                f'({ddim} devices); pick multiples so padded batches '
                f'shard evenly')
        norm = self._norm_args(batch)
        data = np.asarray(batch.data)
        extras = [np.asarray(e) for e in batch.extra_data]
        outs = []
        for off, take, b in chunk_plan(data.shape[0], self.pred_buckets):
            d = self._shard_batch(pad_rows(data[off:off + take], b),
                                  cast=not norm)
            ex = tuple(self._shard_batch(pad_rows(e[off:off + take], b))
                       for e in extras)
            (value,) = self._forward_fn(self.params, d, ex, self.round,
                                        nodes=(nid,), norm=norm)
            outs.append(np.asarray(value)[:take])
        if not outs:
            return np.empty((0,), np.float32)
        return np.concatenate(outs, axis=0)

    def predict(self, batch) -> np.ndarray:
        """Argmax of the final node per instance (``TransformPred``,
        nnet_impl:286-298)."""
        last = self.net.cfg.layers[-1].nindex_out[-1]
        if self.pred_buckets:
            out = self._forward_node_bucketed(batch, last)
        else:
            out = self._forward_nodes(batch, [last])[0]
        n = batch.batch_size - batch.num_batch_padd
        out = out[:n]
        return self._pred_transform(out)

    @staticmethod
    def _pred_transform(out: np.ndarray) -> np.ndarray:
        if out.ndim > 1 and out.shape[1] != 1:
            return np.argmax(out, axis=1).astype(np.float32)
        return out.reshape(-1).astype(np.float32)

    def forward_stream(self, batches, nid: int):
        """Generator of one node's per-batch host outputs, pad rows
        trimmed, with a one-batch software pipeline: batch i+1's forward
        is enqueued before batch i's readback blocks, so the device
        computes under the host transfer — the pred/extract analog of
        :meth:`evaluate`'s overlap (reference eval-request overlap,
        nnet_impl:232-241).  When ``pred_buckets`` is set the stream
        routes through the bucketed forward instead (trading the
        one-batch overlap for the bounded compile cache) — otherwise an
        iterator with varying batch sizes would still trace novel-shape
        programs and defeat the ladder."""
        if self.pred_buckets:
            for batch in batches:
                out = self._forward_node_bucketed(batch, nid)
                yield out[:batch.batch_size - batch.num_batch_padd]
            return
        pending = None
        for batch in batches:
            outs = self._forward_nodes_async(batch, [nid])
            prev, pending = pending, (
                outs[0], batch.batch_size - batch.num_batch_padd)
            if prev is not None:
                yield np.asarray(prev[0])[:prev[1]]
        if pending is not None:
            yield np.asarray(pending[0])[:pending[1]]

    def predict_stream(self, batches):
        """Pipelined :meth:`predict` over a batch iterator."""
        last = self.net.cfg.layers[-1].nindex_out[-1]
        for out in self.forward_stream(batches, last):
            yield self._pred_transform(out)

    def extract_feature(self, batch, node_name: str) -> np.ndarray:
        nid = self.net.node_index(node_name)
        if self.pred_buckets:
            out = self._forward_node_bucketed(batch, nid)
        else:
            out = self._forward_nodes(batch, [nid])[0]
        n = batch.batch_size - batch.num_batch_padd
        return out[:n]

    # --- checkpointing ----------------------------------------------------
    def _training_state(self) -> dict:
        """The exact-resume tree: params, optimizer state, counters and,
        where ``update_period > 1`` keeps one, the gradient accumulator
        (a period-1 trainer has none, and its sidecar holds none)."""
        tree = {'params': self.params, 'opt_state': self.opt_state,
                'counters': {
                    # numpy (not jnp): int64 survives regardless of the
                    # jax x64 flag
                    'epoch': np.asarray(self.epoch_counter, np.int64),
                    'sample': np.asarray(self.sample_counter, np.int64),
                    'round': np.asarray(self.round, np.int64)}}
        if self.grad_acc is not None:
            tree['grad_acc'] = self.grad_acc
        return tree

    def save_training_state(self, ckpt_dir: str, step: int,
                            block: bool = True, retry=None) -> str:
        """Beyond-reference EXACT resume state: params + optimizer state
        (momentum/Adam moments) + counters + the gradient accumulator of
        an ``update_period > 1`` trainer, via the sharded orbax path
        (nnet/sharded_ckpt.py).  The reference model
        file deliberately drops optimizer state (``nnet_impl:82-87`` saves
        layer blobs only — parity preserved in :meth:`save_model`); this
        sidecar makes ``continue=1`` bit-exact mid-momentum.  Works for
        mesh-sharded state (shards save/restore in place)."""
        from . import sharded_ckpt
        self._steps.reset()
        return sharded_ckpt.save_sharded(ckpt_dir, step,
                                         self._training_state(),
                                         block=block, retry=retry)

    def snapshot_training_state(self):
        """Donation-safe snapshot of the exact-resume tree (same structure
        as :meth:`save_training_state`) for the async save path: every
        device leaf is copied into a fresh buffer (a cheap, non-blocking
        dispatch — the compiled ``train_step`` donates params/opt_state/
        grad_acc, so handing the LIVE arrays to a background writer would
        hand it buffers the very next step invalidates), counters are
        copied eagerly.  Any validity gate (e.g. the supervisor's
        NaN-streak rule) must be resolved BEFORE taking the snapshot —
        once taken, the writer will commit it."""
        from ..runtime.async_ckpt import snapshot_tree
        self._steps.reset()
        return snapshot_tree(self._training_state())

    def load_training_state(self, ckpt_dir: str,
                            step: Optional[int] = None,
                            restore_params: bool = False,
                            fallback: bool = False, retry=None) -> int:
        """Restore :meth:`save_training_state` output (latest step by
        default) into this initialized trainer; returns the step.

        By default only the OPTIMIZER side (opt_state, grad_acc,
        counters) is adopted — the weights stay whatever the caller
        loaded (normally the reference model file, which the sidecar's
        params duplicate).  That makes a stale sidecar (left behind by an
        older run in the same dir) at worst a wrong-momentum bug instead
        of silently resuming on the wrong WEIGHTS.  Pass
        ``restore_params=True`` to adopt the sidecar's params too (e.g.
        when restoring without a model file).

        ``fallback=True`` restores resiliently: the newest step that
        passes integrity verification wins, corrupt ones are quarantined
        (``sharded_ckpt.restore_resilient``) — the supervisor's
        restore-last-good path.

        The accumulator follows this trainer's ``update_period``, not the
        writer's: a sidecar without one (written at period 1) restores
        into a period-P trainer as zeros, which is what it was; a sidecar
        with one (period P, or any written before PR 32) restores into a
        period-1 trainer only if it is all zeros, and raises if gradients
        would be lost."""
        from . import sharded_ckpt
        self._steps.reset()

        def like(saved_keys):
            tree = self._training_state()
            tree.pop('grad_acc', None)
            if 'grad_acc' in saved_keys:
                tree['grad_acc'] = jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype,
                                                   sharding=p.sharding),
                    self.params)
            return tree

        if fallback:
            tree, got = sharded_ckpt.restore_resilient(ckpt_dir, like,
                                                       retry=retry)
        else:
            tree, got = sharded_ckpt.restore_sharded(ckpt_dir, like, step,
                                                     retry=retry)
        acc = tree.get('grad_acc')
        if self.update_period > 1:
            acc = self._zero_accumulator() if acc is None else acc
        elif acc is not None:
            self._require_empty(
                acc, f'restoring step {got} of {ckpt_dir} into an '
                f'update_period = 1 trainer')
            acc = None
        if restore_params:
            self.params = tree['params']
        self.opt_state = tree['opt_state']
        self.grad_acc = acc
        self._record_state()
        c = tree['counters']
        self.epoch_counter = int(c['epoch'])
        self.sample_counter = int(c['sample'])
        self.round = int(c['round'])
        return got

    def model_header(self) -> bytes:
        """The model-file preamble ahead of the weight blob (NetConfig +
        epoch_counter) — cheap host bytes; an async save captures them at
        snapshot time while the blob serializes in the background."""
        import io as _io
        b = _io.BytesIO()
        self.net_cfg.save_net(b)
        b.write(struct.pack('<q', self.epoch_counter))
        return b.getvalue()

    @staticmethod
    def write_model_bytes(fo: BinaryIO, header: bytes,
                          blob: bytes) -> None:
        """THE model-file layout, in one place: header, u64 blob length,
        blob — sync :meth:`save_model` and the CLI's async writer both
        route through here, so the formats can never drift apart."""
        fo.write(header)
        fo.write(struct.pack('<Q', len(blob)))
        fo.write(blob)

    def save_model(self, fo: BinaryIO) -> None:
        self._steps.reset()
        self.write_model_bytes(
            fo, self.model_header(),
            checkpoint.params_to_blob(self.net, self.params))

    def load_model(self, fi: BinaryIO) -> None:
        self.net_cfg = NetConfig()
        self.net_cfg.load_net(fi)
        (self.epoch_counter,) = struct.unpack('<q', fi.read(8))
        (blob_len,) = struct.unpack('<Q', fi.read(8))
        blob = fi.read(blob_len)
        # init_net reconfigures the loaded structure (validating it against
        # the config) and rebuilds net/mesh/hypers/compiled steps
        self.init_net()
        self.params = checkpoint.blob_to_params(self.net, blob)
        self._post_params_init()

    def copy_model_from(self, fi: BinaryIO) -> None:
        """Finetune: name-matched layer copy + epoch reset
        (``nnet_impl:101-134``)."""
        self.init_model()
        old_cfg = NetConfig()
        old_cfg.load_net(fi)
        fi.read(8)  # old epoch_counter, discarded (reset to 0)
        (blob_len,) = struct.unpack('<Q', fi.read(8))
        blob = fi.read(blob_len)
        self.epoch_counter = 0
        old_raw = checkpoint.blob_to_raw(old_cfg.layers, blob)
        params = jax.device_get(self.params)
        for i, old_info in enumerate(old_cfg.layers):
            if not old_info.name or str(i) not in old_raw:
                continue
            for j, new_info in enumerate(self.net_cfg.layers):
                if new_info.name == old_info.name:
                    print(f'Copying layer {old_info.name}')
                    params[str(j)] = checkpoint.record_to_memory(
                        self.net.layers[j], new_info.type, old_raw[str(i)])
        self.params = params
        self._post_params_init()


class _HostLabelInfo:
    """Host-side label field view used by metrics."""

    def __init__(self, mat: np.ndarray, name_map, ranges):
        self._mat = mat
        self._name_map = name_map
        self._ranges = ranges

    def slice(self, n: int) -> '_HostLabelInfo':
        return _HostLabelInfo(self._mat[:n], self._name_map, self._ranges)

    def field(self, name: str) -> np.ndarray:
        a, b = self._ranges[self._name_map[name]]
        return self._mat[:, a:b]
