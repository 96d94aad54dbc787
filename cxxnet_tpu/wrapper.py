"""User-level API with the reference wrapper's surface.

The reference exposed its C++ trainer to Python through a C ABI + ctypes
(``wrapper/cxxnet_wrapper.h:29-225``, ``wrapper/cxxnet.py:64-312``).  Here
the trainer *is* Python/JAX, so the same user API — ``DataIter``, ``Net``
(set_param/init_model/load/save/start_round/update/evaluate/predict/
extract/set_weight/get_weight) and module-level ``train()`` helpers — binds
directly, with no FFI hop on the train path.  Semantics preserved:

* ``Net.update`` accepts a DataIter positioned on a batch or a raw
  ``(batch, channel, y, x)`` numpy array + label,
* ``get_weight``/``set_weight`` use the reference's on-disk weight layouts
  (fullc wmat ``(nhidden, nin)``, conv ``(ngroup, nch/g, nin/g*kh*kw)``),
  addressed by layer name and tag ('wmat'/'bias'),
* model files interoperate with the CLI's ``models/%04d.model`` format.
"""

from __future__ import annotations

import sys
from typing import Iterator, Optional

import jax
import numpy as np

from .io.data import DataBatch, create_iterator
from .nnet import checkpoint
from .nnet.trainer import NetTrainer
from .utils.config import parse_config_string


class DataIter:
    """Config-driven data iterator with the reference's cursor protocol."""

    def __init__(self, cfg: str):
        pairs = parse_config_string(cfg)
        self._it = create_iterator(pairs)
        # pairs after `iter = end` are section defaults (batch_size,
        # input_shape, ...) applied to the whole chain — how the reference
        # wrapper confs are written (example/MNIST/mnist.py)
        seen_end = False
        for name, val in pairs:
            if name == 'iter' and val == 'end':
                seen_end = True
            elif seen_end:
                self._it.set_param(name, val)
        self._it.init()
        self._cursor: Optional[Iterator] = None
        self._batch: Optional[DataBatch] = None
        self.head = True
        self.tail = False

    def before_first(self) -> None:
        self._cursor = iter(self._it)
        self._batch = None
        self.head = True
        self.tail = False

    def next(self) -> bool:
        if self._cursor is None:
            self.before_first()
        try:
            self._batch = next(self._cursor)
            self.head = False
            return True
        except StopIteration:
            self.tail = True
            self._batch = None
            return False

    def check_valid(self) -> None:
        if self.head:
            raise RuntimeError('iterator at head state; call next() first')
        if self.tail:
            raise RuntimeError('iterator reached end')

    @property
    def value(self) -> DataBatch:
        self.check_valid()
        return self._batch

    def get_data(self) -> np.ndarray:
        # CXNIOGetData hands out POST-augment float data (reference
        # wrapper contract).  Under device_normalize=1 the batch carries
        # raw pixels + the deferred spec — apply it here so wrapper
        # consumers see the same values either way.
        batch = self.value
        if batch.norm_spec is not None:
            return batch.norm_spec.apply(batch.data)
        return np.asarray(batch.data, np.float32)

    def get_label(self) -> np.ndarray:
        return np.asarray(self.value.label, np.float32)


class Net:
    """Neural net object (reference ``Net``, wrapper/cxxnet.py:105-280)."""

    def __init__(self, dev: str = 'tpu', cfg: str = ''):
        self._pairs = list(parse_config_string(cfg)) if cfg else []
        if dev:
            self._pairs.append(('dev', dev))
        self._trainer: Optional[NetTrainer] = None
        self._engine = None     # serve.PredictEngine after serve_start
        self._batcher = None    # serve.DynamicBatcher after serve_start
        self._fleet = None      # serve.MultiModelRegistry (models=)
        self._online = None     # online.OnlinePipeline after online_start
        self._online_thread = None
        self._online_result = None

    def _require(self) -> NetTrainer:
        if self._trainer is None:
            raise RuntimeError('call init_model()/load_model() first')
        return self._trainer

    def set_param(self, name, value) -> None:
        self._pairs.append((str(name), str(value)))

    def init_model(self) -> None:
        self._trainer = NetTrainer(self._pairs)
        self._trainer.init_model()

    def load_model(self, fname: str) -> None:
        self._trainer = NetTrainer(self._pairs)
        with open(fname, 'rb') as f:
            f.read(4)   # net_type prefix
            self._trainer.load_model(f)

    def save_model(self, fname: str, net_type: int = 0) -> None:
        with open(fname, 'wb') as f:
            f.write(int(net_type).to_bytes(4, 'little', signed=True))
            self._require().save_model(f)

    def start_round(self, round_counter: int) -> None:
        self._require().start_round(round_counter)

    def update(self, data, label=None) -> None:
        tr = self._require()
        if isinstance(data, DataIter):
            tr.update(data.value)
            return
        data = np.asarray(data, np.float32)
        if data.ndim != 4:
            raise ValueError('Net.update: need 4-d (batch, channel, y, x)')
        if label is None:
            raise ValueError('Net.update: need label')
        label = np.asarray(label, np.float32)
        if label.ndim == 1:
            label = label[:, None]
        if label.shape[0] != data.shape[0]:
            raise ValueError('Net.update: data/label size mismatch')
        tr.update(DataBatch(data, label))

    def evaluate(self, data: 'DataIter', name: str) -> str:
        if not isinstance(data, DataIter):
            raise TypeError('evaluate needs a DataIter')
        data.before_first()
        return self._require().evaluate(iter(data._it), name)

    def predict(self, data) -> np.ndarray:
        tr = self._require()
        if isinstance(data, DataIter):
            return tr.predict(data.value)
        data = np.asarray(data, np.float32)
        if data.ndim != 4:
            raise ValueError('need 4-d tensor to predict')
        return tr.predict(DataBatch(data, np.zeros((data.shape[0], 1),
                                                   np.float32)))

    def extract(self, data, name: str) -> np.ndarray:
        tr = self._require()
        if isinstance(data, DataIter):
            return tr.extract_feature(data.value, name)
        data = np.asarray(data, np.float32)
        return tr.extract_feature(
            DataBatch(data, np.zeros((data.shape[0], 1), np.float32)), name)

    # --- streaming whole-iterator prediction ------------------------------
    def predict_stream(self, data: 'DataIter'):
        """Generator of per-batch prediction vectors over the WHOLE
        iterator (rewound first), pad rows trimmed — the O(batch)-host-
        memory path behind ``CXNNetPredictIter`` (capi.net_predict_iter);
        batches pipeline through ``NetTrainer.predict_stream``."""
        if not isinstance(data, DataIter):
            raise TypeError('predict_stream needs a DataIter')
        tr = self._require()
        data.before_first()
        yield from tr.predict_stream(iter(data._it))

    def extract_stream(self, data: 'DataIter', name: str):
        """Generator of per-batch node activations over the whole
        iterator — the streaming path behind ``CXNNetExtractIter``."""
        if not isinstance(data, DataIter):
            raise TypeError('extract_stream needs a DataIter')
        tr = self._require()
        data.before_first()
        yield from tr.forward_stream(iter(data._it), tr.net.node_index(name))

    # --- online serving (doc/serving.md) ----------------------------------
    def serve_start(self, buckets='1,8,32', max_queue: int = 64,
                    max_wait: float = 0.002, deadline: float = 1.0,
                    warm: bool = True, models=None,
                    mem_budget: int = 0, dtype: str = 'f32',
                    replicas: int = 0, fold_bn: int = 0,
                    fold_batch=None) -> None:
        """Stand up the serving stack over this net's loaded params: a
        bucketed ``PredictEngine`` plus a ``DynamicBatcher``.  Call once;
        ``serve_stop()`` tears down (and must precede a restart).

        ``models`` (optional) is a ``{model_id: model_dir}`` dict of
        sibling checkpoints (same architecture as this net) served
        through a ``MultiModelRegistry`` under ``mem_budget`` bytes —
        route to one with ``serve_scores(..., model=id)``; cold models
        load on demand and evict coldest-first under pressure.
        ``dtype`` selects the quantized-inference storage tier
        (``f32``/``bf16``/``int8`` — doc/serving.md "Quantized
        inference"); it applies to this engine AND every fleet sibling,
        so the ``mem_budget`` ledger fits ~4x more int8 models.
        ``replicas>=2`` serves N per-device data-parallel engine
        replicas behind the one batcher (``serve.replicas``,
        doc/serving.md "Sharded serving").  ``fold_bn=1`` folds conv+BN
        pairs into the conv at engine build (f32 tier only; frozen
        calibration-batch statistics — doc/kernels.md), calibrating on
        ``fold_batch`` (NCHW) or a seeded random batch."""
        from .serve import (DynamicBatcher, PredictEngine,
                            ReplicatedPredictEngine)
        from .utils.bucketing import parse_buckets
        if self._batcher is not None:
            raise RuntimeError('serving already started; serve_stop() first')
        tr = self._require()
        bks = parse_buckets(buckets) if isinstance(buckets, str) \
            else tuple(buckets)
        if replicas >= 2:
            from .utils.metric import StatSet
            self._engine = ReplicatedPredictEngine(
                tr, bks, dtype=dtype, replicas=replicas, stats=StatSet(),
                fold_bn=fold_bn, fold_batch=fold_batch)
        else:
            self._engine = PredictEngine(tr, bks, dtype=dtype,
                                         fold_bn=fold_bn,
                                         fold_batch=fold_batch)
        if warm:
            self._engine.warm()
        self._batcher = DynamicBatcher(self._engine, max_queue=max_queue,
                                       max_wait=max_wait, deadline=deadline,
                                       stats=getattr(self._engine, 'stats',
                                                     None))
        self._fleet = None
        if models:
            from .serve import MultiModelRegistry
            self._fleet = MultiModelRegistry(mem_budget=mem_budget)
            for mid, mdir in dict(models).items():
                self._fleet.add_model(
                    mid, self._fleet_factory(mdir, bks, dtype),
                    model_dir=mdir)

    def _fleet_factory(self, model_dir: str, buckets, dtype: str = 'f32'):
        """Factory closure for one fleet sibling: builds an isolated
        inference-only trainer from this net's config pairs and loads the
        newest checkpoint in ``model_dir`` through the retried reader
        (the factory owns every reference, so eviction really frees the
        device memory)."""
        from .serve import PredictEngine
        from .serve.registry import load_into_trainer, newest_model_file

        def factory():
            best = newest_model_file(model_dir)
            if best is None:
                raise FileNotFoundError(f'no model files in {model_dir}')
            tr = load_into_trainer(
                NetTrainer(self._pairs + [('inference_only', '1')]),
                best[1])
            return PredictEngine(tr, buckets, dtype=dtype)
        return factory

    def _require_serving(self):
        if self._batcher is None:
            raise RuntimeError('call serve_start() first')
        return self._batcher

    def serve_scores(self, data, deadline: Optional[float] = None,
                     model: Optional[str] = None) -> np.ndarray:
        """Submit one request through the batcher; blocks for the final
        node's score rows.  Raises the typed serving errors
        (``ServeOverloadError`` / ``DeadlineExceededError``).
        ``model=`` routes to a fleet sibling (engine-direct: fleet
        models are budget-managed, not micro-batched — a cold model may
        load first, so the path is unbounded and ``deadline`` is
        rejected rather than silently ignored).  The fleet lease holds
        off eviction for the whole forward."""
        if model is not None:
            if self._fleet is None:
                raise RuntimeError('serve_start(models=...) first')
            if deadline is not None:
                raise ValueError(
                    'deadline is not enforced on the fleet path (a cold '
                    'model may need to load); pass deadline=None')
            with self._fleet.lease(model) as engine:
                return engine.predict_scores(np.asarray(data, np.float32))
        return self._require_serving().submit(
            np.asarray(data, np.float32), deadline)

    def serve_predict(self, data, deadline: Optional[float] = None,
                      model: Optional[str] = None) -> np.ndarray:
        """Like :meth:`predict` but through the serving stack (micro-
        batched with concurrent callers, bucket-padded)."""
        return NetTrainer._pred_transform(
            self.serve_scores(data, deadline, model=model))

    def serve_reload(self, fname: str) -> None:
        """Manually hot-swap a checkpoint into the live engine (the
        registry's verify→load→warm→swap cycle, minus the watching)."""
        from .nnet import checkpoint
        from .serve.registry import load_model_params
        if self._engine is None:
            raise RuntimeError('call serve_start() first')
        reason = checkpoint.verify_model_digest(fname)
        if reason:
            from .runtime.faults import CheckpointCorruptError
            raise CheckpointCorruptError(f'{fname}: {reason}')
        placed = self._engine.place_params(
            load_model_params(self._engine, fname))
        self._engine.warm_params(placed)
        self._engine.swap_params(placed, version=fname)

    def serve_stats(self, name: str = 'serve') -> str:
        """Per-bucket latency/throughput counters in eval-line format
        (+ the fleet's memory ledger when ``models=`` is serving)."""
        out = self._require_serving().report(name)
        if self._fleet is not None:
            out += self._fleet.report()
        return out

    def serve_stop(self, timeout: Optional[float] = None) -> None:
        """Drain and tear down the serving stack (idempotent)."""
        if self._batcher is not None:
            self._batcher.close(timeout)
            self._batcher = None
        if self._engine is not None and hasattr(self._engine, 'close'):
            self._engine.close(timeout)   # replica worker threads
        if self._fleet is not None:
            self._fleet.close(timeout)
            self._fleet = None
        self._engine = None

    # --- train-while-serve (doc/online.md) --------------------------------
    def online_start(self, train_data, model_dir: str, rounds: int = 1,
                     save_every: int = 8, freshness_slo: float = 0.0,
                     freshness_strict: bool = False, reload: float = 0.05,
                     buckets='1,8,32', max_queue: int = 64,
                     max_wait: float = 0.002, deadline: float = 1.0,
                     qps: float = 50.0, request_source=None,
                     steps_per_dispatch: int = 1,
                     watchdog_deadline: float = 60.0,
                     dtype: str = 'f32') -> None:
        """Run the train-while-serve loop over this net: training starts
        on a background thread while the colocated serving stack answers
        :meth:`online_scores` / :meth:`online_predict` requests, hot-
        reloading each checkpoint published every ``save_every`` steps.
        ``train_data`` is a ``DataIter`` (or raw iterator chain);
        passing a ``request_source`` arms the built-in traffic driver
        (``qps`` requests/sec) for embedders that don't push their own
        requests.
        ``online_wait()`` joins the training thread and returns the
        summary; ``online_stop()`` tears everything down."""
        import threading

        from .online import OnlineConfig, OnlinePipeline
        from .utils.bucketing import parse_buckets
        if self._online is not None:
            raise RuntimeError('online already started; online_stop() first')
        tr = self._require()
        it = train_data._it if isinstance(train_data, DataIter) \
            else train_data
        bks = parse_buckets(buckets) if isinstance(buckets, str) \
            else tuple(buckets)
        cfg = OnlineConfig(
            model_dir=model_dir, save_every=save_every,
            freshness_slo=freshness_slo, freshness_strict=freshness_strict,
            reload_poll=reload, buckets=bks, max_queue=max_queue,
            max_wait=max_wait, deadline=deadline, dtype=dtype,
            qps=qps, watchdog_deadline=watchdog_deadline or None,
            steps_per_dispatch=steps_per_dispatch, silent=True)
        # a request_source arms the built-in driver at `qps`; without
        # one the embedder pushes its own requests via online_scores
        pipe = OnlinePipeline(
            tr, it,
            lambda: NetTrainer(self._pairs + [('inference_only', '1')]),
            cfg, request_source=request_source)
        pipe.start()                      # serving is live before return
        self._online = pipe
        self._online_result = {}

        def _train():
            try:
                self._online_result['summary'] = pipe.run(rounds)
            except BaseException as e:     # surfaced by online_wait
                self._online_result['error'] = e

        self._online_thread = threading.Thread(
            target=_train, daemon=True, name='online-train')
        self._online_thread.start()

    def _require_online(self):
        if self._online is None:
            raise RuntimeError('call online_start() first')
        return self._online

    def online_scores(self, data, deadline: Optional[float] = None):
        """One request through the live online stack (final-node score
        rows); typed serving errors propagate."""
        return self._require_online().submit(
            np.asarray(data, np.float32), deadline)

    def online_predict(self, data, deadline: Optional[float] = None):
        """Class id per row through the online stack."""
        return NetTrainer._pred_transform(self.online_scores(data, deadline))

    def online_stats(self, name: str = 'online') -> str:
        """Freshness/swap gauges + serving ledger, eval-line format."""
        pipe = self._require_online()
        return pipe.eval_line(name) + pipe.serve_report()

    def online_wait(self, timeout: Optional[float] = None) -> dict:
        """Join the training thread; re-raises its error or returns the
        run summary (freshness p50/p99, swaps, served, dropped...)."""
        self._require_online()
        t = self._online_thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError('online training still running')
        res = self._online_result or {}
        if 'error' in res:
            raise res['error']
        return res.get('summary', self._online.summary())

    def online_stop(self, timeout: Optional[float] = None) -> None:
        """Tear down the online loop (idempotent); joins the training
        thread first so close() never races a live step loop."""
        if self._online is None:
            return
        t = self._online_thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError(
                    'online training still running — tearing the stack '
                    'down under a live step loop would corrupt the run')
        self._online.close(timeout)
        self._online = None
        self._online_thread = None

    # --- telemetry (doc/observability.md) ---------------------------------
    def obs_stats(self) -> str:
        """One JSON snapshot of the process-wide telemetry hub — the
        same body the ``/statusz`` endpoint serves: uptime, every
        registered StatSet's counters, subsystem status views
        (registry state machine, execution plan, elastic membership),
        and the flight-recorder state.  Works with or without a loaded
        model: the hub is process-wide."""
        import json

        from .obs import get_hub
        return json.dumps(get_hub().status(), sort_keys=True, default=str)

    def obs_slos(self) -> str:
        """The attached SLO engines' typed verdicts as one JSON object —
        the same body the ``/slos`` endpoint serves (state, burn
        ratios, breach counts, window samples, verdict history per
        objective; ``{}`` when no engine is attached).  The embedder's
        portless way to read health the way the future autoscaler will
        (doc/observability.md "SLOs and burn rates")."""
        import json

        from .obs import get_hub
        return json.dumps(get_hub().slos_view(), sort_keys=True,
                          default=str)

    def obs_programs(self) -> str:
        """The compiler-truth program ledger as one JSON object — the
        same body the ``/programs`` endpoint serves: every compiled
        executable's (name, shape-key) row with compile wall-ms, HLO
        flops / bytes-accessed, and argument/output/temp/peak memory,
        plus the recompile-sentinel totals (doc/observability.md
        "Programs, memory, and MFU")."""
        import json

        from .obs.programs import get_ledger
        return json.dumps(get_ledger().view(), sort_keys=True,
                          default=str)

    def autotune(self, spec: str, probe_fn, baseline=None,
                 task: str = 'train') -> str:
        """Run the grafttune two-stage search (doc/autotune.md) over an
        ``autotune=`` spec string with a caller-supplied measured probe
        — ``probe_fn(candidate_dict) -> score`` (higher is better) —
        and return the receipt as one JSON object.  The embedding owns
        probe execution (it knows what a representative workload is);
        stage-1 ledger pruning and the budgeted stage-2 sweep are the
        library's.  The tuned knobs are ``receipt['best']``."""
        import json

        from .tune import TuneSearch, TuneSpace
        space = TuneSpace.parse(spec)
        result = TuneSearch(space, probe_fn,
                            baseline=baseline).run(task)
        return json.dumps(result.receipt(), sort_keys=True, default=str)

    # --- weight access (visitor equivalent) -------------------------------
    def _resolve(self, layer_name: str):
        tr = self._require()
        idx = tr.net_cfg.get_layer_index(layer_name)
        return tr, idx, tr.net_cfg.layers[idx].type

    def get_weight(self, layer_name: str, tag: str) -> Optional[np.ndarray]:
        if tag not in ('bias', 'wmat'):
            raise ValueError('tag must be bias or wmat')
        tr, idx, type_id = self._resolve(layer_name)
        rec = tr.params.get(str(idx), {})
        if tag not in rec:
            return None
        arr = np.asarray(jax.device_get(rec[tag]), np.float32)
        layer = tr.net.layers[idx]
        return checkpoint.to_disk_layout(type_id, tag, arr,
                                         layer.param.num_group)

    def set_weight(self, weight: np.ndarray, layer_name: str,
                   tag: str) -> None:
        if tag not in ('bias', 'wmat'):
            raise ValueError('tag must be bias or wmat')
        tr, idx, type_id = self._resolve(layer_name)
        key = str(idx)
        if key not in tr.params or tag not in tr.params[key]:
            raise KeyError(f'layer {layer_name} has no weight {tag}')
        layer = tr.net.layers[idx]
        mem = checkpoint.from_disk_layout(
            type_id, tag, np.asarray(weight, np.float32), layer)
        if mem.shape != tr.params[key][tag].shape:
            raise ValueError(
                f'set_weight: shape {mem.shape} != '
                f'{tr.params[key][tag].shape}')
        params = dict(tr.params)
        params[key] = dict(params[key])
        params[key][tag] = jax.device_put(mem,
                                          tr.params[key][tag].sharding)
        tr.params = params


def train_iter(cfg: str, data: DataIter, num_round: int, param,
               eval_data: Optional[DataIter] = None) -> Net:
    """Module-level train helper over a DataIter (wrapper/cxxnet.py:281)."""
    net = Net(cfg=cfg)
    if isinstance(param, dict):
        param = param.items()
    for k, v in param:
        net.set_param(k, v)
    net.init_model()
    for r in range(num_round):
        net.start_round(r)
        data.before_first()
        counter = 0
        while data.next():
            net.update(data)
            counter += 1
            if counter % 100 == 0:
                print(f'[{r}] {counter} batch passed')
        if eval_data is not None:
            sys.stderr.write(net.evaluate(eval_data, 'eval') + '\n')
    return net


def train(cfg: str, data, label, num_round: int, param) -> Net:
    """Module-level train helper over a numpy batch (wrapper/cxxnet.py:300)."""
    net = Net(cfg=cfg)
    if isinstance(param, dict):
        param = param.items()
    for k, v in param:
        net.set_param(k, v)
    net.init_model()
    for r in range(num_round):
        net.start_round(r)
        net.update(data=data, label=label)
    return net


class LMServe:
    """Python-embedder surface for the continuous-batching decode stack
    (doc/serving.md "Continuous decode") — the LM counterpart of
    :class:`Net`'s serving surface, and the object the flat C ABI's
    ``lm_serve_*`` calls hand around (capi.py delegates here).

    Built from a compact ``k=v[;k=v...]`` spec: model
    ``vocab``/``d_model``/``heads``/``d_ff``/``stages``/``experts``,
    params from ``model_in`` (a ``%04d.lm`` tree) or ``seed`` init,
    engine shape ``slots``/``pages``/``page_size``/``max_prompt``/
    ``max_new``/``eos``, batcher knobs ``max_queue``/``max_wait``/
    ``deadline``, serving tier ``dtype`` (``f32``/``bf16``/``int8``), prefix
    sharing ``prefix_share`` (index page cap, 0 = off; doc/serving.md
    "Prefix sharing"), and greedy speculative decoding ``spec_k`` plus
    ``draft.*`` keys (``draft.d_model=16;draft.stages=1;draft.seed=1``
    or ``draft.model_in=`` — the draft's vocab defaults to the
    target's; doc/serving.md "Speculative decoding"), and the graftcache
    KV tiers ``kv_host_mb`` / ``kv_disk_mb`` / ``kv_dir`` /
    ``kv_share_dir`` (doc/serving.md "Tiered KV cache"; tiers need
    ``prefix_share`` on), plus graftshard's ``shard=tp:N`` tensor-
    parallel decode and ``prefill_workers=N`` disaggregated prefill
    (doc/serving.md "Sharded serving")."""

    def __init__(self, svc):
        self.svc = svc

    @classmethod
    def from_spec(cls, cfg: str) -> 'LMServe':
        from .models import transformer as T
        from .serve.decode import DecodeService, load_lm_params
        from .utils.config import parse_kv_list

        def build_model(kw, model_in, seed):
            tcfg = T.TransformerConfig(**kw)
            params = (load_lm_params(model_in) if model_in
                      else T.init_params(np.random.RandomState(seed),
                                         tcfg))
            return params, tcfg

        cfg_kw = {'attn': 'local'}
        draft_kw = {'attn': 'local'}
        svc_kw = {}
        seed, model_in, eos = 0, None, None
        draft_seed, draft_model_in, has_draft = 0, None, False
        names = {'vocab': 'vocab_size', 'd_model': 'd_model',
                 'heads': 'num_heads', 'd_ff': 'd_ff',
                 'stages': 'num_stages', 'experts': 'num_experts',
                 'seq': 'seq_len'}
        ints = ('slots', 'pages', 'page_size', 'max_prompt', 'max_queue',
                'prefix_share', 'spec_k', 'kv_host_mb', 'kv_disk_mb',
                'prefill_workers')
        for key, val in parse_kv_list(cfg or ''):
            if key in names:
                cfg_kw[names[key]] = int(val)
            elif key in ints:
                svc_kw[key] = int(val)
            elif key == 'max_new':
                svc_kw['max_new_bound'] = int(val)
            elif key in ('max_wait', 'deadline'):
                svc_kw[key] = float(val)
            elif key == 'seed':
                seed = int(val)
            elif key == 'model_in':
                model_in = val
            elif key == 'eos':
                eos = None if int(val) < 0 else int(val)
            elif key == 'dtype':
                svc_kw['dtype'] = val
            elif key in ('kv_dir', 'kv_share_dir'):
                svc_kw[key] = val
            elif key == 'shard':
                svc_kw['shard'] = val
            elif key.startswith('draft.'):
                has_draft = True
                sub = key[len('draft.'):]
                if sub in names:
                    draft_kw[names[sub]] = int(val)
                elif sub == 'seed':
                    draft_seed = int(val)
                elif sub == 'model_in':
                    draft_model_in = val
                else:
                    raise ValueError(f'unknown lm_serve option: {key!r}')
            else:
                raise ValueError(f'unknown lm_serve option: {key!r}')
        params, tcfg = build_model(cfg_kw, model_in, seed)
        if has_draft:
            draft_kw.setdefault('vocab_size', tcfg.vocab_size)
            svc_kw['draft'] = build_model(draft_kw, draft_model_in,
                                          draft_seed)
        return cls(DecodeService(params, tcfg, eos_id=eos, **svc_kw))

    # --- DecodeService delegation (the capi duck-type surface) ------------
    @property
    def engine(self):
        return self.svc.engine

    @property
    def batcher(self):
        return self.svc.batcher

    def generate(self, prompt, max_new: int, temperature: float = 0.0,
                 rng=None, deadline: Optional[float] = None) -> np.ndarray:
        return self.svc.generate(prompt, max_new, temperature, rng,
                                 deadline)

    def autoscale(self, policy: str):
        """Attach an SLO-driven autoscaler (``serve.autoscale=``
        grammar, doc/serving.md "Scenarios and autoscaling") over this
        service's live admission caps; returns the
        :class:`~cxxnet_tpu.serve.autoscale.Autoscaler` (call its
        ``evaluate()`` per tick when ``interval=0``, or let its
        ``interval>0`` thread run; ``close()`` detaches)."""
        from .obs import get_hub
        from .serve.autoscale import AutoscalePolicy, Autoscaler
        scaler = Autoscaler(AutoscalePolicy.parse(policy))
        scaler.bind_engine(self.svc.engine)
        scaler.bind_batcher(self.svc.batcher)
        scaler.register_into(get_hub())
        return scaler

    def run_scenario(self, spec: str, time_scale: float = 1.0,
                     on_tick=None) -> dict:
        """Drive a seeded traffic scenario (``serve.scenario=``
        grammar) against this service and return the reconciled
        ledger's summary dict (submitted / per-bucket counts / p50 /
        p99).  Deterministic: the same spec replays the same storm."""
        from .serve.scenario import ScenarioLedger, ScenarioSpec, drive
        sspec = ScenarioSpec.parse(spec)
        base = ScenarioLedger.stat_snapshot(self.engine.stats)
        led = drive(self.svc, sspec, vocab=self.engine.cfg.vocab_size,
                    on_tick=on_tick, time_scale=time_scale)
        led.reconcile(self.engine.stats, base=base)
        return led.summary()

    def report(self, name: str = 'decode') -> str:
        return self.svc.report(name)

    def close(self, timeout: Optional[float] = None) -> None:
        self.svc.close(timeout)
