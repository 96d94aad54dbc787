"""Config-file-driven task CLI.

Equivalent of the reference driver (``src/cxxnet_main.cpp:16-478``)::

    python -m cxxnet_tpu.main config.conf [k=v ...]

Tasks (``task=``): ``train`` (default), ``finetune``, ``pred``,
``pred_raw``, ``extract``.
Counter/checkpoint choreography preserved: model files are
``model_dir/%04d.model`` with an int ``net_type`` prefix; ``continue=1``
scans forward from ``start_counter`` to resume from the newest checkpoint
(``cxxnet_main.cpp:135-157``); eval output goes to **stderr** as
``[round]\\tname-metric:value``; ``test_io=1`` runs the loop without compute.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional, Tuple

from .io.data import create_iterator
from .nnet import checkpoint as model_io
from .nnet.trainer import NetTrainer
from .obs import span
from .utils.backend import enable_compile_cache
from .utils.config import apply_cli_overrides, parse_config_file
from .utils.profiler import TraceWindow

ConfigEntry = Tuple[str, str]

#: task= name -> the LearnTask method that runs it (serve.mode=decode
#: has its own, see run)
_TASKS = {'train': 'task_train', 'finetune': 'task_train',
          'pred': 'task_predict', 'pred_raw': 'task_predict_raw',
          'extract': 'task_extract', 'serve': 'task_serve',
          'online': 'task_online', 'autotune': 'task_autotune'}


def _spanned_batches(batches):
    """``batches``, with each wait for the next one inside an ``io.next``
    span: what the step loop pays the input chain, serial or pooled (the
    chain's own counters exist only with ``nworker``)."""
    it = iter(batches)
    while True:
        with span('io.next', 'io'):
            batch = next(it, None)
        if batch is None:
            return
        yield batch


class LearnTask:
    def __init__(self):
        self.task = 'train'
        self.net_type = 0
        self.reset_net_type = -1
        self.print_step = 100
        self.continue_training = 0
        self.save_period = 1
        self.start_counter = 0
        self.name_model_in = 'NULL'
        self.name_model_dir = 'models'
        self.num_round = 10
        self.max_round = 2147483647
        self.silent = 0
        self.device = 'tpu'
        self.test_io = 0
        self.exact_ckpt = 0
        # fault-tolerant runtime knobs (doc/fault_tolerance.md)
        self.fault_plan = ''           # train.fault_plan grammar
        self.supervise = 0             # train.supervise=1 -> TrainSupervisor
        self.watchdog_deadline = 60.0  # train.watchdog_deadline (s, 0=off)
        self.max_restarts = 3          # train.max_restarts per round
        self.nan_breaker = 3           # train.nan_breaker (consecutive NaNs)
        self.save_every = 0            # train.save_every (steps, 0=per-round)
        self.keep_last = 4             # train.keep_last ckpts kept (0=all)
        self.save_async = 0            # save_async=1 -> background ckpt
                                       # writer (doc/fault_tolerance.md);
                                       # final save always barriers
        self.save_workers = 2          # save_workers per-save write threads
        self._async_ckpt = None        # lazy AsyncCheckpointer
        # scanned hot loop: K staged batches per device dispatch
        # (doc/trainer.md; steps_per_dispatch=1 = per-step reference path)
        self.steps_per_dispatch = 1
        self.scan_strict = 0           # 1 = a demotion raises
                                       # ScanStrictError instead of
                                       # silently falling back per-step
        # graftfuse: μ-cuDNN-style conv microbatching (doc/kernels.md);
        # replayed into every conv layer as a netconfig global — this
        # attr only anchors the autotuner's baseline candidate
        self.micro_batch = 1
        # grafttune: task=autotune searches this declared space
        # (doc/autotune.md); parsed at init so a bad spec fails fast
        self.autotune = ''
        self._tune_space = None
        self._data_itcfg = None        # captured data-section config so
        self._data_defcfg = []         # the tuner can rebuild the train
                                       # iterator at a candidate nworker
        self.extract_node_name = ''
        self.name_pred = 'pred.txt'
        self.output_format = 1
        # online serving knobs (task=serve, doc/serving.md)
        self.serve_buckets = '1,8,32'  # serve.buckets batch-size ladder
        self.serve_max_queue = 64      # serve.max_queue admission bound
        self.serve_max_wait = 0.002    # serve.max_wait coalesce window (s)
        self.serve_deadline = 1.0      # serve.deadline per-request (s)
        self.serve_reload = 0.0        # serve.reload poll period (s, 0=off)
        # continuous decode + multi-model fleet (doc/serving.md)
        self.serve_mode = 'predict'    # serve.mode: predict | decode
        self.serve_slots = 4           # serve.slots decode step width
        self.serve_pages = 64          # serve.pages KV pool (physical pages)
        self.serve_page_size = 16      # serve.page_size tokens per page
        self.serve_max_prompt = 64     # serve.max_prompt longest prompt
        self.serve_max_new = 16        # serve.max_new decode horizon/bound
        self.serve_eos = -1            # serve.eos id (-1 = none)
        self.serve_lm = ''             # serve.lm transformer spec (k=v;...)
        self.serve_lm_seed = 0         # serve.lm_seed init seed (no model_in)
        self.serve_lm_model_in = 'NULL'  # serve.lm_model_in %04d.lm file
        self.serve_requests = 16       # serve.requests decode drive size
        self.serve_temperature = 0.0   # serve.temperature decode sampling
        self.serve_seed = 0            # serve.seed drive prompt/rng seed
        self.serve_models = ''         # serve.models fleet: id=dir;id=dir
        self.serve_mem_budget = 0      # serve.mem_budget bytes (0 = off)
        self.serve_dtype = 'f32'       # serve.dtype: f32 | bf16 | int8
        self.serve_fold_bn = 0         # serve.fold_bn: 1 = fold conv+BN
                                       # at engine build (doc/kernels.md)
        self.serve_prefix_share = 0    # serve.prefix_share index pages (0=off)
        # graftcache: tiered KV prefix cache (doc/serving.md "Tiered KV
        # cache"); tiers need serve.prefix_share > 0
        self.serve_kv_host_mb = 0      # serve.kv_host_mb tier-1 RAM (0=off)
        self.serve_kv_disk_mb = 0      # serve.kv_disk_mb tier-2 disk (0=off)
        self.serve_kv_dir = ''         # serve.kv_dir tier-2 record dir
        self.serve_kv_share_dir = ''   # serve.kv_share_dir cross-replica
        self.serve_spec_k = 0          # serve.spec_k window width (0/1=off)
        self.serve_draft = ''          # serve.draft spec (k=v;... like serve.lm)
        # graftshard: mesh-sharded decode + disaggregated prefill +
        # data-parallel predict replicas (doc/serving.md "Sharded serving")
        self.serve_shard = ''          # serve.shard tp:N decode tensor split
        self.serve_prefill_workers = 0  # serve.prefill_workers threads (0=inline)
        self.serve_replicas = 0        # serve.replicas predict DP (0/1=single)
        # graftstorm: adversarial traffic + SLO-driven autoscaling
        self.serve_scenario = ''       # serve.scenario spec (shape=...;seed=...)
        self.serve_autoscale = ''      # serve.autoscale policy (min_slots=...;...)
        # train-while-serve (task=online, doc/online.md); batcher shape
        # comes from the serve.* keys above
        self.online_save_every = 8     # online.save_every steps/checkpoint
        self.online_freshness_slo = 0.0  # online.freshness_slo seconds
        self.online_freshness_strict = 0  # online.freshness_strict 1=raise
        self.online_reload = 0.05      # online.reload registry poll (s)
        self.online_qps = 50.0         # online.qps traffic driver rate
        # elastic multi-host training (doc/fault_tolerance.md
        # "Multi-host recovery"); hosts>0 turns the elastic runtime on
        self.dist_hosts = 0            # dist.hosts worker-host count
        self.dist_rank = -1            # dist.rank (-1 = launcher role)
        self.dist_coordinator = ''     # dist.coordinator host:port
        self.dist_heartbeat = 2.0      # dist.heartbeat seconds
        self.dist_rejoin = 2           # dist.rejoin respawn budget
        self.dist_shards = 0           # dist.shards micro-shards (0=hosts)
        self.dist_sync_timeout = 60.0  # dist.sync_timeout seconds
        self.dist_launch = 0           # dist.launch=1 forces launcher role
        # graftscope telemetry (doc/observability.md)
        self.obs_port = -1             # obs.port: -1 off, 0 ephemeral, >0 fixed
        self.obs_trace_export = ''     # obs.trace_export Chrome-trace path
        self.obs_ring_events = 4096    # obs.ring_events flight-recorder ring
        self.obs_dump_dir = ''         # obs.dump_dir ('' = model_dir/flight)
        # graftwatch: gauge history sampler + declarative SLO engine
        # (doc/observability.md "SLOs and burn rates" / "Fleet view")
        self.obs_sample_every = 0.0    # obs.sample_every s (0 = auto: on
                                       # at 0.25s only when slo.* given)
        self.obs_fleet_port = -1       # obs.fleet_port launcher merged
                                       # endpoint: -1 off, 0 ephemeral
        self.obs_trace_merge = ''      # obs.trace_merge merged Perfetto
                                       # trace path (launcher role)
        # graftprof: compiler-truth ledger + device memory + /profile
        # (doc/observability.md "Programs, memory, and MFU")
        self.obs_recompile = 'warn'    # obs.recompile: warn | raise | off
        self.obs_profile = 1           # obs.profile: /profile?ms=N on
        self.obs_hbm = 1               # obs.hbm: hbm.* device gauges on
        self.slo_specs: List[ConfigEntry] = []   # slo.<name> grammar
        self._obs_server = None
        self._obs_sampler = None
        self._obs_slo = None
        self._train_stats = None       # train-mfu/steps_per_sec gauges
        self.cfg: List[ConfigEntry] = []
        self.net_trainer: Optional[NetTrainer] = None
        self.itr_train = None
        self.itr_evals = []
        self.eval_names = []
        self.itr_pred = None

    def set_param(self, name: str, val: str) -> None:
        if val == 'default':
            return
        simple = {
            'net_type': ('net_type', int), 'reset_net_type': ('reset_net_type', int),
            'print_step': ('print_step', int), 'continue': ('continue_training', int),
            'save_model': ('save_period', int), 'start_counter': ('start_counter', int),
            'model_in': ('name_model_in', str), 'model_dir': ('name_model_dir', str),
            'num_round': ('num_round', int), 'max_round': ('max_round', int),
            'silent': ('silent', int), 'task': ('task', str), 'dev': ('device', str),
            'test_io': ('test_io', int), 'extract_node_name': ('extract_node_name', str),
            'exact_ckpt': ('exact_ckpt', int),
            'train.fault_plan': ('fault_plan', str),
            'train.supervise': ('supervise', int),
            'train.watchdog_deadline': ('watchdog_deadline', float),
            'train.max_restarts': ('max_restarts', int),
            'train.nan_breaker': ('nan_breaker', int),
            'train.save_every': ('save_every', int),
            'train.keep_last': ('keep_last', int),
            'save_async': ('save_async', int),
            'save_workers': ('save_workers', int),
            'steps_per_dispatch': ('steps_per_dispatch', int),
            'train.steps_per_dispatch': ('steps_per_dispatch', int),
            'scan_strict': ('scan_strict', int),
            'train.scan_strict': ('scan_strict', int),
            'micro_batch': ('micro_batch', int),
            'train.micro_batch': ('micro_batch', int),
            'serve.buckets': ('serve_buckets', str),
            'serve.max_queue': ('serve_max_queue', int),
            'serve.max_wait': ('serve_max_wait', float),
            'serve.deadline': ('serve_deadline', float),
            'serve.reload': ('serve_reload', float),
            'serve.mode': ('serve_mode', str),
            'serve.slots': ('serve_slots', int),
            'serve.pages': ('serve_pages', int),
            'serve.page_size': ('serve_page_size', int),
            'serve.max_prompt': ('serve_max_prompt', int),
            'serve.max_new': ('serve_max_new', int),
            'serve.eos': ('serve_eos', int),
            'serve.lm': ('serve_lm', str),
            'serve.lm_seed': ('serve_lm_seed', int),
            'serve.lm_model_in': ('serve_lm_model_in', str),
            'serve.requests': ('serve_requests', int),
            'serve.temperature': ('serve_temperature', float),
            'serve.seed': ('serve_seed', int),
            'serve.models': ('serve_models', str),
            'serve.mem_budget': ('serve_mem_budget', int),
            'serve.dtype': ('serve_dtype', str),
            'serve.fold_bn': ('serve_fold_bn', int),
            'serve.prefix_share': ('serve_prefix_share', int),
            'serve.kv_host_mb': ('serve_kv_host_mb', int),
            'serve.kv_disk_mb': ('serve_kv_disk_mb', int),
            'serve.kv_dir': ('serve_kv_dir', str),
            'serve.kv_share_dir': ('serve_kv_share_dir', str),
            'serve.spec_k': ('serve_spec_k', int),
            'serve.draft': ('serve_draft', str),
            'serve.shard': ('serve_shard', str),
            'serve.prefill_workers': ('serve_prefill_workers', int),
            'serve.replicas': ('serve_replicas', int),
            'serve.scenario': ('serve_scenario', str),
            'serve.autoscale': ('serve_autoscale', str),
            'dist.hosts': ('dist_hosts', int),
            'dist.rank': ('dist_rank', int),
            'dist.coordinator': ('dist_coordinator', str),
            'dist.heartbeat': ('dist_heartbeat', float),
            'dist.rejoin': ('dist_rejoin', int),
            'dist.shards': ('dist_shards', int),
            'dist.sync_timeout': ('dist_sync_timeout', float),
            'dist.launch': ('dist_launch', int),
            'obs.port': ('obs_port', int),
            'obs.trace_export': ('obs_trace_export', str),
            'obs.ring_events': ('obs_ring_events', int),
            'obs.dump_dir': ('obs_dump_dir', str),
            'obs.sample_every': ('obs_sample_every', float),
            'obs.fleet_port': ('obs_fleet_port', int),
            'obs.trace_merge': ('obs_trace_merge', str),
            'obs.recompile': ('obs_recompile', str),
            'obs.profile': ('obs_profile', int),
            'obs.hbm': ('obs_hbm', int),
            'online.save_every': ('online_save_every', int),
            'online.freshness_slo': ('online_freshness_slo', float),
            'online.freshness_strict': ('online_freshness_strict', int),
            'online.reload': ('online_reload', float),
            'online.qps': ('online_qps', float),
            'autotune': ('autotune', str),
        }
        if name in simple:
            attr, typ = simple[name]
            setattr(self, attr, typ(val))
        if name == 'obs.recompile' and val not in ('warn', 'raise', 'off'):
            # fail at config parse, like a malformed slo.* spec
            raise ValueError(
                f'obs.recompile must be warn|raise|off, got {val!r}')
        if name.startswith('slo.') and len(name) > 4:
            # declarative SLO grammar (doc/observability.md):
            # slo.<name> = <set>.<key><op><threshold>@<window>[:burn];
            # fleet.-scoped specs evaluate at the elastic launcher.
            # Validated here so a bad spec fails at config parse, and
            # @0 rejected outright: per-sample specs are fed through
            # SLOEngine.observe by in-process code (the freshness
            # path) — from the CLI one would never evaluate, a dead
            # objective reading OK forever
            from .obs.slo import SLOSpec
            spec = SLOSpec.parse(name[4:], val)
            if spec.window <= 0:
                raise ValueError(
                    f'{name}: @0 per-sample specs are engine-API-only '
                    f'(SLOEngine.observe); give a window > 0 seconds')
            self.slo_specs.append((name[4:], val))
        if name == 'output_format':
            self.output_format = 1 if val == 'txt' else 0
        self.cfg.append((name, val))

    # --- setup ------------------------------------------------------------
    def _create_net(self) -> NetTrainer:
        if self.reset_net_type != -1:
            self.net_type = self.reset_net_type
        cfg = self.cfg
        if self.task == 'serve':
            # serving never trains: skip optimizer-state allocation
            cfg = cfg + [('inference_only', '1')]
        return NetTrainer(cfg)

    def _model_path(self, counter: int) -> str:
        return os.path.join(self.name_model_dir, f'{counter:04d}.model')

    def _sync_latest_model(self) -> bool:
        """Adopt the newest ``%04d.model`` at or past ``start_counter``.
        Gap-tolerant by design: ``task=online`` publishes checkpoints
        named by STEP on the supervisor's save cadence (0008, 0016, ...),
        so the reference's consecutive-counter walk would stop at the
        first hole and miss every online checkpoint — the newest-file
        scan is the one the serving registry already trusts."""
        from .serve.registry import newest_model_file
        best = newest_model_file(self.name_model_dir)
        if best is None or best[0] < self.start_counter:
            return False
        counter, last = best

        def _read(f):
            self.net_type = int.from_bytes(f.read(4), 'little', signed=True)
            self.net_trainer = self._create_net()
            self.net_trainer.load_model(f)

        model_io.read_model_file(last, _read)
        self.start_counter = counter + 1
        if self.exact_ckpt:
            from .nnet.sharded_ckpt import step_dir
            # ask for EXACTLY the loaded model's step: newer leftover
            # sidecars (e.g. after rolling back by deleting model files)
            # must not block restoring the matching one
            if os.path.isdir(step_dir(self._exact_dir(), counter)):
                self.net_trainer.load_training_state(self._exact_dir(),
                                                     counter)
                if not self.silent:
                    print(f'Init: exact optimizer state restored from '
                          f'{self._exact_dir()} step {counter}', flush=True)
            elif not self.silent:
                print(f'Init: no exact state for step {counter} — resuming '
                      f'with reset momentum (reference behavior)',
                      flush=True)
        return True

    def _load_model(self) -> None:
        base = os.path.basename(self.name_model_in)
        stem = base.split('.')[0]
        if stem.isdigit():
            self.start_counter = int(stem)

        def _read(f):
            self.net_type = int.from_bytes(f.read(4), 'little', signed=True)
            self.net_trainer = self._create_net()
            self.net_trainer.load_model(f)

        model_io.read_model_file(self.name_model_in, _read)
        self.start_counter += 1

    def _copy_model(self) -> None:
        self.net_trainer = self._create_net()

        def _read(f):
            f.read(4)
            self.net_trainer.copy_model_from(f)

        model_io.read_model_file(self.name_model_in, _read)

    def _exact_dir(self) -> str:
        return os.path.join(self.name_model_dir, 'exact_state')

    def _ckpt(self):
        """The CLI's background checkpoint writer (``save_async=1``)."""
        if self._async_ckpt is None:
            from .runtime.async_ckpt import AsyncCheckpointer
            self._async_ckpt = AsyncCheckpointer(workers=self.save_workers)
        return self._async_ckpt

    def _prune_exact(self, counter: int) -> None:
        # only the sidecar matching the newest model file is ever
        # restored: prune older ones (~3x model size each)
        from .nnet.sharded_ckpt import step_dir
        import shutil
        for old in range(counter):
            d = step_dir(self._exact_dir(), old)
            if os.path.isdir(d):
                shutil.rmtree(d, ignore_errors=True)

    def _save_model(self) -> None:
        counter = self.start_counter
        path = self._model_path(counter)
        self.start_counter += 1
        if self.save_period == 0 or self.start_counter % self.save_period != 0:
            return
        os.makedirs(self.name_model_dir, exist_ok=True)
        if self.save_async:
            self._save_model_async(counter, path)
            return

        def _write(f):
            f.write(int(self.net_type).to_bytes(4, 'little', signed=True))
            self.net_trainer.save_model(f)

        # atomic (temp+fsync+rename) + retried: a crash mid-save can never
        # leave a truncated file where continue=1 would load it
        model_io.save_model_file(path, _write)
        # integrity sidecar for hot-reloading servers (serve/registry.py
        # digest-verifies before swapping a checkpoint into live traffic)
        model_io.write_model_digest(path)
        if self.exact_ckpt:
            # beyond reference: sidecar with optimizer state + counters so
            # continue=1 resumes bit-exact mid-momentum (the reference
            # model file drops momentum by design — trainer.save_model)
            self.net_trainer.save_training_state(self._exact_dir(), counter)
            self._prune_exact(counter)

    def _save_model_async(self, counter: int, path: str) -> None:
        """``save_async=1``: the round boundary only snapshots (donation-
        safe device copies + the cheap config header); serialization and
        the atomic+retried+digested writes run on the background writer.
        Same bytes, same crash contract as the sync path — the next round
        starts without waiting on storage.  ``run()`` barriers before
        exit, so the last model file is always durable."""
        from .nnet.trainer import NetTrainer
        from .runtime import async_ckpt
        tr = self.net_trainer
        header = (int(self.net_type).to_bytes(4, 'little', signed=True)
                  + tr.model_header())
        net = tr.net
        # one param snapshot per boundary: the exact-resume tree already
        # carries a params copy, so the model blob serializes from it
        tsnap = tr.snapshot_training_state() if self.exact_ckpt else None
        psnap = (tsnap['params'] if tsnap is not None
                 else async_ckpt.snapshot_tree(tr.params))
        exact_dir = self._exact_dir()
        ck = self._ckpt()

        def job():
            blob = model_io.serialize_blob(net, async_ckpt.host_tree(psnap))
            model_io.save_model_file(
                path, lambda f: NetTrainer.write_model_bytes(f, header,
                                                             blob))
            model_io.write_model_digest(path)
            if tsnap is not None:
                from .nnet import sharded_ckpt
                sharded_ckpt.save_tree_native(exact_dir, counter, tsnap,
                                              pool=ck.io_pool)
                self._prune_exact(counter)

        ck.submit(job, step=counter, label=f'save_model:{counter:04d}')

    def _create_iterators(self) -> None:
        flag = 0
        evname = ''
        itcfg: List[ConfigEntry] = []
        defcfg: List[ConfigEntry] = []
        for name, val in self.cfg:
            if name == 'data':
                flag = 1
                continue
            if name == 'eval':
                evname = val
                flag = 2
                continue
            if name == 'pred':
                flag = 3
                self.name_pred = val
                continue
            if name == 'iter' and val == 'end':
                assert flag != 0, 'wrong configuration file'
                if flag == 1 and self.task not in ('pred', 'pred_raw',
                                                   'serve'):
                    assert self.itr_train is None, 'can only have one data'
                    self.itr_train = create_iterator(itcfg)
                    # grafttune nworker probes rebuild this iterator at
                    # candidate worker counts (doc/autotune.md)
                    self._data_itcfg = list(itcfg)
                if flag == 2 and self.task not in ('pred', 'pred_raw',
                                                   'serve'):
                    self.itr_evals.append(create_iterator(itcfg))
                    self.eval_names.append(evname)
                if flag == 3 and self.task in ('pred', 'pred_raw', 'extract',
                                               'serve', 'online'):
                    assert self.itr_pred is None, 'only one pred section'
                    self.itr_pred = create_iterator(itcfg)
                flag = 0
                itcfg = []
                continue
            if flag == 0:
                defcfg.append((name, val))
            else:
                itcfg.append((name, val))
        self._data_defcfg = list(defcfg)
        for it in ([self.itr_train] if self.itr_train else []) + \
                ([self.itr_pred] if self.itr_pred else []) + self.itr_evals:
            for name, val in defcfg:
                it.set_param(name, val)
            it.init()

    def init(self) -> None:
        if self.task == 'autotune':
            # parse the space NOW so a malformed spec fails at init like
            # a bad slo.*/scenario spec, not mid-search
            from .tune import TuneSpace
            self._tune_space = TuneSpace.parse(self.autotune)
            if self._tune_space.mode == 'decode':
                # decode candidates build their own engines from the
                # serve.lm spec — no netconfig model, like serve decode
                self._create_iterators()
                return
            # mode=train falls through: the probe path needs the real
            # NetTrainer + train iterator
        if self.task == 'serve' and self.serve_mode == 'decode':
            # the decode stack serves a transformer LM tree (serve.lm /
            # serve.lm_model_in), not a netconfig model: no NetTrainer
            self._create_iterators()
            return
        if self.task == 'online' and self.continue_training:
            # resume a train-while-serve run: online model files are
            # named by STEP (the supervisor's save cadence), not round —
            # adopt the newest and re-arm the publish counter so new
            # checkpoints continue strictly past it instead of
            # re-publishing (and re-serving) stale counter names
            if not self._sync_latest_model():
                raise RuntimeError(
                    'Init: cannot find models to continue the online run; '
                    'start fresh or specify model_in')
            self.net_trainer.sample_counter = self.start_counter - 1
            print(f'Init: continue online run from step '
                  f'{self.net_trainer.sample_counter}')
            self._create_iterators()
            return
        if self.task == 'train' and self.continue_training:
            if not self._sync_latest_model():
                raise RuntimeError(
                    'Init: cannot find models to continue training; '
                    'specify model_in instead')
            print(f'Init: Continue training from round {self.start_counter}')
            self._create_iterators()
            return
        self.continue_training = 0
        if self.name_model_in == 'NULL':
            assert self.task in ('train', 'online', 'autotune'), \
                'must specify model_in if not training'
            self.net_trainer = self._create_net()
            self.net_trainer.init_model()
        elif self.task == 'finetune':
            self._copy_model()
        else:
            self._load_model()
        self._create_iterators()

    # --- tasks ------------------------------------------------------------
    def task_train(self) -> None:
        if self.dist_hosts > 0:
            if self.task != 'train':
                # never silently train single-host when the config asked
                # for a fleet (the same contract as maybe_init_distributed)
                raise ValueError(
                    f'dist.hosts={self.dist_hosts} supports task=train '
                    f'only (got task={self.task}); drop the dist.* keys '
                    'or switch the task')
            # elastic multi-host worker (or the in-process single-host
            # twin); the launcher role never reaches here — run()
            # dispatches it before init()
            from .parallel.elastic import elastic_train
            elastic_train(self)
            return
        start = time.monotonic()
        if self.continue_training == 0 and self.name_model_in == 'NULL':
            self._save_model()
        else:
            for it, name in zip(self.itr_evals, self.eval_names):
                sys.stderr.write(self.net_trainer.evaluate(it, name))
            sys.stderr.write('\n')
            sys.stderr.flush()
        if self.itr_train is None:
            return
        if self.test_io:
            print('start I/O test')
        tracer = TraceWindow(hlo_text=self.net_trainer.step_program_text)
        tracer.configure(self.cfg)
        batch_counter = 0
        try:
            self._train_rounds(tracer, batch_counter, start)
        finally:
            tracer.stop()
            if self._async_ckpt is not None:
                # the FINAL save always barriers: a deferred write error
                # surfaces here (like the sync path's, rounds late), and
                # the newest model file is durable before the CLI returns
                try:
                    self._async_ckpt.wait()
                finally:
                    self._async_ckpt.close(wait=False)
                    self._async_ckpt = None

    def _make_supervisor(self):
        from .io.data import ThreadBufferIterator
        from .runtime import faults
        from .runtime.supervisor import SupervisorConfig, TrainSupervisor
        # the supervisor brings its own watchdog ThreadBuffer, so a
        # conf-level `iter = threadbuffer` stage is unwrapped: batches
        # would otherwise be double-buffered, and two producers would
        # both register the 'batch' fault scope with different index
        # bases — one-shot stall events would land on whichever thread
        # races to the index first
        self._sup_iter = self.itr_train
        if isinstance(self._sup_iter, ThreadBufferIterator):
            self._sup_iter = self._sup_iter.base
        if self._sup_iter is not None \
                and not self._sup_iter.is_replay_stable():
            msg = ('train iterator reshuffles per pass (shuffle=1): '
                   'recovery restores exact params, but the replayed '
                   'pass draws a fresh permutation — the run is NOT '
                   'bitwise-identical to an uninterrupted one')
            faults.global_failure_log().record('replay_unstable', msg)
            if not self.silent:
                print(f'TrainSupervisor: {msg}', flush=True)
        cfg = SupervisorConfig(
            batch_deadline=self.watchdog_deadline or None,
            max_restarts=self.max_restarts,
            nan_breaker=self.nan_breaker,
            save_every=self.save_every,
            keep_last=self.keep_last,
            save_async=self.save_async,
            save_workers=self.save_workers,
            # pooled chains (nworker) report the watchdog's stalls on
            # the chain StatSet and get the doubled first-batch grace
            pipeline_stats=(None if self._sup_iter is None
                            else self._sup_iter.pipeline_stats()))
        return TrainSupervisor(
            self.net_trainer,
            os.path.join(self.name_model_dir, 'supervised_state'), cfg)

    def _supervised_round(self, sup, plan, tracer, batch_counter,
                          start) -> int:
        """One round's batches under the supervisor: watchdog on the
        pipeline, divergence breaker on the loss, restore-and-resume from
        the exact sidecar on recoverable faults.  ``batch_factory(k)``
        re-winds a fresh epoch pass to batch k after a restore — k counts
        DISPATCHED steps (epoch-absolute), so recovery composes with the
        scanned window (a fault mid-window abandons staged batches and
        re-pulls them); bitwise recovery additionally needs a
        replay-stable iterator (``is_replay_stable`` — _make_supervisor
        warns otherwise).  The supervised per-step path dispatches
        immediately (lookahead=0); the scanned path's K-deep staging
        window provides the H2D overlap instead."""
        import itertools
        it = self._sup_iter

        def factory(k):
            return _spanned_batches(itertools.islice(iter(it), k, None))

        def before_step(i):
            # same progress/trace cadence as the unsupervised loop
            tracer.before_update(batch_counter + i)
            self._progress(i + 1, start)

        return sup.run(
            factory, before_step=before_step,
            make_stepper=lambda: plan.round_stepper(self.net_trainer,
                                                    lookahead=0))

    def _train_rounds(self, tracer, batch_counter, start) -> None:
        from .nnet.execution import ExecutionPlan
        sup = None
        if self.supervise and self.test_io == 0:
            sup = self._make_supervisor()
        # ONE plan per run: everything the old fallback matrix excluded
        # (supervise, update_period>1, eval_train metrics, async saves)
        # now composes with the scan — only profiling and test_io demote
        # statically, extra_data demotes per round (doc/trainer.md)
        plan = ExecutionPlan.resolve(
            requested_k=self.steps_per_dispatch,
            profiling=tracer.enabled, test_io=bool(self.test_io),
            strict=bool(self.scan_strict), silent=bool(self.silent))
        try:
            self._run_rounds(sup, plan, tracer, batch_counter, start)
        finally:
            if sup is not None:
                sup.close()

    def _progress(self, sample_counter: int, start: float) -> None:
        if sample_counter % self.print_step == 0 and not self.silent:
            elapsed = int(time.monotonic() - start)
            print(f'round {self.start_counter - 1:8d}:'
                  f'[{sample_counter:8d}] {elapsed} sec elapsed', flush=True)

    def _round(self, plan, tracer, batch_counter, start):
        """One unsupervised round through the plan's WindowedStepper:
        per-step (K=1) keeps the classic one-batch host->device lookahead
        — batch i+1's transfers are enqueued (stage_batch, async) before
        batch i's step is dispatched, so the host link rides behind
        device compute; scanned (K>1) accumulates K staged batches (the
        lookahead runs K deep) into ONE ``compile_multi_step`` dispatch,
        with the short epoch tail finishing per-step (bitwise-identical,
        so epoch length need not divide K).  An ``attachtxt`` chain
        (extra_data) demotes THIS round only — the next round's stepper
        re-probes."""
        stepper = plan.round_stepper(
            self.net_trainer,
            before_dispatch=lambda u: tracer.before_update(
                batch_counter + u))
        sample_counter = 0
        for batch in _spanned_batches(self.itr_train):
            if self.test_io == 0:
                stepper.feed(batch)
            sample_counter += 1
            self._progress(sample_counter, start)
        stepper.finish()
        return stepper.updates, sample_counter

    def _run_rounds(self, sup, plan, tracer, batch_counter, start) -> None:
        cc = self.max_round
        while self.start_counter <= self.num_round and cc > 0:
            cc -= 1
            if not self.silent:
                print(f'update round {self.start_counter - 1}', flush=True)
            self.net_trainer.start_round(self.start_counter)
            t_round = time.monotonic()
            if sup is not None:
                n = self._supervised_round(sup, plan, tracer, batch_counter,
                                           start)
                batch_counter += n
            else:
                n, _ = self._round(plan, tracer, batch_counter, start)
                batch_counter += n
            if self.test_io == 0 and self.net_trainer.params is not None:
                # the round ends when the device has finished its last
                # step, not when the host has dispatched it: with
                # eval_train = 0 nothing else waits, and steps/sec (and
                # the MFU gauge) would read above the chip's peak
                import jax
                jax.block_until_ready(self.net_trainer.params)
            dt_round = time.monotonic() - t_round
            # settle the one-step-deferred divergence gate (no-op unless
            # nan_action=halt / nan_breaker armed the check)
            self.net_trainer.flush_divergence_check()
            if self.test_io == 0:
                sys.stderr.write(f'[{self.start_counter}]')
                if not self.itr_evals:
                    sys.stderr.write(self.net_trainer.evaluate(None, 'train'))
                for it, name in zip(self.itr_evals, self.eval_names):
                    sys.stderr.write(self.net_trainer.evaluate(it, name))
                self._write_io_stats()
                sys.stderr.write('\n')
                self._write_train_speed(n, dt_round)
                sys.stderr.flush()
            self._save_model()
        if not self.silent:
            print(f'\nupdating end, {int(time.monotonic() - start)} sec in all')

    def _write_io_stats(self) -> None:
        """Pipeline observability: when the train chain is instrumented
        (``nworker`` set, doc/io.md) its per-stage stats join the round's
        eval line in the same ``\\tio-key:value`` format, then reset so
        each round reports its own pass.  Render-and-reset is ONE atomic
        drain (``print_and_clear``): the old print()-then-clear() pair
        silently dropped any update a pool/buffer worker recorded
        between the two lock holds."""
        if self.itr_train is None:
            return
        stats = self.itr_train.pipeline_stats()
        if stats is None:
            return
        line = stats.print_and_clear('io')
        if line:
            sys.stderr.write(line)

    def _write_train_speed(self, n: int, dt: float) -> None:
        """The MFU gauge rides the train eval block
        (doc/observability.md "Programs, memory, and MFU"): measured
        steps/sec for the round × ledger flops/step over the
        per-platform peak-FLOPs table.  Deliberately its OWN stderr
        line right under the ``[N]`` eval line: eval lines are a
        bitwise-compared surface (the scan/supervise CLI twins assert
        them equal across runs) and wall-clock numbers may never ride
        one.  ``train-mfu`` only prints when a peak is known (real
        chip or ``CXXNET_PEAK_TFLOPS``) — an unknown denominator
        reports nothing, never a fake 0.  The same gauges serve on
        ``/metrics`` (registered StatSet), so they are SLO-able for
        free."""
        if n <= 0 or dt <= 0:
            return
        from .obs import get_hub
        from .obs.programs import mfu
        if self._train_stats is None:
            from .utils.metric import StatSet
            self._train_stats = StatSet()
            get_hub().register_stats('train', self._train_stats)
        st = self._train_stats
        sps = n / dt
        st.gauge('steps_per_sec', round(sps, 3))
        flops = self.net_trainer.train_step_flops()
        if flops > 0:
            st.gauge('flops_per_step', flops)
        m = mfu(flops, sps, devices=self.net_trainer._mesh.devices.size)
        if m is not None:
            st.gauge('mfu', round(m, 5))
        sys.stderr.write(st.print('train').lstrip('\t') + '\n')

    # --- telemetry (graftscope, doc/observability.md) ----------------------
    def _obs_start(self) -> None:
        """Arm the telemetry hub for this run: flight-recorder ring +
        fault-triggered dumps + SIGUSR1 are always armed (the recorder
        is the postmortem every chaos drill ships); the live
        ``/metrics`` + ``/statusz`` + ``/healthz`` + ``/slos`` endpoint
        thread comes up only with ``obs.port >= 0`` (0 = ephemeral —
        the bound port prints to stdout, and announces into
        ``CXXNET_OBS_PORT_FILE`` when the elastic launcher set one).
        Any ``slo.<name>=`` spec (or an explicit ``obs.sample_every``)
        additionally starts the gauge-history sampler + SLO engine —
        verdicts serve on ``/slos``/``/metrics``, a breach records the
        typed ``SLOBreachError`` kind (which dumps a postmortem), and
        ``/healthz`` reports ``degraded`` while one is BREACHED."""
        from .obs import get_hub
        hub = get_hub()
        if self.obs_ring_events > 0:
            hub.set_ring(self.obs_ring_events)
        dump_dir = self.obs_dump_dir or os.path.join(self.name_model_dir,
                                                     'flight')
        hub.arm_flight_recorder(dump_dir)
        hub.arm_signal_dump()
        # graftprof: the compiler-truth ledger joins the hub (programs.*
        # gauges + /statusz summary; /programs serves it raw), device
        # memory gauges ride the same sampler/fleet machinery
        from .obs import programs as obs_programs
        ledger = obs_programs.get_ledger()
        ledger.set_recompile(self.obs_recompile)
        ledger.register_into(hub)
        if self.obs_hbm:
            obs_programs.register_hbm(hub)
        # fleet.-scoped specs belong to the launcher's cross-rank view;
        # a worker evaluating one would only ever see "no data"
        local_specs = [(n, v) for n, v in self.slo_specs
                       if not v.startswith('fleet.')]
        fleet_specs = [n for n, v in self.slo_specs
                       if v.startswith('fleet.')]
        if fleet_specs and not os.environ.get('CXXNET_OBS_PORT_FILE') \
                and not self.silent:
            # this process is neither the launcher (that role returned
            # from _maybe_elastic_launch before ever reaching here) nor
            # a worker under one (the launcher sets the port file) —
            # nothing will evaluate these specs, and silence here would
            # be the watching-nothing trap all over again
            print(f"obs: warning — fleet-scoped "
                  f"slo.{{{','.join(sorted(fleet_specs))}}} only "
                  'evaluate at the elastic launcher (dist.hosts > 1); '
                  'nothing watches them in this run', flush=True)
        if local_specs or self.obs_sample_every > 0:
            from .obs.history import GaugeSampler, hub_source
            # <= 0 (including a -1 spelled like obs.port's off) means
            # "auto": the 0.25s default cadence, never a clamped 100 Hz
            self._obs_sampler = GaugeSampler(
                hub_source(hub),
                period=(self.obs_sample_every
                        if self.obs_sample_every > 0 else 0.25))
            if local_specs:
                from .obs.slo import SLOEngine, SLOSpec
                self._obs_slo = SLOEngine(self._obs_sampler.history)
                for spec_name, text in local_specs:
                    self._obs_slo.add(SLOSpec.parse(spec_name, text))
                self._obs_slo.register_into(hub)
                self._obs_sampler.add_listener(self._obs_slo.on_tick)
            self._obs_sampler.start()
        if self.obs_port >= 0:
            from .obs.endpoints import ObsServer
            self._obs_server = ObsServer(
                hub, port=self.obs_port,
                port_file=os.environ.get('CXXNET_OBS_PORT_FILE'),
                profile_dir=(os.path.join(dump_dir, 'profile')
                             if self.obs_profile else None))
            routes = '/metrics /statusz /healthz /slos /programs'
            if self.obs_profile:
                routes += ' /profile'
            print(f'obs: telemetry on http://127.0.0.1:'
                  f'{self._obs_server.port} ({routes}), flight dumps in '
                  f'{dump_dir}', flush=True)

    def _obs_register_iterators(self) -> None:
        """Instrumented io chains join the hub so their per-stage stats
        serve on /metrics alongside the eval line."""
        if self.itr_train is None:
            return
        stats = self.itr_train.pipeline_stats()
        if stats is not None:
            from .obs import get_hub
            get_hub().register_stats('io', stats)

    def _obs_stop(self) -> None:
        from .obs import get_hub
        hub = get_hub()
        if self.obs_trace_export:
            path = hub.export_chrome_trace(self.obs_trace_export)
            if not self.silent:
                print(f'obs: Chrome trace exported to {path} '
                      '(load in Perfetto; doc/observability.md)',
                      flush=True)
        if self._obs_sampler is not None:
            self._obs_sampler.close(timeout=5.0)
            self._obs_sampler = None
        if self._obs_slo is not None:
            if not self.silent:
                from .obs.slo import summary_lines
                for line in summary_lines(self._obs_slo.status_view()):
                    print(f'obs: {line}', flush=True)
            self._obs_slo.close()
            self._obs_slo = None
        if self._obs_server is not None:
            self._obs_server.close(timeout=5.0)
            self._obs_server = None
        hub.disarm()

    def task_predict(self) -> None:
        assert self.itr_pred is not None, 'must specify a pred iterator'
        print('start predicting...')
        with open(self.name_pred, 'w') as fo:
            for pred in self.net_trainer.predict_stream(self.itr_pred):
                for v in pred:
                    fo.write(f'{v:g}\n')
        print(f'finished prediction, write into {self.name_pred}')

    def task_predict_raw(self) -> None:
        """``task=pred_raw``: the final node's raw score vector per
        instance, one space-separated line each — the format
        ``make_submission.py`` consumes.  (The reference gates the pred
        iterator on this task name, ``cxxnet_main.cpp:242``, but its Run()
        never dispatches it — here it works.)"""
        assert self.itr_pred is not None, 'must specify a pred iterator'
        print('start predicting (raw scores)...')
        tr = self.net_trainer
        with open(self.name_pred, 'w') as fo:
            for out in tr.forward_stream(self.itr_pred,
                                         tr.net.node_index('top[-1]')):
                for row in out.reshape(out.shape[0], -1):
                    fo.write(' '.join(f'{v:g}' for v in row) + '\n')
        print(f'finished prediction, write into {self.name_pred}')

    def task_serve(self) -> None:
        """``task=serve``: the online inference stack (doc/serving.md) —
        bucketed engine + dynamic micro-batcher + (optionally) checkpoint
        hot-reload — driven over the ``pred=`` iterator as the request
        source, so the CLI exercises exactly the path a fronting server
        embeds via ``net_serve_*``.  Predictions land in ``pred=``'s file
        (task=pred format); per-bucket latency/queue/throughput stats go
        to stderr at shutdown in eval-line format."""
        assert self.itr_pred is not None, 'must specify a pred iterator'
        import numpy as np

        from .serve import (DynamicBatcher, ModelRegistry, PredictEngine,
                            ReplicatedPredictEngine)
        from .utils.bucketing import parse_buckets

        if self.serve_replicas >= 2:
            # graftshard DP: N per-device replicas behind ONE batcher;
            # coalesced windows round-robin, hot swaps drain the fleet.
            # Completion is engine-owned, so the replicas share the
            # batcher's StatSet (single-owner counting still holds)
            from .utils.metric import StatSet as _SS
            engine = ReplicatedPredictEngine(
                self.net_trainer, parse_buckets(self.serve_buckets),
                dtype=self.serve_dtype, replicas=self.serve_replicas,
                stats=_SS(), fold_bn=self.serve_fold_bn)
        else:
            engine = PredictEngine(self.net_trainer,
                                   parse_buckets(self.serve_buckets),
                                   dtype=self.serve_dtype,
                                   fold_bn=self.serve_fold_bn)
        engine.warm()
        if not self.silent:
            nrep = getattr(engine, 'engines', None)
            print(f'serve: warmed {len(engine.buckets)} bucket programs '
                  f'{engine.buckets} (dtype={self.serve_dtype}, '
                  f'{engine.resident_bytes()} resident bytes'
                  + (f', {len(nrep)} replicas' if nrep else '') + ')',
                  flush=True)
            fv = getattr(engine, 'fold_view', lambda: None)()
            if fv:
                pairs = ','.join(f'{c}+{b}' for c, b in fv['pairs'])
                print(f'serve: folded {len(fv["pairs"])} conv+BN pair(s) '
                      f'[{pairs}] — proof max_abs_err '
                      f'{fv["max_abs_err"]:.3g} on the calibration batch',
                      flush=True)
        batcher = DynamicBatcher(engine, max_queue=self.serve_max_queue,
                                 max_wait=self.serve_max_wait,
                                 deadline=self.serve_deadline,
                                 stats=getattr(engine, 'stats', None))
        registry = None
        if self.serve_reload > 0:
            registry = ModelRegistry(
                engine, self.name_model_dir,
                poll_interval=self.serve_reload,
                current=self.start_counter - 1,
                on_swap=None if self.silent else (
                    lambda c, p: print(f'serve: hot-reloaded checkpoint '
                                       f'{c} from {p}', flush=True)))
            registry.start()
        # live telemetry: the batcher's per-bucket gauges serve on
        # /metrics, the registry state machine on /statusz
        from .obs import get_hub
        from .utils.metric import StatSet
        _hub = get_hub()
        # the refresh folds the LIVE queue depth per render, so an SLO
        # over serve.queue_depth reads admission pressure, not peaks
        batcher.register_into(_hub)
        if registry is not None:
            registry.register_into(_hub)
        fleet = self._serve_fleet(engine)
        if fleet is not None:
            _fleet_stats_set = StatSet()
            _hub.register_stats(
                'fleet', _fleet_stats_set,
                refresh=lambda: fleet.report(stats=_fleet_stats_set))
            for mid in fleet.models():
                try:
                    fleet.get(mid)       # budgeter decides who stays warm
                # lint: allow(fault-taxonomy): a cold sibling must not kill serve; printed, and the budgeter retries on demand
                except Exception as e:
                    print(f'serve: fleet model {mid!r} not loaded: {e}',
                          flush=True)
            if not self.silent:
                print(f'serve: fleet of {len(fleet.models())} models, '
                      f'{len(fleet.loaded())} resident under '
                      f'{self.serve_mem_budget or "unbounded"} bytes',
                      flush=True)
        print('start serving...')
        served = 0
        try:
            with open(self.name_pred, 'w') as fo:
                # windowed async submits: keep up to half the admission
                # queue in flight so the batcher can coalesce, drain in
                # order so the output file matches task=pred row order
                import collections
                pending = collections.deque()
                cap = max(1, self.serve_max_queue // 2)
                # the bulk drive keeps `cap` requests queued by design, so
                # the LIVE-traffic deadline would expire in our own queue
                # on any non-trivial model; bulk requests are throughput-
                # bound, not latency-bound — the bound scales with the
                # queue a request may sit behind (generous per-request
                # allowance; a truly wedged worker still trips it)
                bulk_deadline = max(self.serve_deadline,
                                    60.0 + 30.0 * cap)

                def _drain_one():
                    for v in self.net_trainer._pred_transform(
                            batcher.wait(pending.popleft())):
                        fo.write(f'{v:g}\n')

                for batch in self.itr_pred:
                    n = batch.batch_size - batch.num_batch_padd
                    if not n:
                        continue
                    data = batch.data
                    if batch.norm_spec is not None:
                        # serving wire contract: normalized floats
                        data = batch.norm_spec.apply(data)
                    rows = np.ascontiguousarray(
                        np.asarray(data, np.float32)[:n])
                    pending.append(batcher.submit_async(
                        rows, deadline=bulk_deadline))
                    served += n
                    while len(pending) >= cap:
                        _drain_one()
                while pending:
                    _drain_one()
        finally:
            if registry is not None:
                registry.close(timeout=5.0)
            batcher.close(timeout=30.0)
            if hasattr(engine, 'close'):        # replica worker threads
                engine.close(timeout=10.0)
            sys.stderr.write(f'[serve]{batcher.report("serve")}\n')
            if registry is not None:
                # swap stamps: which step is serving and how stale it is
                # (the serving half of the freshness metric, doc/online.md)
                sys.stderr.write(f'[serve]{registry.report()}\n')
            if fleet is not None:
                sys.stderr.write(f'[serve]{fleet.report()}\n')
                fleet.close(timeout=5.0)
            sys.stderr.flush()
        print(f'finished serving {served} instances, predictions in '
              f'{self.name_pred} (compiled {engine.compile_count} programs '
              f'for {len(engine.buckets)} buckets)')

    def task_online(self) -> None:
        """``task=online``: the train-while-serve loop (doc/online.md) —
        a supervised trainer over the ``data=`` section (idiomatically
        ``iter = imgbin_stream``) publishing a serving checkpoint every
        ``online.save_every`` steps, while the colocated
        engine/batcher/registry stack hot-reloads them under traffic
        replayed from the ``pred=`` section at ``online.qps``.  Each
        round's eval line carries the freshness gauges; the serving
        ledger and a one-line JSON summary print at shutdown."""
        assert self.itr_train is not None, 'task=online needs a data section'
        import json

        import numpy as np

        from .online import OnlineConfig, OnlinePipeline
        from .utils.bucketing import parse_buckets

        request_source = None
        if self.itr_pred is not None:
            # replay the pred section's (normalized) rows cyclically —
            # the CLI's stand-in for a fronting server's live traffic
            rows_pool = []
            for batch in self.itr_pred:
                n = batch.batch_size - batch.num_batch_padd
                if not n:
                    continue
                data = batch.data
                if batch.norm_spec is not None:
                    data = batch.norm_spec.apply(data)
                rows_pool.append(np.ascontiguousarray(
                    np.asarray(data, np.float32)[:n]))
            if rows_pool:
                state = {'i': 0}

                def request_source():
                    r = rows_pool[state['i'] % len(rows_pool)]
                    state['i'] += 1
                    return r
        # online runs default to async publishing (the whole point is a
        # step loop that never waits on storage); an explicit
        # save_async=0 in the conf still wins
        save_async = self.save_async
        if not any(k == 'save_async' for k, _ in self.cfg):
            save_async = 1
        cfg = OnlineConfig(
            model_dir=self.name_model_dir,
            save_every=self.online_save_every,
            save_workers=self.save_workers,
            freshness_slo=self.online_freshness_slo,
            freshness_strict=bool(self.online_freshness_strict),
            reload_poll=self.online_reload,
            buckets=parse_buckets(self.serve_buckets),
            max_queue=self.serve_max_queue,
            max_wait=self.serve_max_wait,
            deadline=self.serve_deadline,
            dtype=self.serve_dtype,
            qps=self.online_qps,
            watchdog_deadline=self.watchdog_deadline or None,
            max_restarts=self.max_restarts,
            nan_breaker=self.nan_breaker,
            keep_last=self.keep_last,
            save_async=save_async,
            steps_per_dispatch=self.steps_per_dispatch,
            net_type=self.net_type,
            silent=bool(self.silent))
        serve_factory = (
            lambda: NetTrainer(self.cfg + [('inference_only', '1')]))
        pipe = OnlinePipeline(self.net_trainer, self.itr_train,
                              serve_factory, cfg,
                              request_source=request_source)
        scaler = None
        if self.serve_autoscale:
            # SLO-driven autoscaling over the online stack: the batcher
            # queue and the train/serve split are the bound knobs; with
            # interval=0 the evaluation rides the before_step hook so
            # the loop stays deterministic
            from .obs import get_hub
            from .serve.autoscale import AutoscalePolicy, Autoscaler
            pol = AutoscalePolicy.parse(self.serve_autoscale)
            scaler = Autoscaler(pol, name='online_scale')
            pipe.start()
            if pipe.batcher is not None:
                scaler.bind_batcher(pipe.batcher)
            scaler.bind_online(pipe)
            scaler.register_into(get_hub())
        print('start online training-while-serving...')
        start = time.monotonic()

        def before_step(i):
            self._progress(i + 1, start)
            if scaler is not None and scaler.policy.interval <= 0:
                scaler.evaluate()

        try:
            summary = pipe.run(
                num_rounds=self.num_round,
                evals=list(zip(self.itr_evals, self.eval_names)),
                before_step=before_step)
            sys.stderr.write(f'[online]{pipe.serve_report()}\n')
            if scaler is not None:
                sys.stderr.write(f'[online]{scaler.report()}\n')
            sys.stderr.flush()
            print(f'online summary: {json.dumps(summary, sort_keys=True)}',
                  flush=True)
        finally:
            if scaler is not None:
                scaler.close()
            pipe.close(timeout=30.0)
        print(f'finished online run, {int(time.monotonic() - start)} sec in all')

    def _parse_lm_spec(self, spec: str, model_in: str = 'NULL',
                       seed: int = 0, default_vocab=None):
        """Build a transformer (params, cfg) from a compact
        ``k=v[;k=v...]`` spec (vocab, d_model, heads, d_ff, stages,
        experts, seq, plus inline ``model_in=``/``seed=`` overrides);
        params come from a ``%04d.lm`` tree or a seeded init.  Shared by
        ``serve.lm`` (the target) and ``serve.draft`` (the speculative-
        decode draft, whose vocab defaults to the target's)."""
        import numpy as np

        from .models import transformer as TT
        from .utils.config import parse_kv_list
        kw = {'attn': 'local'}
        if default_vocab is not None:
            kw['vocab_size'] = int(default_vocab)
        names = {'vocab': ('vocab_size', int), 'd_model': ('d_model', int),
                 'heads': ('num_heads', int), 'd_ff': ('d_ff', int),
                 'stages': ('num_stages', int), 'seq': ('seq_len', int),
                 'experts': ('num_experts', int)}
        for key, val in parse_kv_list(spec or ''):
            if key == 'model_in':
                model_in = val
            elif key == 'seed':
                seed = int(val)
            elif key in names:
                attr, typ = names[key]
                kw[attr] = typ(val)
            else:
                raise ValueError(f'unknown lm spec key: {key!r}')
        cfg = TT.TransformerConfig(**kw)
        if model_in != 'NULL':
            from .serve.decode import load_lm_params
            params = load_lm_params(model_in)
        else:
            params = TT.init_params(np.random.RandomState(seed), cfg)
        return params, cfg

    def _lm_spec(self):
        """The decode target model from ``serve.lm`` /
        ``serve.lm_model_in`` / ``serve.lm_seed``."""
        return self._parse_lm_spec(self.serve_lm,
                                   model_in=self.serve_lm_model_in,
                                   seed=self.serve_lm_seed)

    def task_serve_decode(self) -> None:
        """``task=serve serve.mode=decode``: the continuous-batching
        decode stack (doc/serving.md "Continuous decode") driven over
        seeded synthetic prompts of mixed lengths — the CLI exercises
        exactly the join/leave/page path an embedding server drives via
        ``lm_serve_*``.  Token streams land in ``pred=``'s file (one
        space-separated line per request, arrival order); the first few
        are cross-checked against offline ``transformer.generate`` twins
        and the per-token stats print to stderr at shutdown."""
        import numpy as np

        from .models import transformer as TT
        from .serve.decode import DecodeService

        params, cfg = self._lm_spec()
        draft = None
        if self.serve_draft:
            # the draft rides the same spec grammar; its vocab defaults
            # to the target's (the verify window compares token ids)
            draft = self._parse_lm_spec(self.serve_draft,
                                        default_vocab=cfg.vocab_size)
        svc = DecodeService(
            params, cfg, slots=self.serve_slots, pages=self.serve_pages,
            page_size=self.serve_page_size,
            max_prompt=self.serve_max_prompt,
            max_new_bound=self.serve_max_new,
            eos_id=None if self.serve_eos < 0 else self.serve_eos,
            max_queue=self.serve_max_queue, max_wait=self.serve_max_wait,
            # bulk drive: throughput-bound, not latency-bound (the same
            # reasoning as the predict drive's bulk_deadline)
            deadline=max(self.serve_deadline, 60.0),
            dtype=self.serve_dtype,
            prefix_share=self.serve_prefix_share,
            spec_k=self.serve_spec_k, draft=draft,
            kv_host_mb=self.serve_kv_host_mb,
            kv_disk_mb=self.serve_kv_disk_mb,
            kv_dir=self.serve_kv_dir or None,
            kv_share_dir=self.serve_kv_share_dir or None,
            shard=self.serve_shard,
            prefill_workers=self.serve_prefill_workers)
        from .obs import get_hub
        # ONE StatSet backs both the engine and the batcher
        # (DecodeService shares it), so this single registration carries
        # the admission gauges too; refresh folds the pull-style page/
        # gen-cache/acceptance gauges before each /metrics render
        get_hub().register_stats('decode', svc.engine.stats,
                                 refresh=lambda: svc.report('decode'))
        if svc.engine.kv_stats is not None:
            # graftcache tier gauges ride the hub under their own set so
            # slo.kv_hit=kv.hit_rate>=0.5@60-style specs resolve; the
            # refresh folds tier occupancy right before each render
            get_hub().register_stats(
                'kv', svc.engine.kv_stats,
                refresh=svc.engine.kv_occupancy)
        if not self.silent:
            print(f'serve: decode engine up — {self.serve_slots} slots, '
                  f'{self.serve_pages}x{self.serve_page_size}-token KV '
                  f'pages (slot cache {svc.engine.cache_len}, '
                  f'dtype={svc.engine.serve_dtype}'
                  f', prefix_share={self.serve_prefix_share}'
                  f', spec_k={svc.engine._spec_k}'
                  + (f', shard=tp:{svc.engine._tp} over '
                     f'{svc.engine._tp} devices'
                     if svc.engine._tp > 1 else '')
                  + (f', prefill_workers={self.serve_prefill_workers}'
                     if self.serve_prefill_workers else '')
                  + ')', flush=True)
        if self.serve_scenario:
            self._serve_decode_scenario(svc, cfg)
            return
        print('start serving (decode)...')
        rng = np.random.RandomState(self.serve_seed)
        n_req = max(1, self.serve_requests)
        prompts = [rng.randint(
            0, cfg.vocab_size,
            (1, int(rng.randint(1, max(2, self.serve_max_prompt)))))
            .astype(np.int32) for _ in range(n_req)]
        temp = float(self.serve_temperature)
        keys = [None] * n_req
        if temp > 0:
            import jax
            keys = [jax.random.PRNGKey(self.serve_seed * 100003 + i)
                    for i in range(n_req)]
        reqs = [svc.submit_async(p, self.serve_max_new, temp, k)
                for p, k in zip(prompts, keys)]
        served = 0
        try:
            with open(self.name_pred, 'w') as fo:
                for r in reqs:
                    toks = svc.batcher.wait(r)
                    fo.write(' '.join(str(int(t)) for t in toks) + '\n')
                    served += 1
            # bitwise-twin spot check: the stream each request got must
            # equal its offline generate call (same seed/schedule) —
            # over the ENGINE's stored tree and compute config, so the
            # twin holds on every serve.dtype tier (a quantized model's
            # oracle is generate() over the same quantized tree)
            checked = 0
            for i in range(min(3, n_req)):
                off = np.asarray(TT.generate(
                    svc.engine.oracle_params(), prompts[i],
                    self.serve_max_new, svc.engine.cfg,
                    temperature=temp, rng=keys[i],
                    eos_id=None if self.serve_eos < 0
                    else self.serve_eos))[0]
                got = reqs[i].result
                if not (np.asarray(got) == off[:len(got)]).all():
                    raise AssertionError(
                        f'decode stream {i} diverged from its offline '
                        f'generate twin: {got} vs {off}')
                checked += 1
            if not self.silent:
                print(f'decode twin check: {checked} streams equal their '
                      'offline generate calls', flush=True)
        finally:
            sys.stderr.write(f'[serve]{svc.report("decode")}\n')
            sys.stderr.flush()
            svc.close(30.0)
        print(f'finished serving {served} decode streams, token ids in '
              f'{self.name_pred}')

    def _serve_decode_scenario(self, svc, cfg) -> None:
        """``serve.scenario=``: drive the decode stack through a seeded
        adversarial traffic scenario (doc/serving.md "Scenarios and
        autoscaling") instead of the fixed bulk prompts; with
        ``serve.autoscale=`` an SLO-driven autoscaler retunes the live
        admission caps while the storm runs.  Served streams land in
        ``pred=``'s file (one line per request index); the ledger must
        reconcile exactly against the service counters and the first
        served streams are twin-checked against offline generate."""
        import numpy as np

        from .models import transformer as TT
        from .obs import get_hub
        from .serve.autoscale import AutoscalePolicy, Autoscaler
        from .serve.scenario import ScenarioSpec, drive

        spec = ScenarioSpec.parse(self.serve_scenario)
        scaler = None
        on_tick = None
        if self.serve_autoscale:
            pol = AutoscalePolicy.parse(self.serve_autoscale)
            scaler = Autoscaler(pol)
            scaler.bind_engine(svc.engine)
            scaler.bind_batcher(svc.batcher)
            scaler.register_into(get_hub())
            if pol.interval <= 0:
                on_tick = lambda _t: scaler.evaluate()
        print(f'start serving (decode, scenario {spec.shape})...')
        try:
            led = drive(svc, spec, vocab=cfg.vocab_size, on_tick=on_tick)
            led.reconcile(svc.engine.stats)
            with open(self.name_pred, 'w') as fo:
                for i in sorted(led.streams):
                    fo.write(' '.join(str(int(t))
                                      for t in led.streams[i]) + '\n')
            checked = 0
            for i in sorted(led.streams)[:3]:
                rec = spec.schedule()[i]
                prompt = spec.prompt_for(i, rec.prompt_len,
                                         cfg.vocab_size)
                off = np.asarray(TT.generate(
                    svc.engine.params, prompt, rec.max_new,
                    svc.engine.cfg))[0]
                got = np.asarray(led.streams[i])
                if not (got == off[:len(got)]).all():
                    raise AssertionError(
                        f'scenario stream {i} diverged from its offline '
                        f'generate twin: {got} vs {off}')
                checked += 1
            if not self.silent:
                print(f'scenario twin check: {checked} streams equal '
                      'their offline generate calls', flush=True)
            print(f'scenario summary: {led.summary()}')
            if scaler is not None:
                print(f'autoscale actions: {len(scaler.history())}, '
                      f'degraded={scaler.degraded}')
        finally:
            if scaler is not None:
                sys.stderr.write(f'[serve]{scaler.report()}\n')
                scaler.close()
            sys.stderr.write(f'[serve]{svc.report("decode")}\n')
            sys.stderr.flush()
            svc.close(30.0)
        print(f'finished scenario ({led.counts["served"]} streams '
              f'served), token ids in {self.name_pred}')

    def _serve_fleet(self, engine):
        """``serve.models=id=dir;id=dir``: register sibling checkpoints
        (same architecture as the conf) in a MultiModelRegistry under
        ``serve.mem_budget`` bytes; returns the fleet (or None)."""
        if not self.serve_models:
            return None
        from .serve import MultiModelRegistry, PredictEngine
        from .utils.bucketing import parse_buckets
        from .utils.config import parse_kv_list

        fleet = MultiModelRegistry(mem_budget=self.serve_mem_budget,
                                   poll_interval=self.serve_reload or 1.0)

        def make_factory(mdir):
            def factory():
                from .serve.registry import (load_into_trainer,
                                             newest_model_file)
                best = newest_model_file(mdir)
                if best is None:
                    raise FileNotFoundError(f'no model files in {mdir}')
                tr = load_into_trainer(self._create_net(), best[1])
                return PredictEngine(tr,
                                     parse_buckets(self.serve_buckets),
                                     dtype=self.serve_dtype)
            return factory

        for mid, mdir in parse_kv_list(self.serve_models):
            fleet.add_model(mid, make_factory(mdir), model_dir=mdir)
        if self.serve_reload > 0:
            fleet.start()
        return fleet

    # --- grafttune (doc/autotune.md) --------------------------------------
    def _tune_gate(self, space, baseline, feasible=None):
        """Stage-1 admission from compiler truth: one batched AOT sweep
        fills the ledger, the largest live footprint among analyzed
        programs becomes the base price, and the declared ``mem_mb``
        ceiling (scaled by the required headroom) bounds every
        candidate.  ``mem_mb=0`` disables byte pruning — on a platform
        with no HBM story (CPU) there is nothing truthful to prune
        against."""
        from .obs.programs import get_ledger
        from .tune import LedgerGate
        led = get_ledger()
        led.ensure_analyzed_batch()
        base = 0
        for e in led.entries():
            peak = e.peak_bytes or (e.argument_bytes + e.output_bytes
                                    + e.temp_bytes)
            base = max(base, peak)
        ceiling = 0.0
        if space.mem_mb > 0:
            ceiling = space.mem_mb * (1 << 20) * (1.0 - space.headroom)
        return LedgerGate(base_bytes=float(base), ceiling_bytes=ceiling,
                          baseline=baseline,
                          mem_knobs=space.mem_knobs(),
                          mem_inv_knobs=space.mem_inv_knobs(),
                          feasible=feasible)

    def _tune_baseline(self, space) -> dict:
        """The hand-set config values, clamped into the declared ranges
        — the candidate every measured probe competes against."""
        current = {'steps_per_dispatch': self.steps_per_dispatch,
                   'slots': self.serve_slots, 'pages': self.serve_pages,
                   'page_size': self.serve_page_size,
                   'spec_k': self.serve_spec_k,
                   'max_queue': self.serve_max_queue,
                   'micro_batch': self.micro_batch,
                   'nworker': 1}
        if self._data_itcfg:
            for name, val in self._data_itcfg:
                if name == 'nworker':
                    current['nworker'] = int(val)
        out = {}
        for r in space.knobs:
            out[r.name] = max(r.lo, min(r.hi, int(current[r.name])))
        return out

    def _set_micro_batch(self, value: int) -> None:
        """Apply a candidate ``micro_batch`` to every layer of the LIVE
        trainer and rebuild its step programs: the knob is read at trace
        time (layers/conv.py ``_micro_split``), so an already-compiled
        program would never see the change."""
        tr = self.net_trainer
        for layer in tr.net.layers:
            layer.param.micro_batch = int(value)
        tr._compile_steps()

    def _rebuild_train_iterator(self, nworker: int):
        itcfg = [(n, v) for n, v in (self._data_itcfg or [])
                 if n != 'nworker'] + [('nworker', str(int(nworker)))]
        it = create_iterator(itcfg)
        for name, val in self._data_defcfg:
            it.set_param(name, val)
        it.init()
        return it

    def _autotune_train(self, space):
        """mode=train probes: steps/sec of the REAL plan/stepper path
        (``execution.measured_probe``) at each candidate K, over batches
        drawn once from the train iterator — a candidate ``nworker``
        rebuilds the iterator and redraws, so the pool depth it pays for
        is the pool depth it measures."""
        import itertools as _it

        from .nnet import execution
        from .runtime import faults as _faults
        from .tune import TuneSearch
        if self.itr_train is None:
            raise _faults.TuneSpecError(
                'autotune mode=train needs a data section to probe with')
        batches = list(_it.islice(iter(self.itr_train), space.probe_steps))
        if not batches:
            raise _faults.TuneSpecError(
                'autotune: the train iterator yielded no batches')
        baseline = self._tune_baseline(space)
        base_k = baseline.get('steps_per_dispatch', self.steps_per_dispatch)
        # warm-up at the baseline K fills the ledger: stage 1 prices
        # candidates from THIS program's compiler truth
        execution.measured_probe(self.net_trainer, base_k, batches,
                                 repeats=1)
        gate = self._tune_gate(space, baseline)

        base_mb = baseline.get('micro_batch', self.micro_batch)
        applied_mb = [base_mb]

        def probe(cand):
            pb = batches
            if 'nworker' in cand and cand['nworker'] != baseline['nworker']:
                itr = self._rebuild_train_iterator(cand['nworker'])
                pb = list(_it.islice(iter(itr), space.probe_steps))
            mb = int(cand.get('micro_batch', base_mb))
            if mb != applied_mb[0]:
                self._set_micro_batch(mb)
                applied_mb[0] = mb
            k = cand.get('steps_per_dispatch', base_k)
            return execution.measured_probe(
                self.net_trainer, k, pb, repeats=space.probe_repeats)

        try:
            return TuneSearch(space, probe, gate=gate,
                              baseline=baseline).run('train')
        finally:
            # probes mutate the live trainer; leave it at the hand-set
            # split, not whatever the last candidate happened to be
            if applied_mb[0] != base_mb:
                self._set_micro_batch(base_mb)

    def _autotune_decode(self, space):
        """mode=decode probes: tokens/sec of a real DecodeService built
        at each candidate's slots/pages/page_size/spec_k over seeded
        prompts; candidates wanting speculation without a configured
        draft are pruned in stage 1 (feasibility, not bytes)."""
        import numpy as np

        from .serve.decode import DecodeService
        from .tune import TuneSearch
        params, cfg = self._lm_spec()
        draft = None
        if self.serve_draft:
            draft = self._parse_lm_spec(self.serve_draft,
                                        default_vocab=cfg.vocab_size)
        baseline = self._tune_baseline(space)

        def build(cand):
            return DecodeService(
                params, cfg,
                slots=cand.get('slots', self.serve_slots),
                pages=cand.get('pages', self.serve_pages),
                page_size=cand.get('page_size', self.serve_page_size),
                max_prompt=self.serve_max_prompt,
                max_new_bound=self.serve_max_new,
                eos_id=None if self.serve_eos < 0 else self.serve_eos,
                max_queue=cand.get('max_queue', self.serve_max_queue),
                max_wait=self.serve_max_wait,
                deadline=max(self.serve_deadline, 60.0),
                dtype=self.serve_dtype,
                prefix_share=self.serve_prefix_share,
                spec_k=cand.get('spec_k', self.serve_spec_k),
                draft=draft)

        def probe(cand):
            svc = build(cand)
            try:
                rng = np.random.RandomState(space.seed)
                n_req = max(1, space.probe_steps)
                prompts = [rng.randint(
                    0, cfg.vocab_size,
                    (1, int(rng.randint(1, max(2, self.serve_max_prompt)))))
                    .astype(np.int32) for _ in range(n_req)]

                def one_pass():
                    t0 = time.perf_counter()
                    reqs = [svc.submit_async(p, self.serve_max_new, 0.0,
                                             None) for p in prompts]
                    toks = sum(len(svc.batcher.wait(r)) for r in reqs)
                    return toks / max(1e-9, time.perf_counter() - t0)

                one_pass()              # warm-up: compile off the clock
                return max(one_pass()
                           for _ in range(max(1, space.probe_repeats)))
            finally:
                svc.close(30.0)

        def feasible(cand):
            if cand.get('spec_k', 0) > 0 and draft is None:
                return 'spec_k needs a serve.draft model'
            if 'pages' in cand and 'slots' in cand \
                    and cand['pages'] < cand['slots']:
                return 'fewer KV pages than decode slots'
            return None

        # baseline engine warm-up fills the ledger for stage-1 pricing
        svc0 = build(baseline)
        try:
            svc0.engine.resident_bytes()
        finally:
            svc0.close(30.0)
        gate = self._tune_gate(space, baseline, feasible=feasible)
        return TuneSearch(space, probe, gate=gate,
                          baseline=baseline).run('decode')

    def task_autotune(self) -> None:
        """``task=autotune``: run the two-stage grafttune search over
        the declared ``autotune=`` space and write the reproducible
        artifact pair — byte-deterministic ``tuned_<mode>.conf`` plus a
        JSON receipt stamping every probe — into ``model_dir``."""
        space = self._tune_space
        os.makedirs(self.name_model_dir, exist_ok=True)
        if space.mode == 'decode':
            result = self._autotune_decode(space)
        else:
            result = self._autotune_train(space)
        conf = result.write_conf(os.path.join(
            self.name_model_dir, f'tuned_{space.mode}.conf'))
        result.write_receipt(os.path.join(
            self.name_model_dir, f'tuned_{space.mode}.json'))
        if not self.silent:
            print(f'autotune: best {result.best} '
                  f'speedup {result.speedup:.3f}x over {result.baseline} '
                  f'({result.stage1_pruned} pruned by ledger, '
                  f'{result.measured} measured, {result.failed} failed, '
                  f'wall {result.wall_s:.1f}s of {space.budget:g}s) '
                  f'-> {conf}', flush=True)

    def task_extract(self) -> None:
        assert self.itr_pred is not None, 'must specify a pred iterator'
        node = self.extract_node_name or 'top[-1]'
        print(f'start extracting feature from {node}...')
        import numpy as np
        tr = self.net_trainer
        feats = list(tr.forward_stream(self.itr_pred,
                                       tr.net.node_index(node)))
        out = np.concatenate(feats, axis=0)
        if self.output_format == 1:
            np.savetxt(self.name_pred, out.reshape(out.shape[0], -1), '%g')
        else:
            out.astype('<f4').tofile(self.name_pred)
        print(f'finished extract, write into {self.name_pred}')

    def run(self, argv: List[str]) -> int:
        if not argv:
            print('Usage: <config> [k=v ...]')
            return 0
        cfg = parse_config_file(argv[0])
        cfg = apply_cli_overrides(cfg, argv[1:])
        for name, val in cfg:
            self.set_param(name, val)
        if self.task not in _TASKS:
            raise ValueError(
                f'unknown task {self.task!r}; choose from {sorted(_TASKS)}')
        if self.task == 'train' and self.dist_rank < 0 \
                and (self.dist_hosts > 1
                     or (self.dist_hosts == 1 and self.dist_launch)):
            # elastic launcher role: own the coordinator, spawn one
            # worker per host, respawn preempted ranks.  Dispatched
            # BEFORE init() — the launcher never builds a net or touches
            # a device; workers replay this same argv with their rank
            # appended (doc/fault_tolerance.md "Multi-host recovery")
            from .parallel.elastic import ElasticLauncher
            return ElasticLauncher(
                argv=list(argv), hosts=self.dist_hosts,
                rejoin=self.dist_rejoin, heartbeat=self.dist_heartbeat,
                silent=bool(self.silent),
                # fleet observability: merged rank-labeled /metrics,
                # cross-rank (fleet.*) SLOs, per-host-lane trace merge
                fleet_port=self.obs_fleet_port,
                sample_every=self.obs_sample_every,
                slo_specs=[(n, v) for n, v in self.slo_specs
                           if v.startswith('fleet.')],
                trace_merge=self.obs_trace_merge).run()
        # classic jax.distributed world (param_server=dist / cluster
        # env): one global mesh over every host's devices
        from .parallel.distributed import maybe_init_distributed
        maybe_init_distributed(self.cfg)
        plan = None
        if self.fault_plan:
            # deterministic fault injection (tests/chaos drills): the plan
            # drives the SAME hooks production faults exercise
            from .runtime import faults
            plan = faults.FaultPlan.parse(self.fault_plan)
            faults.install_plan(plan)
            if not self.silent:
                print(f'fault plan armed: {plan.describe()}', flush=True)
        self._obs_start()
        try:
            self.init()
            self._obs_register_iterators()
            if not self.silent:
                print('initializing end, start working')
            if self.task == 'serve' and self.serve_mode == 'decode':
                self.task_serve_decode()
            else:
                getattr(self, _TASKS[self.task])()
        finally:
            self._obs_stop()
        if plan is not None and not self.silent:
            # chaos-drill closure: which events actually fired, and what
            # the runtime saw/did about them (doc/fault_tolerance.md)
            from .runtime import faults
            fired = plan.fired()
            print(f"fault plan fired: {'; '.join(fired) or 'nothing'} "
                  f'(failure log: {faults.global_failure_log().summary()})',
                  flush=True)
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    enable_compile_cache()
    return LearnTask().run(argv if argv is not None else sys.argv[1:])


if __name__ == '__main__':
    sys.exit(main())
