"""Continuous-batching decode engine over a paged KV cache.

PR 2's serving stack covers fixed-shape predict; this module opens the
autoregressive path (doc/serving.md "Continuous decode").  The design
goal is the one μ-cuDNN teaches for training applied at serving time:
the work granularity per step — here, WHICH requests ride each decode
step — sets utilization, so the decode loop is ONE persistent compiled
program that requests join and leave at token boundaries:

* **slots** — the compiled step advances a fixed number of request
  slots at once (inactive slots compute into a scratch page and are
  ignored).  A request admitted by the ``DynamicBatcher`` joins a free
  slot at the next token boundary, emits tokens incrementally, and
  leaves on EOS / horizon / deadline — the program never retraces as
  traffic changes,
* **paged KV cache** — K/V live in a fixed pool of fixed-size pages;
  each slot holds a page table mapping its logical cache positions to
  physical pages.  Pages are allocated on demand as a stream grows and
  freed the moment it ends, so memory scales with *live tokens*, not
  ``slots × horizon``.  When the pool runs dry the youngest stream is
  preempted with a typed ``DecodePagesExhaustedError`` carrying its
  token-level progress,
* **bitwise-twin discipline** — per-request sampling RNG is derived
  exactly as ``transformer.generate`` derives it
  (``jax.random.split(rng, max_new + 1)``; pick *n* uses key *n*), the
  prefill and per-token step run through the SAME module functions
  (``transformer.prefill_kv`` / ``transformer.decode_step``), and the
  paged pool gathers into the same dense cache layout before attending
  — so every request's token stream equals an offline
  ``transformer.generate`` call with the same seed, no matter when it
  joined the running loop or who shared its steps.

Two serving multipliers ride the same pool (ROADMAP item 2):

* **prefix sharing** (``serve.prefix_share``, doc/serving.md "Prefix
  sharing") — a content-addressed index maps (model version, pad width,
  logical page, exact token span) -> physical page for every FULL
  prompt page a prefill produced.  A new request whose prompt prefix
  hits the index splices the shared physical pages into its page table
  (refcounted — a page frees only when its last referencing page table
  AND the index let go) and prefills only the tail, attending over the
  shared rows; full shared pages are immutable by construction (decode
  writes only at positions past the prompt bucket), and the one
  partially-filled last page is privately rematerialized by the tail
  prefill — the copy-on-write rule.  N requests sharing a system
  prompt cost ONE prefill and one set of pages,
* **greedy speculative decoding** (``serve.draft``/``serve.spec_k``,
  doc/serving.md "Speculative decoding") — a small draft model
  proposes K-1 tokens per slot from its own dense per-slot cache; the
  target verifies the whole (slots, K) window in ONE multi-token step
  (``transformer.verify_step``) and accepts the longest agreeing prefix
  plus one corrected token.  Every accepted token is the target's own
  greedy argmax at its position, so the stream is TOKEN-EQUAL to the
  target decoding alone — the bitwise-twin discipline holds with a
  draft bolted on, on every ``serve.dtype`` tier.

The attention gathers each slot's pages into a dense (T, heads, hd) view
per step and runs the shared ``transformer.decode_step`` math on it.
``dtype`` selects the quantized-inference tier (``serve.dtype``,
doc/serving.md "Quantized inference"): ``bf16`` casts
params/pool/compute to bfloat16, ``int8`` additionally stores matmul weights as per-channel int8
(``nnet/quantize.py``) consumed through the W8A8 ``qdot`` leg — either
way the stream still has an EXACT offline twin (``transformer.generate``
over the engine's own stored tree + compute config).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..nnet import quantize
from ..parallel import mesh as mesh_mod
from ..obs import format_report, record_event, span
from ..runtime import faults as _faults
from ..runtime.faults import (DeadlineExceededError, DecodePagesExhaustedError,
                              DecodeSlotsExhaustedError,
                              PrefixIndexFullError, RequestAbandonedError,
                              ServeError, TokenDeadlineExceededError)
from ..utils.metric import StatSet

__all__ = ['DecodeEngine', 'DecodeService', 'save_lm_params',
           'load_lm_params', 'lm_loader', 'LM_PATTERN']


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _prompt_bucket(s0: int) -> int:
    """The engine's prompt size-class — ``generate()``'s bucketing rule
    in ONE place, so admission (``_admit``) and the batcher's pricing
    (``prefill_cost``) can never disagree about a prompt's bucket."""
    if os.environ.get('CXXNET_GEN_BUCKETS', '1') != '0':
        return T._size_class(s0, floor=8)
    return s0


class _Slot:
    """Host-side record of one occupied decode slot."""

    __slots__ = ('req', 's0b', 'w', 'pos', 'kidx', 'last_tok', 'temp',
                 'keys', 'max_new', 'join_seq', 'last_emit')

    def __init__(self, req, s0b, w, tok0, keys, temp, max_new, join_seq):
        self.req = req
        self.s0b = int(s0b)
        self.w = int(w)
        self.pos = int(s0b)       # next cache position to write
        self.kidx = 1             # next sampling key index (tok0 used 0)
        self.last_tok = int(tok0)
        self.temp = float(temp)
        self.keys = keys          # (max_new + 1, 2) uint32
        self.max_new = int(max_new)
        self.join_seq = int(join_seq)
        self.last_emit = time.monotonic()


class DecodeEngine:
    """Slot-based continuous decode over a paged KV pool.

    ``params``/``cfg`` are a ``models.transformer`` tree and config
    (single-device; ``cfg.causal`` required).  ``slots`` is the width of
    the persistent compiled step; ``pages``/``page_size`` size the
    physical KV pool (page 0 is a scratch page for idle slots, so
    ``pages - 1`` are allocatable); ``max_prompt``/``max_new_bound``
    bound one request's horizon and fix the slot cache length ``T``
    (page-aligned).  ``eos_id`` is engine-wide (it is baked into the
    compiled step, exactly as ``generate`` bakes it per program).

    ``dtype`` (``serve.dtype``) selects the quantized serving tier:
    ``bf16``/``int8`` replace the compute config's dtype with bfloat16
    (params, KV pool and block math follow), int8 additionally storing
    matmul weights per-channel quantized (``nnet/quantize.py``) —
    either way :attr:`params`/:attr:`cfg` remain the stream oracle:
    ``transformer.generate(engine.params, ..., engine.cfg)`` is
    bitwise-equal to the engine's streams on every tier.
    ``kv_host_mb``/``kv_disk_mb``/``kv_dir``/``kv_share_dir``
    (``serve.kv_*``) attach the graftcache tier hierarchy behind the
    prefix index: evicted index entries demote host → disk instead of
    dropping, later probes promote them back without a re-prefill, and
    a share directory lets N replicas adopt each other's tier-2
    records (doc/serving.md "Tiered KV cache"); requires
    ``prefix_share > 0``.

    Requests arrive through :meth:`execute_requests` (the
    ``DynamicBatcher`` hands over each coalesced batch — the engine owns
    completion) or :meth:`submit_direct`.  Per request ``meta``:
    ``max_new`` (default ``max_new_bound``), ``temperature`` (0 =
    greedy), ``rng`` (a jax PRNG key or int seed; required when
    sampling).  Emitted token ids stream into ``req.tokens`` as they are
    picked; ``req.result`` is the final int32 array.  A stream ends at
    its first EOS — the offline twin keeps emitting EOS after it, so
    equality is prefix + implied-EOS tail.
    """

    def __init__(self, params, cfg, *, slots: int = 4, pages: int = 64,
                 page_size: int = 16, max_prompt: int = 64,
                 max_new_bound: int = 64, eos_id: Optional[int] = None,
                 stats: Optional[StatSet] = None, name: str = 'lm',
                 dtype: str = 'f32',
                 prefix_share: int = 0, spec_k: int = 0, draft=None,
                 kv_host_mb: int = 0, kv_disk_mb: int = 0,
                 kv_dir: Optional[str] = None,
                 kv_share_dir: Optional[str] = None,
                 shard: str = '', prefill_workers: int = 0):
        if not cfg.causal:
            raise ValueError('DecodeEngine requires a causal config')
        if slots < 1 or pages < 2 or page_size < 1:
            raise ValueError('need slots >= 1, pages >= 2 (page 0 is '
                             'scratch), page_size >= 1')
        if prefix_share < 0:
            raise ValueError('prefix_share must be >= 0 (a page cap; '
                             '0 disables sharing)')
        if spec_k < 0 or (spec_k >= 2 and draft is None):
            raise ValueError('spec_k >= 2 needs a draft model '
                             '(draft=(params, cfg)); spec_k must be >= 0')
        if kv_host_mb < 0 or kv_disk_mb < 0:
            raise ValueError('kv_host_mb / kv_disk_mb must be >= 0')
        if (kv_host_mb or kv_disk_mb) and prefix_share <= 0:
            raise ValueError('the tiered KV cache sits behind the '
                             'prefix index: serve.kv_host_mb/kv_disk_mb '
                             'need serve.prefix_share > 0')
        if kv_disk_mb > 0 and not kv_dir:
            raise ValueError('serve.kv_disk_mb > 0 needs serve.kv_dir= '
                             '(the tier-2 record directory)')
        if kv_share_dir and kv_disk_mb <= 0:
            raise ValueError('serve.kv_share_dir shares tier-2 records: '
                             'it needs serve.kv_disk_mb > 0')
        # quantized tier (serve.dtype): bf16/int8 serve with a bfloat16
        # compute config — params, KV pool and block math all follow
        # cfg.dtype, so the offline twin is generate(engine.params,
        # engine.cfg) for EVERY tier
        self.serve_dtype = quantize.parse_serve_dtype(dtype)
        if self.serve_dtype != 'f32':
            cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        # --- tensor-parallel decode (serve.shard, doc/serving.md
        # "Sharded serving"): a 1xN ('data', 'model') mesh; every matmul
        # weight column-shards its LAST axis over 'model' and the K/V
        # page pool shards its heads axis, with explicit all-gather
        # boundaries (transformer._rep) keeping the residual stream
        # replicated — column-sliced matmuls preserve each output
        # element's contraction order, so every stream stays a BITWISE
        # twin of single-device generate at any shard width.
        self._tp = mesh_mod.parse_shard(shard)
        self._mesh = None
        if self._tp > 1:
            if cfg.num_heads % self._tp:
                raise ValueError(
                    f'serve.shard=tp:{self._tp} must divide num_heads='
                    f'{cfg.num_heads} (the KV pool shards per head)')
            if cfg.num_experts:
                raise ValueError('serve.shard supports dense FFN only '
                                 '(num_experts > 0 is unsupported)')
            if slots < 2:
                # XLA lowers the degenerate single-row attention dot
                # (b*q == 1) through a different contraction blocking at
                # one head per device — measured 1-ulp drift at tp:4.
                # A sharded engine exists to widen batching anyway.
                raise ValueError('serve.shard=tp:N needs slots >= 2 '
                                 '(the bitwise-twin contract excludes '
                                 'single-row steps)')
            self._mesh = mesh_mod.decode_mesh(self._tp)
        self.cfg = cfg
        self.name = name
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.n_pages = int(pages)
        self.max_prompt = int(max_prompt)
        self.max_new_bound = int(max_new_bound)
        self.eos_id = eos_id
        self.stats = stats if stats is not None else StatSet()
        horizon = T._size_class(self.max_prompt, floor=8) + max_new_bound
        self.pages_per_slot = _ceil_div(horizon, self.page_size)
        self.cache_len = self.pages_per_slot * self.page_size   # T
        hd = cfg.d_model // cfg.num_heads
        pool_shape = (cfg.num_stages, self.n_pages, self.page_size,
                      cfg.num_heads, hd)
        # sharded engines split the pool per head: each head's K/V pages
        # live on the head's device, so aggregate page capacity scales
        # with the mesh while the per-device slice stays one chip's share
        pool_sh = (None if self._mesh is None else NamedSharding(
            self._mesh, P(None, None, None, 'model', None)))
        self._kpool = jax.device_put(np.zeros(pool_shape, cfg.dtype),
                                     pool_sh)
        self._vpool = jax.device_put(np.zeros(pool_shape, cfg.dtype),
                                     pool_sh)
        self._cond = threading.Condition()
        # physical page 0 is scratch: idle slots write there, nobody reads
        self._free_pages: List[int] = list(
            range(self.n_pages - 1, 0, -1))       # guarded-by: _cond
        # per-physical-page reference counts: every referencing page
        # table holds one, the prefix index holds one more while an
        # entry points at the page — a page returns to the free list
        # only at zero, so preempting a stream can never free a page
        # another slot (or a future prefix hit) still reads
        self._page_refs = np.zeros(self.n_pages,
                                   np.int32)       # guarded-by: _cond
        self._free_min = self.n_pages - 1          # guarded-by: _cond
        # content-addressed FULL-prefix-page index (doc/serving.md
        # "Prefix sharing"): (version, w, logical page, exact padded
        # token span) -> {page, host K/V rows}.  OrderedDict = LRU;
        # bounded by ``prefix_share`` pages.  Host row mirrors let the
        # admitting thread run the tail prefill without touching the
        # loop-owned device pools.
        self._prefix_cap = int(prefix_share)
        self._prefix: collections.OrderedDict = (
            collections.OrderedDict())             # guarded-by: _cond
        # graftcache (serve/kvcache.py): host/disk tiers BEHIND the
        # index — eviction demotes host mirrors down-tier, a later probe
        # promotes them back into a freshly allocated physical page.
        # The cache owns its own `kv` StatSet (hub-registered by the
        # CLI) and its own internal lock; this engine only ever calls
        # it while holding _cond (demote/take) or with no lock at all
        # (prefetch) — lock order _cond -> kvcache._lock, never back.
        self._kv = None
        self.kv_stats: Optional[StatSet] = None
        if kv_host_mb > 0 or kv_disk_mb > 0:
            from .kvcache import KVStore, TieredKVCache
            self.kv_stats = StatSet()
            kv_store = None
            if kv_disk_mb > 0:
                kv_store = KVStore(kv_dir, kv_disk_mb * (1 << 20),
                                   share_dir=kv_share_dir,
                                   stats=self.kv_stats, name=name)
            self._kv = TieredKVCache(host_bytes=kv_host_mb * (1 << 20),
                                     store=kv_store, stats=self.kv_stats)
        # tier-promoted rows awaiting their device upload: (physical
        # page, host K rows, host V rows), each holding its own page
        # reference until the decode loop writes the rows at the next
        # token boundary — a promoting page is never an eviction victim
        self._pending_uploads: collections.deque = (
            collections.deque())                   # guarded-by: _cond
        self._table = np.zeros((self.slots, self.pages_per_slot),
                               np.int32)           # guarded-by: _cond
        self._slots: List[Optional[_Slot]] = (
            [None] * self.slots)                  # guarded-by: _cond
        self._joinq: collections.deque = (
            collections.deque())                  # guarded-by: _cond
        self._admitting = 0   # guarded-by: _cond (admit..join window)
        self._join_seq = 0    # guarded-by: _cond
        self._closed = False  # guarded-by: _cond
        # LOGICAL capacity caps — the autoscaler's grow/shrink surface
        # (serve/autoscale.py).  The PHYSICAL slots/pages are baked into
        # the compiled step (``decode.step`` declares bound=1, so a
        # resize would be a retrace the recompile sentinel rightly
        # flags); scaling therefore clamps ADMISSION only.  Shrinking
        # never touches a live stream: in-flight page growth stays
        # uncapped and a referenced page can never be freed (refcounts).
        self._live_slot_cap = self.slots           # guarded-by: _cond
        self._live_page_cap = self.n_pages - 1     # guarded-by: _cond
        # the ORIGINAL (pre-quantization) structure is the hot-swap
        # contract: .lm files always carry the f32 tree, place_params
        # validates against it and re-quantizes into the serving tier
        self._ref_treedef = jax.tree.structure(params)
        self._ref_shapes = [(tuple(l.shape), l.dtype)
                            for l in jax.tree.leaves(params)]
        self._params = self.place_params(params)  # guarded-by: _cond
        self._params_treedef = jax.tree.structure(self._params)
        self._pending_params = None   # guarded-by: _cond
        self._pending_version = None  # guarded-by: _cond
        self.version: object = 0
        self.swap_count = 0
        # --- greedy speculative decoding (serve.draft / serve.spec_k):
        # the draft keeps a DENSE per-slot cache (it is small — paging
        # and sharing buy nothing) advanced only inside spec windows
        self._spec_k = int(spec_k)
        self._draft_params = None          # guarded-by: _cond
        self._pending_draft = None         # guarded-by: _cond
        self._pending_draft_version = None  # guarded-by: _cond
        self.draft_version: object = -1
        self._draft_cfg = None
        if draft is not None:
            dparams, dcfg = draft
            if not dcfg.causal:
                raise ValueError('draft model must be causal')
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f'draft vocab {dcfg.vocab_size} != target '
                    f'{cfg.vocab_size}: the verify window compares '
                    'token ids, the vocabularies must match')
            if self.serve_dtype != 'f32':
                dcfg = dataclasses.replace(dcfg, dtype=jnp.bfloat16)
            self._draft_cfg = dcfg
            self._draft_ref_treedef = jax.tree.structure(dparams)
            self._draft_ref_shapes = [tuple(l.shape) for l in
                                      jax.tree.leaves(dparams)]
            self._draft_params = self.place_draft_params(dparams)
            self._draft_placed_treedef = jax.tree.structure(
                self._draft_params)
            dhd = dcfg.d_model // dcfg.num_heads
            dshape = (dcfg.num_stages, self.slots, self.cache_len,
                      dcfg.num_heads, dhd)
            # the draft rides the mesh REPLICATED (it is small; its head
            # count need not divide tp) — duplicated compute, zero
            # collectives, bitwise-identical proposals on every device
            drep = (None if self._mesh is None
                    else NamedSharding(self._mesh, P()))
            self._kdc = jax.device_put(np.zeros(dshape, dcfg.dtype),
                                       drep)
            self._vdc = jax.device_put(np.zeros(dshape, dcfg.dtype),
                                       drep)
        # guarded-by: _pf_lock (prefill/tail program caches — touched by
        # prefill worker threads concurrently, never under _cond)
        self._pf_lock = threading.Lock()
        self._prefill_fns: collections.OrderedDict = collections.OrderedDict()
        self._tail_fns: collections.OrderedDict = collections.OrderedDict()
        self._spec_fns: dict = {}
        self._write_fns: dict = {}
        self._dwrite_fns: dict = {}
        # compiler-truth ledger rows (obs/programs.py): the decode step
        # is ONE program by construction (preallocated pools), declared
        # bound=1 so any shape drift trips the recompile sentinel;
        # prefill/tail/spec are LRU-bucketed ladders (unbounded
        # declaration — the gen-cache LRU is their own churn policy)
        from ..obs.programs import get_ledger
        _led = get_ledger()
        self._prog_step = _led.program('decode.step', bound=1)
        self._prog_prefill = _led.program('decode.prefill')
        self._prog_tail = _led.program('decode.tail_prefill')
        self._prog_spec = _led.program('decode.spec')
        self._step = self._build_step()
        # lint: allow(jit-ledger): one scalar-pick program ever (traced temperature); nothing a ledger row would say
        self._pick1 = jax.jit(self._pick_one)
        self._loop = threading.Thread(target=self._run, daemon=True,
                                      name=f'cxxnet-decode-{name}')
        self._loop.start()
        # -- disaggregated prefill: dedicated worker threads own the
        # prompt-prefill leg so a long cold prompt never serializes
        # behind another inside the batcher hand-off; finished KV
        # reaches the loop through the same _joinq token-boundary
        # integration as inline admission (streams stay bitwise twins
        # — only WHO ran the prefill program changes, never its math)
        # guarded-by: _cond (queue + worker wakeups)
        self._prefillq: collections.deque = collections.deque()
        self._prefill_threads: list = []
        for i in range(max(0, int(prefill_workers))):
            t = threading.Thread(target=self._prefill_worker, daemon=True,
                                 name=f'cxxnet-prefill-{name}-{i}')
            t.start()
            self._prefill_threads.append(t)

    # -- compiled programs -------------------------------------------------
    @staticmethod
    def _pick_one(logits, key, temp):
        """Traced-temperature pick for ONE request (prefill's first
        token): same categorical/argmax math as ``generate``'s static-
        temperature pick — identical operand values give identical
        draws, so one program covers every request temperature."""
        safe = jnp.where(temp > 0, temp, jnp.float32(1.0))
        sampled = jax.random.categorical(key, logits / safe, axis=-1)
        return jnp.where(temp > 0, sampled,
                         jnp.argmax(logits, axis=-1)).astype(jnp.int32)

    @staticmethod
    def _pick_slots(logits, r, temp):
        """Per-slot pick: per-slot keys, per-slot draws — bitwise the
        same stream the offline b=1 generate pulls from the same key
        schedule."""
        greedy = jnp.argmax(logits, axis=-1)
        safe = jnp.where(temp > 0, temp, jnp.float32(1.0))
        sampled = jax.vmap(
            lambda k_, lg, t_: jax.random.categorical(
                k_, lg / t_, axis=-1))(r, logits, safe)
        return jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)

    def _build_step(self):
        cfg = self.cfg
        S, ps, pp = self.slots, self.page_size, self.pages_per_slot
        Tlen = self.cache_len
        hd = cfg.d_model // cfg.num_heads

        mesh = self._mesh

        def step(params, kpool, vpool, table, pos, w, tok, r, temp):
            # gather each slot's pages into the dense cache layout the
            # shared decode_step math expects (gather is an exact copy:
            # the paged-vs-dense twin test pins this bitwise).  The
            # shard scope arms transformer._rep's all-gather boundaries
            # for the trace (identity when mesh is None).
            with T.shard_scope(mesh):
                st = kpool.shape[0]
                kc = kpool[:, table].reshape(st, S, Tlen,
                                             cfg.num_heads, hd)
                vc = vpool[:, table].reshape(st, S, Tlen,
                                             cfg.num_heads, hd)
                logits, _, _, knew, vnew = T.decode_step(
                    params, cfg, tok, kc, vc, pos, w)
                # scatter only the newly written rows back into the pool
                page = table[jnp.arange(S), pos // ps]
                off = pos % ps
                si = jnp.arange(st)[:, None]
                kpool = kpool.at[si, page[None, :],
                                 off[None, :]].set(knew)
                vpool = vpool.at[si, page[None, :],
                                 off[None, :]].set(vnew)
                nxt = self._pick_slots(logits, r, temp)
                return kpool, vpool, nxt

        return self._prog_step.jit(step, donate_argnums=(1, 2),
                                   key='gather', fixed=True)

    def _prefill_fn(self, s0b: int, draft: bool = False):
        key = ('draft', s0b) if draft else s0b
        with self._pf_lock:
            fn = self._prefill_fns.get(key)
            if fn is not None:
                self._prefill_fns.move_to_end(key)
                return fn
        self.stats.inc('prefill_programs')   # retrace visibility
        cfg = self._draft_cfg if draft else self.cfg
        mesh = None if draft else self._mesh

        def prefill(params, prompt, w):
            with T.shard_scope(mesh):
                return T.prefill_kv(params, prompt, w, cfg)

        fn = self._prog_prefill.jit(
            prefill, key=f'{"draft_" if draft else ""}s{s0b}',
            fixed=True)
        with self._pf_lock:
            self._prefill_fns[key] = fn
            # same LRU bound (and env knob) as generate's program cache
            while len(self._prefill_fns) > T._gen_cache_max():
                self._prefill_fns.popitem(last=False)
        return fn

    def _tail_fn(self, t0: int, tt: int):
        """Jitted prefix-shared tail prefill, keyed by (prefix, tail)
        lengths (``w`` stays a traced value, like the full prefill)."""
        with self._pf_lock:
            fn = self._tail_fns.get((t0, tt))
            if fn is not None:
                self._tail_fns.move_to_end((t0, tt))
                return fn
        self.stats.inc('prefill_programs')
        cfg = self.cfg
        mesh = self._mesh

        def tail_prefill(params, pk, pv, tail, w):
            with T.shard_scope(mesh):
                return T.prefill_tail_kv(params, pk, pv, tail, w, cfg)

        fn = self._prog_tail.jit(tail_prefill, key=f't{t0}+{tt}',
                                 fixed=True)
        with self._pf_lock:
            self._tail_fns[(t0, tt)] = fn
            while len(self._tail_fns) > T._gen_cache_max():
                self._tail_fns.popitem(last=False)
        return fn

    def _dwrite_fn(self, s0b: int):
        """Jitted draft-cache prompt write: the draft's prefill rows for
        one slot land in the dense per-slot cache (``sid`` is traced —
        one program per prompt bucket covers every slot)."""
        fn = self._dwrite_fns.get(s0b)
        if fn is None:
            def dwrite(kdc, vdc, dks, dvs, sid):
                kdc = jax.lax.dynamic_update_slice(
                    kdc, dks, (0, sid, 0, 0, 0))
                vdc = jax.lax.dynamic_update_slice(
                    vdc, dvs, (0, sid, 0, 0, 0))
                return kdc, vdc
            # lint: allow(jit-ledger): two dynamic-update-slices — cache keyed by the same prompt buckets the ledgered prefill already rows
            fn = self._dwrite_fns[s0b] = jax.jit(dwrite,
                                                 donate_argnums=(0, 1))
        return fn

    def _spec_fn(self, K: int):
        """Jitted speculative round at window width ``K``: K-1 greedy
        draft proposals (sequential ``decode_step``s over the dense
        draft cache) + ONE target ``verify_step`` over the (slots, K)
        window, its new K/V rows scattered into the page pool.  Returns
        the consumed window and
        the target's per-position greedy picks; acceptance is host-side
        (variable per slot)."""
        fn = self._spec_fns.get(K)
        if fn is None:
            self.stats.inc('spec_programs')
            cfg, dcfg = self.cfg, self._draft_cfg
            S, ps, Tlen = self.slots, self.page_size, self.cache_len
            hd = cfg.d_model // cfg.num_heads
            mesh = self._mesh

            def spec(params, dparams, kpool, vpool, kdc, vdc, table,
                     pos, w, tok):
                # draft proposals run OUTSIDE the shard scope: the
                # draft is replicated on the mesh, so its steps are
                # duplicated (bitwise-identical) compute per device
                window = [tok]
                dtok = tok
                for k in range(K - 1):
                    dlogits, kdc, vdc, _, _ = T.decode_step(
                        dparams, dcfg, dtok, kdc, vdc, pos + k, w)
                    dtok = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                    window.append(dtok)
                toks = jnp.stack(window, axis=1)            # (S, K)
                with T.shard_scope(mesh):
                    st = kpool.shape[0]
                    kc = kpool[:, table].reshape(st, S, Tlen,
                                                 cfg.num_heads, hd)
                    vc = vpool[:, table].reshape(st, S, Tlen,
                                                 cfg.num_heads, hd)
                    logits, _, _, knew, vnew = T.verify_step(
                        params, cfg, toks, kc, vc, pos, w)
                    tq = pos[:, None] + jnp.arange(K)[None, :]
                    page = table[jnp.arange(S)[:, None], tq // ps]
                    off = tq % ps
                    si = jnp.arange(st)[:, None, None]
                    kpool = kpool.at[si, page[None],
                                     off[None]].set(knew)
                    vpool = vpool.at[si, page[None],
                                     off[None]].set(vnew)
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return kpool, vpool, kdc, vdc, toks, tgt

            fn = self._spec_fns[K] = self._prog_spec.jit(
                spec, donate_argnums=(2, 3, 4, 5), key=f'k{K}',
                steps=K, fixed=True)
        return fn

    def _write_fn(self, n_pages: int, nrows: int):
        """Jitted prompt-K/V scatter: ``nrows`` prefilled rows into
        ``n_pages`` physical pages (the whole prompt, or just the tail
        past a prefix hit)."""
        key = (n_pages, nrows)
        fn = self._write_fns.get(key)
        if fn is None:
            ps = self.page_size

            def write(kpool, vpool, ks, vs, pages):
                st = kpool.shape[0]
                pad = n_pages * ps - nrows
                shaped = []
                for arr in (ks, vs):
                    a = arr[:, 0]                      # (stages, s0b, H, hd)
                    a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    shaped.append(a.reshape(st, n_pages, ps,
                                            a.shape[-2], a.shape[-1]))
                kpool = kpool.at[:, pages].set(shaped[0])
                vpool = vpool.at[:, pages].set(shaped[1])
                return kpool, vpool

            # lint: allow(jit-ledger): pure pad+scatter of already-prefilled rows; the compute it stores was rowed by decode.prefill
            fn = self._write_fns[key] = jax.jit(write,
                                                donate_argnums=(0, 1))
        return fn

    # -- parameters (PredictEngine-compatible surface) ---------------------
    @property
    def params(self):
        with self._cond:
            return self._params

    def oracle_params(self):
        """The serving tree AS AN OFFLINE ORACLE should see it: for a
        sharded engine, a host copy — ``transformer.generate`` over
        mesh-committed leaves would itself compile SPMD and is NOT the
        single-device reference the twin contract pins against."""
        p = self.params
        if self._mesh is None:
            return p
        return jax.tree.map(np.asarray, p)

    def _check_tree(self, params) -> None:
        if jax.tree.structure(params) != self._ref_treedef:
            raise ValueError('swap_params: param tree structure differs '
                             'from the serving model')
        # dtype is part of the contract only on the f32 tier — the
        # quantized tiers normalize every incoming float dtype anyway
        strict = self.serve_dtype == 'f32'
        for leaf, (shape, dtype) in zip(jax.tree.leaves(params),
                                        self._ref_shapes):
            if tuple(leaf.shape) != shape or \
                    (strict and leaf.dtype != dtype):
                raise ValueError(
                    f'swap_params: leaf {tuple(leaf.shape)}/{leaf.dtype} '
                    f'!= serving {shape}/{dtype} — a shape change needs '
                    'a new engine, not a hot swap')

    def _quantize(self, host_tree):
        """Load/swap-time quantization into the serving tier — the hot
        path never re-quantizes weights (doc/serving.md)."""
        if self.serve_dtype == 'f32':
            return host_tree
        return quantize.quantize_tree(host_tree, self.serve_dtype,
                                      out_dtype=self.cfg.dtype,
                                      quant_key=quantize.lm_quant_key)

    def place_params(self, host_params):
        # this method's own output (the registry's warm->swap sequence
        # re-passes it) short-circuits the validate+quantize: an int8
        # tree is structurally distinct, a bf16 one re-casts to itself
        already = (getattr(self, '_params_treedef', None) is not None
                   and self._params_treedef != self._ref_treedef
                   and jax.tree.structure(host_params)
                   == self._params_treedef)
        if not already:
            if getattr(self, '_ref_treedef', None) is not None:
                self._check_tree(host_params)
            host_params = self._quantize(host_params)
        if self._mesh is not None:
            return self._shard_tree(host_params)
        return jax.tree.map(
            lambda h: h if isinstance(h, jax.Array)
            else jax.device_put(np.asarray(h)), host_params)

    def _shard_tree(self, tree):
        """Tensor-parallel placement of a (possibly quantized) param
        tree: matmul weights column-shard their LAST axis over 'model'
        (wq/wk/wv/wo/embed/head/w1/w2 — the layout transformer._rep's
        all-gather boundaries assume), QuantLeaf scales co-shard with
        their q (``quantize.shard_put``), and everything else — norms,
        biases, non-dividing leaves — replicates onto the mesh so no
        leaf stays committed to a lone device."""
        mesh, tpn = self._mesh, self._tp

        def one(name, leaf):
            nd = getattr(leaf, 'ndim', 0)
            if (name in quantize.LM_MATMUL_KEYS and 2 <= nd <= 3
                    and leaf.shape[-1] % tpn == 0):
                spec = (None,) * (nd - 1) + ('model',)
            else:
                spec = (None,) * nd
            return quantize.shard_put(leaf, mesh, P(*spec))

        return quantize._map_named(one, tree)

    def warm_params(self, params) -> None:
        placed = self.place_params(params)
        jax.block_until_ready(jax.tree.leaves(placed))

    def swap_params(self, params, version: object = None) -> None:
        """Hot-swap with DRAIN semantics: in-flight streams finish on
        the params they started with (one compiled step takes one tree —
        mixing versions inside a step is impossible by construction);
        new admissions wait, join under the new tree once the last
        pre-swap stream leaves.  Zero requests are dropped.  Blocks
        until the swap is applied."""
        placed = self.place_params(params)
        with self._cond:
            if self._closed:
                raise ServeError('decode engine is closed')
            while self._pending_params is not None:
                self._cond.wait(0.05)
            self._pending_params = placed
            self._pending_version = version
            self._cond.notify_all()
            while self._pending_params is not None and not self._closed:
                self._cond.wait(0.05)

    # -- speculative-decode draft model ------------------------------------
    def place_draft_params(self, host_params):
        """Validate + quantize a draft tree into the serving tier (the
        SAME tier as the target — verify consumes both through one
        ``qdot`` dispatch) and place it on device."""
        if self._draft_cfg is None:
            raise ValueError('engine was built without a draft model')
        td = jax.tree.structure(host_params)
        if td == self._draft_ref_treedef:
            # treedefs are shape-blind (target and draft trees share the
            # same nesting): a wrong-architecture tree must fail HERE,
            # typed, not at the next spec round's trace
            for leaf, shape in zip(jax.tree.leaves(host_params),
                                   self._draft_ref_shapes):
                if tuple(leaf.shape) != shape:
                    raise ValueError(
                        f'swap_draft_params: leaf {tuple(leaf.shape)} != '
                        f'draft {shape} — a shape change needs a new '
                        'engine, not a hot swap')
            host_params = quantize.quantize_lm_tree(
                host_params, self.serve_dtype,
                out_dtype=self._draft_cfg.dtype)
        elif td != getattr(self, '_draft_placed_treedef', None):
            raise ValueError('swap_draft_params: tree structure differs '
                             'from the draft model')
        if self._mesh is not None:
            # replicated on the mesh (see the draft-cache placement)
            rep = NamedSharding(self._mesh, P())
            return jax.tree.map(
                lambda h: jax.device_put(np.asarray(h) if not
                                         isinstance(h, jax.Array) else h,
                                         rep), host_params)
        return jax.tree.map(
            lambda h: h if isinstance(h, jax.Array)
            else jax.device_put(np.asarray(h)), host_params)

    def warm_draft_params(self, params) -> None:
        placed = self.place_draft_params(params)
        jax.block_until_ready(jax.tree.leaves(placed))

    def swap_draft_params(self, params, version: object = None) -> None:
        """Hot-swap the DRAFT tree with the same drain semantics as
        :meth:`swap_params`.  A draft change can never alter a stream
        (verify acceptance guards every token), so this only affects
        acceptance rate — but the drain keeps one spec round on one
        draft tree by construction."""
        placed = self.place_draft_params(params)
        with self._cond:
            if self._closed:
                raise ServeError('decode engine is closed')
            while self._pending_draft is not None:
                self._cond.wait(0.05)
            self._pending_draft = placed
            self._pending_draft_version = version
            self._cond.notify_all()
            while self._pending_draft is not None and not self._closed:
                self._cond.wait(0.05)

    # -- prefix index (requires-lock helpers) ------------------------------
    def _prefix_keys(self, padded, w, n):  # requires-lock: _cond
        """Content keys for the first ``n`` full pages of a padded
        prompt: (model version, pad width, logical page, EXACT token
        span through that page) — dict equality does the exact match,
        so there is no hash-collision correctness risk."""
        ps = self.page_size
        row = padded[0]
        return [(self.version, w, lp, row[:(lp + 1) * ps].tobytes())
                for lp in range(n)]

    def _prefix_probe(self, padded, w, s0b, touch=True):  # requires-lock: _cond
        """Longest consecutive full-page prefix hit: returns (n_hit,
        pages, host_k_rows, host_v_rows).  Hits must cover every bucket-
        pad slot (``n_hit * ps >= w``) so the tail prefill only ever
        sees real queries, and always leave >= 1 tail token to
        regenerate the last-position logits (>= 2 when sharded: XLA
        lowers a fully degenerate one-row-per-device dot differently,
        so the twin contract excludes single-query tails)."""
        ps = self.page_size
        max_hit = (s0b - 1 - (self._mesh is not None)) // ps
        pages, hks, hvs = [], [], []
        for key in self._prefix_keys(padded, w, max_hit):
            ent = self._prefix.get(key)
            if ent is None:
                break
            if touch:
                self._prefix.move_to_end(key)
            pages.append(ent['page'])
            hks.append(ent['hk'])
            hvs.append(ent['hv'])
        if len(pages) * ps < w:
            return 0, [], [], []
        return len(pages), pages, hks, hvs

    def _prefix_evict_one(self, demote: bool = True) -> bool:  # requires-lock: _cond
        """Drop the LRU index entry; frees its page when the index held
        the last reference.  With a tiered cache attached the entry's
        host mirrors DEMOTE down-tier instead of dropping (memory-moves
        only — spill I/O happens on the store's worker thread, never
        under this lock).  ``demote=False`` on param swaps: the rows
        are the old model's activations and their keys carry the old
        version — caching them would be pure waste."""
        if not self._prefix:
            return False
        key, ent = self._prefix.popitem(last=False)
        if demote and self._kv is not None and key[0] == self.version:
            self._kv.demote(key, ent['hk'], ent['hv'])
        self._release_pages([ent['page']])
        return True

    def _prefix_publish(self, padded, w, s0b, pages, hk_full, hv_full):  # requires-lock: _cond
        """Insert every not-yet-indexed FULL page of a just-prefilled
        prompt (immutable by construction: decode writes only at
        positions >= s0b).  ``pages``/``hk_full``/``hv_full``:
        the slot's physical pages and host K/V row mirrors for
        positions [0, s0b).  LRU-evicts at the ``prefix_share`` cap; a
        prompt whose shareable pages exceed the whole cap raises
        :class:`PrefixIndexFullError` internally — recorded, served
        unshared, never surfaced to the request."""
        ps = self.page_size
        n_pub = s0b // ps
        keys = self._prefix_keys(padded, w, n_pub)
        fresh = [i for i, k in enumerate(keys) if k not in self._prefix]
        if not fresh:
            return
        if len(fresh) > self._prefix_cap:
            self.stats.inc('prefix_index_full')
            from ..runtime import faults
            faults.global_failure_log().record(
                'prefix_index_full',
                repr(PrefixIndexFullError(n_pub, self._prefix_cap)))
            return
        for i in fresh:
            while len(self._prefix) >= self._prefix_cap:
                if not self._prefix_evict_one():
                    return               # cap raced to 0: give up quietly
            page = int(pages[i])
            self._page_refs[page] += 1   # the index's own reference
            self._prefix[keys[i]] = {
                'page': page,
                'hk': hk_full[:, i * ps:(i + 1) * ps],
                'hv': hv_full[:, i * ps:(i + 1) * ps]}
            self.stats.inc('prefix_published')

    def _reclaim_index_pages(self, n: int, exclude=()):  # requires-lock: _cond
        """Free up to ``n`` pages by dropping LRU index entries whose
        page the index alone still references — the pool-dry path
        prefers forgetting cold prefixes over preempting live streams.
        ``exclude``: physical pages that must survive even at refcount
        1 — the admission path passes the prefix pages it just probed,
        which its slot is about to splice (freeing one would alias the
        same physical page as both a shared prefix page and a fresh
        allocation, and tail writes would clobber the prefix rows)."""
        freed = 0
        for key in list(self._prefix):
            if freed >= n:
                break
            ent = self._prefix[key]
            if ent['page'] in exclude:
                continue
            if self._page_refs[ent['page']] == 1:
                if self._kv is not None and key[0] == self.version:
                    self._kv.demote(key, ent['hk'], ent['hv'])
                del self._prefix[key]
                self._release_pages([ent['page']])
                freed += 1
                self.stats.inc('prefix_reclaimed')
        return freed

    def _clear_prefix_index(self) -> None:  # requires-lock: _cond
        """Release every index reference (param swaps: cached rows are
        the OLD model's activations — stale keys would leak pages).
        Never demotes: the tiers must not inherit a dead version's rows
        (old-version entries already down-tier can never alias — the
        version is part of every key and every record header)."""
        while self._prefix:
            self._prefix_evict_one(demote=False)

    def _promote_splice(self, padded, w, s0b, n_hit,  # requires-lock: _cond
                        pages, hks, hvs) -> int:
        """Extend the index hit chain with tier-promoted pages: for
        each consecutive full page past ``n_hit`` whose rows tier 1
        holds (prefetched from disk OUTSIDE this lock), take the rows,
        re-publish the key against the freshly allocated physical page
        ``pages[lp]``, and queue the device upload for the decode loop
        (which scatters it at the next token boundary, BEFORE any join
        splices a table row at it).  Promoted pages join ``hks/hvs`` so
        the tail prefill attends over them exactly as over index hits —
        the promoted rows ARE the original prefill rows, so streams
        stay bitwise twins.  Returns the new ``n_hit``; memory-moves
        only, safe under the lock."""
        ps = self.page_size
        max_hit = (s0b - 1 - (self._mesh is not None)) // ps
        if n_hit >= max_hit:
            return n_hit
        keys = self._prefix_keys(padded, w, max_hit)
        taken = []
        for lp in range(n_hit, max_hit):
            ent = self._kv.take(keys[lp])
            if ent is None:
                break
            taken.append((keys[lp], ent))
        if not taken:
            return n_hit
        if (n_hit + len(taken)) * ps < w:
            # the probe's pad-coverage rule: hits must span every pad
            # slot or the tail prefill would see pad queries — put the
            # rows back rather than serve a chain we cannot splice
            for key, (hk, hv) in taken:
                self._kv.put_back(key, hk, hv)
            return n_hit
        for i, (key, (hk, hv)) in enumerate(taken):
            page = int(pages[n_hit + i])
            while len(self._prefix) >= self._prefix_cap:
                if not self._prefix_evict_one():
                    break
            if len(self._prefix) < self._prefix_cap:
                # the index's own reference, exactly as publish takes
                self._page_refs[page] += 1
                self._prefix[key] = {'page': page, 'hk': hk, 'hv': hv}
                self.stats.inc('prefix_published')
            # the pending upload's reference: until the rows land, the
            # page can be neither reclaimed nor reallocated
            self._page_refs[page] += 1
            self._pending_uploads.append((page, hk, hv))
            hks.append(hk)
            hvs.append(hv)
            self.stats.inc('kv_promoted_pages')
        return n_hit + len(taken)

    # -- page accounting (requires-lock helpers) ---------------------------
    def _alloc_pages(self, n: int) -> List[int]:  # requires-lock: _cond
        pages = [self._free_pages.pop() for _ in range(n)]
        for p in pages:
            self._page_refs[p] = 1
        if len(self._free_pages) < self._free_min:
            self._free_min = len(self._free_pages)
        return pages

    def _release_pages(self, pages) -> None:  # requires-lock: _cond
        """Drop one reference per page; a page returns to the free list
        only when nobody — page table or index — references it."""
        for p in pages:
            p = int(p)
            self._page_refs[p] -= 1
            if self._page_refs[p] <= 0:
                self._page_refs[p] = 0
                self._free_pages.append(p)
        self._cond.notify_all()

    def prefill_cost(self, req) -> int:
        """Admission-cost estimate for the batcher's coalescing budget
        (serve/batcher.py): the tokens THIS prompt's prefill would
        actually compute right now — a prefix-index hit costs only its
        tail.  Non-binding (the index can shift before admission); never
        touches the LRU clock."""
        prompt = np.asarray(req.data, np.int32)
        if prompt.ndim != 2 or prompt.shape[0] != 1:
            return max(1, int(prompt.size))
        s0 = prompt.shape[1]
        s0b = _prompt_bucket(s0)
        w = s0b - s0
        if self._prefix_cap <= 0:
            return s0b
        padded = np.pad(prompt, ((0, 0), (w, 0)))
        with self._cond:
            n_hit, _, _, _ = self._prefix_probe(padded, w, s0b,
                                                touch=False)
        return max(1, s0b - n_hit * self.page_size)

    def resident_bytes(self) -> int:
        """Device-memory ledger entry for the budgeter: params + pools
        (+ the draft tree and its dense cache when spec decoding).
        The paged KV pool is ONE allocation counted ONCE — prefix
        sharing multiplies page-table references, never this number
        (pinned by a regression test: two slots sharing a prefix report
        the same footprint as one).  The tiered cache's host/disk bytes
        are deliberately EXCLUDED: they are not device memory, and
        folding them in would double-count tiers against the
        ``hbm.headroom_frac`` / ``budget_drift()`` cross-check (their
        occupancy reports through the ``kv.*`` gauges instead; pinned
        by a kv_tier regression test)."""
        with self._cond:
            params = self._params
            draft = self._draft_params
            pool = self._kpool.nbytes + self._vpool.nbytes
            if self._draft_cfg is not None:
                pool += self._kdc.nbytes + self._vdc.nbytes
        total = pool + sum(l.nbytes for l in jax.tree.leaves(params))
        if draft is not None:
            total += sum(l.nbytes for l in jax.tree.leaves(draft))
        return int(total)

    def resident_bytes_per_device(self) -> list:
        """Per-device split of :meth:`resident_bytes` for sharded
        engines: one entry per mesh device, summed from each array's
        ``addressable_shards`` (replicated leaves — norms, biases, the
        draft — count their FULL bytes on EVERY device, matching what
        the allocator actually holds there).  Unsharded engines return
        the scalar as a one-entry vector so callers never branch.  The
        sum over devices therefore EXCEEDS ``resident_bytes()`` exactly
        by the replication overhead — the budgeter prices the max-
        loaded device, not the sum."""
        if self._mesh is None:
            return [self.resident_bytes()]
        with self._cond:
            arrs = list(jax.tree.leaves(self._params))
            arrs += [self._kpool, self._vpool]
            if self._draft_cfg is not None:
                arrs += [self._kdc, self._vdc]
            if self._draft_params is not None:
                arrs += list(jax.tree.leaves(self._draft_params))
        per = {d.id: 0 for d in self._mesh.devices.flat}
        for arr in arrs:
            for sh in arr.addressable_shards:
                if sh.device.id in per:
                    per[sh.device.id] += sh.data.nbytes
        return [per[d.id] for d in self._mesh.devices.flat]

    def kv_occupancy(self) -> Optional[Tuple[int, int]]:
        """``(host_bytes, disk_bytes)`` held by the tiered cache, or
        None when no tiers are attached — the fleet-report surface.
        Deliberately separate from :meth:`resident_bytes`: tier bytes
        are host/disk, never HBM, and must not feed the budgeter."""
        if self._kv is None:
            return None
        self._kv.refresh_gauges()
        store = self._kv.store
        return (self._kv.host_bytes(),
                0 if store is None else store.disk_bytes())

    def busy(self) -> bool:
        with self._cond:
            return (any(s is not None for s in self._slots)
                    or bool(self._joinq) or self._admitting > 0
                    or bool(self._prefillq))

    def set_live_limits(self, max_slots: Optional[int] = None,
                        max_pages: Optional[int] = None):
        """Clamp admission capacity live (the autoscaler's decode knob).

        Caps clamp to [1, physical]; a shrink takes effect at the next
        admission attempt — streams already past admission keep every
        page they grow into (the cap gates entry, not survival), so no
        autoscale action can ever corrupt or preempt a live stream.
        Returns the effective ``(slot_cap, page_cap)``."""
        with self._cond:
            if max_slots is not None:
                self._live_slot_cap = max(1, min(int(max_slots),
                                                 self.slots))
            if max_pages is not None:
                self._live_page_cap = max(1, min(int(max_pages),
                                                 self.n_pages - 1))
            self._cond.notify_all()
            return (self._live_slot_cap, self._live_page_cap)

    def live_limits(self):
        """Current logical ``(slot_cap, page_cap)`` admission clamps."""
        with self._cond:
            return (self._live_slot_cap, self._live_page_cap)

    def capacity_view(self) -> dict:
        """Physical vs live capacity in one snapshot — the autoscaler's
        ``/statusz`` provider surfaces this per bound engine."""
        with self._cond:
            return {'slots': self.slots,
                    'pages': self.n_pages - 1,
                    'live_slot_cap': self._live_slot_cap,
                    'live_page_cap': self._live_page_cap,
                    'free_pages': len(self._free_pages),
                    'occupied': sum(1 for s in self._slots
                                    if s is not None)}

    # -- admission ---------------------------------------------------------
    @property
    def buckets(self):
        """DynamicBatcher protocol: coalesce at most ``slots`` requests
        (one row each) per window."""
        return (self.slots,)

    def execute_requests(self, batch) -> None:
        """Batcher hand-off: admit each coalesced request into a slot
        (blocking for capacity up to its deadline).  The engine owns
        completion — per-request errors land on the request, never the
        worker.  With ``prefill_workers`` the hand-off is a queue push:
        dedicated prefill threads run admission concurrently, so one
        long cold prompt never heads-of-line-blocks the prompts behind
        it in the same coalescing window."""
        for req in batch:
            queued = False
            if self._prefill_threads:
                with self._cond:
                    if not self._closed:
                        self._prefillq.append(req)
                        self._cond.notify_all()
                        queued = True
            if not queued:
                self._admit_one(req)

    def _admit_one(self, req) -> None:
        """Admit ONE request, converting failures into typed
        per-request outcomes (never a raised exception — the caller
        may be a batcher worker or a prefill thread)."""
        try:
            self._admit(req)
        except BaseException as e:  # typed per-request outcome
            if isinstance(e, DeadlineExceededError):
                self.stats.inc('expired')
            elif isinstance(e, RequestAbandonedError):
                self.stats.inc('abandoned')
            elif isinstance(e, (DecodeSlotsExhaustedError,
                                DecodePagesExhaustedError)):
                self.stats.inc('shed_inadmissible')
            else:
                self.stats.inc('engine_errors')
            req.error = e
            req.event.set()

    def _prefill_worker(self) -> None:
        """Dedicated prefill thread: pop queued requests and run the
        full admission path (reserve -> prefill -> joinq).  After
        close(), the queue drains through ``_admit_one`` so every
        still-queued request fails typed (ServeError) instead of
        hanging its waiter."""
        while True:
            with self._cond:
                while not self._prefillq and not self._closed:
                    self._cond.wait(0.05)
                if not self._prefillq:
                    return          # closed and drained
                req = self._prefillq.popleft()
            self._admit_one(req)

    def submit_direct(self, prompt, max_new: int = None,
                      temperature: float = 0.0, rng=None,
                      deadline: float = 30.0):
        """Batcher-less admission (tests / embedding without a queue):
        returns the ``ServeRequest``; wait on ``req.event``."""
        from .batcher import ServeRequest
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        req = ServeRequest(prompt, deadline,
                           meta={'max_new': max_new,
                                 'temperature': temperature, 'rng': rng})
        self.execute_requests([req])
        return req

    def _admit(self, req) -> None:
        prompt = np.asarray(req.data, np.int32)
        if prompt.ndim != 2 or prompt.shape[0] != 1 or prompt.shape[1] < 1:
            raise ValueError('decode request payload must be one prompt '
                             'row: (1, s0) int tokens')
        s0 = prompt.shape[1]
        meta = req.meta or {}
        raw = meta.get('max_new')
        max_new = self.max_new_bound if raw is None else int(raw)
        temp = float(meta.get('temperature') or 0.0)
        rng = meta.get('rng')
        if max_new < 1:
            raise ValueError('max_new must be >= 1')
        if temp > 0 and rng is None:
            raise ValueError('temperature>0 sampling needs an rng key')
        s0b = _prompt_bucket(s0)
        w = s0b - s0
        if max_new > self.max_new_bound:
            raise DecodeSlotsExhaustedError(
                f'max_new={max_new} > engine bound {self.max_new_bound}')
        if s0b + max_new - 1 > self.cache_len:
            raise DecodeSlotsExhaustedError(
                f'prompt bucket {s0b} + max_new {max_new} exceeds the '
                f'slot cache ({self.cache_len} positions)')
        total_pages = (s0b + max_new - 2) // self.page_size + 1 \
            if max_new >= 2 else _ceil_div(s0b, self.page_size)
        if total_pages > min(self.pages_per_slot, self.n_pages - 1):
            raise DecodeSlotsExhaustedError(
                f'request needs {total_pages} KV pages; the pool can '
                f'offer at most {min(self.pages_per_slot, self.n_pages - 1)}')
        n_prompt = _ceil_div(s0b, self.page_size)
        # reserve the prompt pages plus the first decode position's page
        # now; later pages allocate on demand as the stream grows
        n0 = (s0b // self.page_size + 1) if max_new >= 2 else n_prompt
        ps = self.page_size
        padded = np.pad(prompt, ((0, 0), (w, 0)))
        if self._kv is not None and (s0b - 1) // ps > 0:
            # tier-2 promote prefetch: disk records rise into the host
            # tier HERE, on the admit thread with NO engine lock held —
            # the reserve loop's take() below is then memory-only.  The
            # reads are ThreadBuffer-double-buffered in the cache.
            with self._cond:
                want = [k for k in
                        self._prefix_keys(padded, w, (s0b - 1) // ps)
                        if k not in self._prefix]
            self._kv.prefetch(want)
        # --- reserve capacity (blocks; bounded by the request deadline)
        with self._cond:
            while True:
                if self._closed:
                    raise ServeError('decode engine is closed')
                if getattr(req, 'abandoned', False):
                    # the client walked away while we waited for
                    # capacity: a typed drop, never a burned slot
                    raise RequestAbandonedError(
                        time.monotonic() - req.t_submit)
                if total_pages > self._live_page_cap:
                    # autoscaler-clamped pool: shed fast and typed
                    # instead of waiting out a deadline the clamp
                    # guarantees we'd miss (the cap may grow back —
                    # the CLIENT retries, the queue does not)
                    raise DecodeSlotsExhaustedError(
                        f'request needs {total_pages} KV pages but the '
                        f'live page cap is {self._live_page_cap} '
                        f'(physical pool {self.n_pages - 1})')
                n_hit, hit_pages, hks, hvs = (
                    self._prefix_probe(padded, w, s0b)
                    if self._prefix_cap > 0 else (0, [], [], []))
                need = n0 - n_hit
                occupied = sum(1 for s in self._slots if s is not None)
                if (self._pending_params is None
                        and self._pending_draft is None
                        and occupied < self._live_slot_cap):
                    used = self.n_pages - 1 - len(self._free_pages)
                    # index-only pages count as used, so a shrunk live
                    # cap must reclaim them too — but never the hit
                    # pages this request is about to splice
                    short = max(need - len(self._free_pages),
                                used + need - self._live_page_cap)
                    if short > 0:
                        self._reclaim_index_pages(
                            short, exclude=set(hit_pages))
                        used = self.n_pages - 1 - len(self._free_pages)
                    if (len(self._free_pages) >= need
                            and used + need <= self._live_page_cap):
                        break
                remaining = req.deadline_abs - time.monotonic()
                if remaining <= 0:
                    raise DeadlineExceededError(
                        req.deadline, time.monotonic() - req.t_submit, 1)
                self._cond.wait(min(remaining, 0.05))
            sid = self._slots.index(None)
            self._slots[sid] = 'RESERVED'          # placeholder
            for p in hit_pages:                    # splice shared pages
                self._page_refs[p] += 1
            pages = list(hit_pages) + self._alloc_pages(need)
            if self._kv is not None:
                n_hit = self._promote_splice(padded, w, s0b, n_hit,
                                             pages, hks, hvs)
                self.kv_stats.inc('hits' if n_hit else 'misses')
            if n_hit:
                self.stats.inc('prefix_hits')
                self.stats.inc('prefix_hit_pages', n_hit)
                if n_hit == (s0b - 1) // ps and s0b % ps:
                    # the divergence page: everything shareable was
                    # shared, the partial last page is privately
                    # rematerialized by the tail prefill (the CoW rule)
                    self.stats.inc('cow_copies')
            elif self._prefix_cap > 0:
                self.stats.inc('prefix_misses')
            self._admitting += 1
            params = self._params
            draft_params = self._draft_params
            seq = self._join_seq
            self._join_seq += 1
        try:
            # --- RNG schedule: exactly generate()'s derivation
            if temp > 0:
                key = (jax.random.PRNGKey(rng) if isinstance(rng, int)
                       else rng)
                keys = np.asarray(jax.random.split(key, max_new + 1))
            else:
                keys = np.zeros((max_new + 1, 2), np.uint32)
            # --- prefill off the loop thread (joins stay token-aligned):
            # a prefix hit computes ONLY the tail, attending over the
            # shared rows' host mirrors (never the loop-owned pools)
            if n_hit:
                record_event('decode.prefix_hit', 'decode', req.trace_id,
                             hit_pages=n_hit)
                t0 = n_hit * ps
                with span('decode.tail_prefill', 'decode', req.trace_id,
                          prompt=s0b, tail=s0b - t0):
                    pk = np.concatenate(hks, axis=1)[:, None]
                    pv = np.concatenate(hvs, axis=1)[:, None]
                    ks, vs, logits0 = self._tail_fn(t0, s0b - t0)(
                        params, pk, pv, padded[:, t0:], np.int32(w))
                    hk_full = np.concatenate(
                        [pk[:, 0], np.asarray(ks)[:, 0]], axis=1)
                    hv_full = np.concatenate(
                        [pv[:, 0], np.asarray(vs)[:, 0]], axis=1)
            else:
                with span('decode.prefill', 'decode', req.trace_id,
                          prompt=s0b):
                    ks, vs, logits0 = self._prefill_fn(s0b)(
                        params, padded, np.int32(w))
                hk_full = hv_full = None   # mirrored lazily below
            dks = dvs = None
            if self._draft_cfg is not None and self._spec_k >= 2:
                # the draft full-prefills every prompt (it is small;
                # sharing its dense cache would buy nothing)
                dks, dvs, _ = self._prefill_fn(s0b, draft=True)(
                    draft_params, padded, np.int32(w))
            tok0 = int(self._pick1(logits0[0],
                                   jax.numpy.asarray(keys[0]),
                                   np.float32(temp)))
            if (self._prefix_cap > 0 and s0b // ps
                    and hk_full is None):
                # publish mirrors sync device->host HERE, outside the
                # engine lock — the decode loop takes _cond at every
                # token boundary and must not wait out a D2H copy
                hk_full = np.asarray(ks)[:, 0]
                hv_full = np.asarray(vs)[:, 0]
            now = time.monotonic()
            req.tokens.append(tok0)
            req.token_times.append(now)
            self.stats.inc('tokens')
            record_event('decode.emit', 'decode', req.trace_id,
                         token_index=0)
            done0 = self.eos_id is not None and tok0 == self.eos_id
            with self._cond:
                if done0 or max_new == 1:
                    self._slots[sid] = None
                    self._release_pages(pages)
                    self._finish(req)
                else:
                    # rows still to be written into the pool: the tail
                    # (hit) or the whole prompt (miss)
                    self._joinq.append(
                        {'sid': sid, 'pages': pages,
                         'wpages': pages[n_hit:n_prompt],
                         'wrows': s0b - n_hit * ps,
                         's0b': s0b, 'w': w, 'ks': ks, 'vs': vs,
                         'dks': dks, 'dvs': dvs,
                         'tok0': tok0, 'keys': keys, 'temp': temp,
                         'max_new': max_new, 'req': req, 'seq': seq})
                    self.stats.inc('joined')
                    if hk_full is not None:
                        self._prefix_publish(padded, w, s0b,
                                             pages[:s0b // ps],
                                             hk_full, hv_full)
                self._admitting -= 1
                self._cond.notify_all()
        except BaseException:
            with self._cond:
                self._slots[sid] = None
                self._release_pages(pages)
                self._admitting -= 1
                self._cond.notify_all()
            raise

    # -- the decode loop ---------------------------------------------------
    def _finish(self, req, error: Optional[BaseException] = None) -> None:
        """Complete a request (slot bookkeeping already done)."""
        if error is not None:
            req.error = error
        else:
            req.result = np.asarray(req.tokens, np.int32)
            self.stats.inc('completed')
            self.stats.observe('stream_len', len(req.tokens))
        record_event('decode.finish', 'decode',
                     getattr(req, 'trace_id', None),
                     tokens=len(req.tokens),
                     error=None if error is None else type(error).__name__)
        req.event.set()

    def _free_slot(self, sid: int) -> None:  # requires-lock: _cond
        """Release a slot's page references (caller holds the lock);
        refcounting decides which pages actually return to the pool —
        never one that another slot's table or the prefix index still
        holds."""
        row = self._table[sid]
        self._release_pages(int(p) for p in row[row != 0])
        row[:] = 0
        self._slots[sid] = None
        self._cond.notify_all()

    def _integrate_joins(self) -> None:  # requires-lock: _cond
        """Token boundary: splice every admitted request into its slot
        (caller holds the lock; pool writes release it per join).  A
        prefix-hit join splices the SHARED physical pages and writes
        only its freshly prefilled tail rows.  Tier-promoted pages
        upload FIRST: a promote enqueues its upload strictly before the
        promoted request's join is appended, so draining uploads ahead
        of joins guarantees every promoted page's rows are in the pool
        before any table row can reference it (the decode loop owns the
        device pools — this is the only thread that writes them)."""
        if self._pending_uploads:
            # one scatter for the whole backlog: a promote lands a whole
            # prefix of pages at once, and per-page uploads would pay a
            # dispatch each — batching matches the join path's
            # one-call-per-splice idiom
            batch = list(self._pending_uploads)
            self._pending_uploads.clear()
            ps = self.page_size
            pages = np.asarray([b[0] for b in batch], np.int32)
            hk = np.concatenate([b[1] for b in batch], axis=1)
            hv = np.concatenate([b[2] for b in batch], axis=1)
            wfn = self._write_fn(len(batch), len(batch) * ps)
            self._kpool, self._vpool = wfn(
                self._kpool, self._vpool, hk[:, None], hv[:, None],
                pages)
            # the uploads' own references (taken at promote) retire
            self._release_pages(pages.tolist())
            self.stats.inc('kv_uploads')
        while self._joinq:
            j = self._joinq.popleft()
            sid = j['sid']
            self._table[sid, :len(j['pages'])] = j['pages']
            if j['wpages']:
                wfn = self._write_fn(len(j['wpages']), j['wrows'])
                self._kpool, self._vpool = wfn(
                    self._kpool, self._vpool, j['ks'], j['vs'],
                    np.asarray(j['wpages'], np.int32))
            if j.get('dks') is not None:
                dwfn = self._dwrite_fn(j['s0b'])
                self._kdc, self._vdc = dwfn(
                    self._kdc, self._vdc, j['dks'], j['dvs'],
                    np.int32(sid))
            self._slots[sid] = _Slot(j['req'], j['s0b'], j['w'],
                                     j['tok0'], j['keys'], j['temp'],
                                     j['max_new'], j['seq'])

    def _expire_slots(self, now: float) -> None:  # requires-lock: _cond
        for sid, slot in enumerate(self._slots):
            if not isinstance(slot, _Slot):
                continue
            if now >= slot.req.deadline_abs:
                self.stats.inc('expired')
                self.stats.inc('tokens_shed',
                               slot.max_new - len(slot.req.tokens))
                err = TokenDeadlineExceededError(
                    slot.req.deadline, now - slot.req.t_submit,
                    len(slot.req.tokens))
                req = slot.req
                self._free_slot(sid)
                self._finish(req, err)

    def _alloc_step_pages(self, win: int = 1) -> None:  # requires-lock: _cond
        """On-demand page allocation for every slot about to write into
        an unmapped logical page — the whole ``win``-token window when
        spec decoding (verify writes rows at ``[pos, pos + win)``).
        Pool-dry first reclaims index-only prefix pages, then sheds the
        youngest stream (refcount-aware: a victim's shared pages stay
        alive for everyone else)."""
        order = sorted((s.join_seq, sid) for sid, s in
                       enumerate(self._slots) if isinstance(s, _Slot))
        for _seq, sid in order:
            slot = self._slots[sid]
            if not isinstance(slot, _Slot):
                continue            # shed as a victim earlier this pass
            last = min(slot.pos + win - 1, self.cache_len - 1)
            for lp in range(slot.pos // self.page_size,
                            last // self.page_size + 1):
                if self._table[sid, lp] != 0:
                    continue
                while not self._free_pages:
                    if self._reclaim_index_pages(1):
                        continue
                    victims = [(s.join_seq, vid) for vid, s in
                               enumerate(self._slots)
                               if isinstance(s, _Slot)]
                    vseq, vid = max(victims)
                    vslot = self._slots[vid]
                    self.stats.inc('shed_pages')
                    self.stats.inc('tokens_shed',
                                   vslot.max_new - len(vslot.req.tokens))
                    err = DecodePagesExhaustedError(
                        len(vslot.req.tokens), self.n_pages - 1)
                    vreq = vslot.req
                    self._free_slot(vid)
                    self._finish(vreq, err)
                    if vid == sid:
                        break
                if not isinstance(self._slots[sid], _Slot):
                    break           # shed as its own victim
                if self._free_pages:
                    self._table[sid, lp] = self._alloc_pages(1)[0]

    def _run(self) -> None:
        """Decode-loop thread body; a non-request fault (trace error,
        device loss) fails every in-flight stream with the error instead
        of stranding clients until their deadlines."""
        try:
            self._run_inner()
        except BaseException as e:  # noqa: BLE001 — loop must not vanish
            from ..runtime import faults
            faults.global_failure_log().record(
                'decode_loop_error', f'decode loop died: {e!r}')
            with self._cond:
                self._closed = True
                for sid, slot in enumerate(self._slots):
                    if isinstance(slot, _Slot):
                        req = slot.req
                        self._free_slot(sid)
                        self._finish(req, ServeError(
                            f'decode loop failed: {e!r}'))
                while self._joinq:
                    j = self._joinq.popleft()
                    self._finish(j['req'], ServeError(
                        f'decode loop failed: {e!r}'))
                self._cond.notify_all()

    def _run_inner(self) -> None:
        S = self.slots
        while True:
            # chaos surface: an installed FaultPlan's ``slow_step``
            # events sleep here, OFF the lock and between token
            # boundaries — latency shifts, streams never do
            _faults.decode_step()
            with self._cond:
                while True:
                    self._expire_slots(time.monotonic())
                    # joins first: anything admitted before a pending
                    # swap belongs to the old params' in-flight set
                    self._integrate_joins()
                    live = any(isinstance(s, _Slot) for s in self._slots)
                    if ((self._pending_params is not None
                            or self._pending_draft is not None)
                            and not live
                            and not self._joinq and self._admitting == 0):
                        if self._pending_params is not None:
                            self._params = self._pending_params
                            if self._pending_version is not None:
                                self.version = self._pending_version
                            self._pending_params = None
                            self.swap_count += 1
                            # the cached rows are the OLD model's
                            # activations: stale keys would leak pages
                            self._clear_prefix_index()
                        if self._pending_draft is not None:
                            self._draft_params = self._pending_draft
                            self._pending_draft = None
                            if self._pending_draft_version is not None:
                                self.draft_version = (
                                    self._pending_draft_version)
                                self._pending_draft_version = None
                        self._cond.notify_all()
                        continue
                    if live:
                        break
                    if (self._closed and not self._joinq
                            and self._admitting == 0):
                        return
                    self._cond.wait(0.05)
                # speculative window width: K proposals only when every
                # live stream is greedy (sampled streams keep their
                # per-key RNG schedule — spec pauses, never approximates)
                # and nobody is within K tokens of its horizon
                live_slots = [s for s in self._slots
                              if isinstance(s, _Slot)]
                K_step = 1
                if (self._spec_k >= 2 and self._draft_params is not None
                        and all(s.temp == 0 for s in live_slots)):
                    rem = min(s.max_new - len(s.req.tokens)
                              for s in live_slots)
                    K_step = max(1, min(self._spec_k, rem))
                self._alloc_step_pages(K_step)
                if not any(isinstance(s, _Slot) for s in self._slots):
                    continue        # every stream was shed this pass
                params = self._params
                dparams = self._draft_params
                table = np.array(self._table)
                pos = np.zeros(S, np.int32)
                w = np.zeros(S, np.int32)
                tok = np.zeros(S, np.int32)
                temp = np.zeros(S, np.float32)
                r = np.zeros((S, 2), np.uint32)
                stepped = []
                for sid, slot in enumerate(self._slots):
                    if isinstance(slot, _Slot):
                        pos[sid] = slot.pos
                        w[sid] = slot.w
                        tok[sid] = slot.last_tok
                        temp[sid] = slot.temp
                        r[sid] = slot.keys[slot.kidx]
                        stepped.append(sid)
            # the K/V pools (and the draft's dense caches) are
            # loop-thread-owned between token boundaries;
            # resident_bytes snapshots them under _cond
            if K_step >= 2:
                # hot path: record_event with explicit timestamps (not
                # a span ctx) — one fewer allocation per step, and gc
                # trigger frequency is the recorder's only real cost
                t0_ns = time.monotonic_ns()
                # lint: allow(lock-discipline): single-writer pool handoff (loop thread)
                (self._kpool, self._vpool, self._kdc, self._vdc,
                 window, tgt) = self._spec_fn(K_step)(
                    params, dparams, self._kpool, self._vpool,
                    self._kdc, self._vdc, table, pos, w, tok)
                window = np.asarray(window)
                tgt = np.asarray(tgt)
                # measured THROUGH the host sync above, like the plain
                # step leg — the dispatch alone is async and ~free
                record_event('decode.spec_verify', 'decode',
                             t_start_ns=t0_ns,
                             dur_ns=time.monotonic_ns() - t0_ns,
                             window=K_step, slots=len(stepped))
                now = time.monotonic()
                self.stats.inc('decode_steps')
                self.stats.inc('spec_steps')
                self.stats.observe('step_occupancy', len(stepped) / S)
                with self._cond:
                    for sid in stepped:
                        slot = self._slots[sid]
                        if not isinstance(slot, _Slot):
                            continue   # shed concurrently (defensive)
                        # accept the longest draft prefix the target
                        # agrees with, plus the target's own corrected
                        # token — every accepted token IS the target's
                        # greedy pick at its position
                        a = 0
                        while (a + 1 < K_step
                               and window[sid, a + 1] == tgt[sid, a]):
                            a += 1
                        self.stats.inc('spec_proposed', K_step - 1)
                        self.stats.inc('spec_accepted', a)
                        self.stats.observe('spec_window', a + 1)
                        for token in (int(t) for t in tgt[sid, :a + 1]):
                            slot.req.tokens.append(token)
                            slot.req.token_times.append(now)
                            self.stats.inc('tokens')
                            self.stats.observe(
                                'token_ms',
                                (now - slot.last_emit) * 1e3)
                            slot.last_emit = now
                            slot.last_tok = token
                            slot.pos += 1
                            slot.kidx += 1
                            hit_eos = (self.eos_id is not None
                                       and token == self.eos_id)
                            if (hit_eos or
                                    len(slot.req.tokens) >= slot.max_new):
                                req = slot.req
                                self._free_slot(sid)
                                self._finish(req)
                                break
                continue
            # hot path: explicit-timestamp record, not a span ctx (same
            # reasoning as the spec leg above)
            t0_ns = time.monotonic_ns()
            # lint: allow(lock-discipline): single-writer pool handoff (loop thread)
            self._kpool, self._vpool, nxt = self._step(
                params, self._kpool, self._vpool, table, pos, w, tok,
                r, temp)
            nxt = np.asarray(nxt)
            record_event('decode.step', 'decode', t_start_ns=t0_ns,
                         dur_ns=time.monotonic_ns() - t0_ns,
                         slots=len(stepped))
            now = time.monotonic()
            self.stats.inc('decode_steps')
            self.stats.observe('step_occupancy', len(stepped) / S)
            with self._cond:
                for sid in stepped:
                    slot = self._slots[sid]
                    if not isinstance(slot, _Slot):
                        continue    # shed concurrently (defensive)
                    token = int(nxt[sid])
                    slot.req.tokens.append(token)
                    slot.req.token_times.append(now)
                    self.stats.inc('tokens')
                    self.stats.observe('token_ms',
                                       (now - slot.last_emit) * 1e3)
                    slot.last_emit = now
                    slot.last_tok = token
                    slot.pos += 1
                    slot.kidx += 1
                    hit_eos = (self.eos_id is not None
                               and token == self.eos_id)
                    if hit_eos or len(slot.req.tokens) >= slot.max_new:
                        req = slot.req
                        self._free_slot(sid)
                        self._finish(req)

    # -- lifecycle / observability -----------------------------------------
    def close(self, timeout: Optional[float] = None) -> bool:
        """Stop admitting; finish in-flight streams (bounded by their
        horizons/deadlines); join the loop thread."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        # dead programs must never be AOT-probed again: a later ledger
        # sweep re-lowering a stale (possibly SPMD) skeleton after this
        # engine's mesh is gone can crash the XLA client outright
        for prog in (self._prog_step, self._prog_prefill,
                     self._prog_tail, self._prog_spec):
            prog.retire()
        if threading.current_thread() is self._loop:
            return False
        ok = True
        for t in self._prefill_threads:
            t.join(timeout)
            ok = not t.is_alive() and ok
        self._loop.join(timeout)
        ok = not self._loop.is_alive() and ok
        if self._kv is not None:
            ok = self._kv.close(timeout) and ok
        return ok

    def report(self, name: Optional[str] = None) -> str:
        """Eval-line stats snapshot; folds in the ``generate`` program-
        cache hit/miss tallies (the serve surface for them) and the
        page-pool / prefix-share / spec-decode gauges (free-page
        low-water mark, shared-page count, index size, acceptance
        rate) so both multipliers are observable, not inferred."""
        gs = T.gen_cache_stats()
        self.stats.gauge('gen_cache.hit', gs['hit'])
        self.stats.gauge('gen_cache.miss', gs['miss'])
        with self._cond:
            free = len(self._free_pages)
            self.stats.gauge('free_pages', free)
            self.stats.gauge('free_pages_min', self._free_min)
            self.stats.gauge('pages_used', self.n_pages - 1 - free)
            self.stats.gauge('pages_shared',
                             int((self._page_refs[1:] > 1).sum()))
            self.stats.gauge('prefix_index_pages', len(self._prefix))
            self.stats.gauge('live_slot_cap', self._live_slot_cap)
            self.stats.gauge('live_page_cap', self._live_page_cap)
            if self._prefill_threads:
                self.stats.gauge('prefill_workers',
                                 len(self._prefill_threads))
                self.stats.gauge('prefill_queue', len(self._prefillq))
            if self._kv is not None:
                self.kv_stats.gauge('pending_uploads',
                                    len(self._pending_uploads))
        if self._kv is not None:
            # tier occupancy/hit gauges land on the separate `kv`
            # StatSet (its own /metrics family and SLO set name) —
            # NEVER on resident_bytes/budget_drift: host and disk
            # bytes are not HBM and must not read as such
            self._kv.refresh_gauges()
        proposed = self.stats.get('spec_proposed')
        if proposed:
            self.stats.gauge('spec_accept_rate',
                             self.stats.get('spec_accepted') / proposed)
        if self._tp > 1:
            self.stats.gauge('shard.tp', self._tp)
            for i, b in enumerate(self.resident_bytes_per_device()):
                self.stats.gauge(f'shard.resident_bytes[d{i}]', int(b))
        drift = self.budget_drift()
        if drift is not None:
            self.stats.gauge('budget_drift', round(drift, 4))
        return format_report(name or self.name, self.stats)

    def budget_drift(self) -> Optional[float]:
        """Signed relative drift of the closed-form
        :meth:`resident_bytes` ledger vs the compiled step's
        ``memory_analysis`` argument bytes (obs/programs.py) — the
        cross-check that keeps the MemoryBudgeter's arithmetic honest.
        The step's arguments are params + both pools + O(slots) scalars,
        so the comparison excludes the draft side (its programs are
        separate); None before the first step compiles or when the
        backend has no memory analysis."""
        truth = self._prog_step.argument_bytes()
        if truth <= 0:
            return None
        with self._cond:
            params = self._params
            pool = self._kpool.nbytes + self._vpool.nbytes
        closed = pool + sum(l.nbytes for l in jax.tree.leaves(params))
        return closed / truth - 1.0


# -- on-disk format for transformer param trees ----------------------------
# ``%04d.lm`` files: an .npz of the flattened tree written through the
# same atomic+retried+digested path as model files, so the registry's
# verify/blacklist machinery applies unchanged to decode models.

LM_PATTERN = r'^(\d+)\.lm$'


def _flatten_tree(tree, prefix=''):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_flatten_tree(tree[k], f'{prefix}{k}/'))
        return out
    return {prefix[:-1]: np.asarray(tree)}


def save_lm_params(path: str, params, retry=None) -> str:
    """Atomically write a transformer param tree (+ crc32 sidecar)."""
    from ..nnet import checkpoint
    flat = _flatten_tree(params)
    checkpoint.save_model_file(
        path, lambda f: np.savez(f, **flat), retry=retry)
    checkpoint.write_model_digest(path)
    return path


def load_lm_params(path: str, retry=None):
    """Read a ``save_lm_params`` file back into a nested dict tree."""
    from ..nnet import checkpoint

    def read(f):
        z = np.load(f, allow_pickle=False)
        return {k: z[k] for k in z.files}

    flat = checkpoint.read_model_file(path, read, retry=retry)
    tree: dict = {}
    for key, leaf in flat.items():
        node = tree
        parts = key.split('/')
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def lm_loader(engine, path: str, retry=None):
    """Registry ``loader`` hook for decode models (the structural check
    happens in ``engine.place_params``)."""
    return load_lm_params(path, retry=retry)


class DecodeService:
    """The embeddable continuous-decode stack: admission-controlled
    ``DynamicBatcher`` fronting a ``DecodeEngine``, sharing one StatSet
    (the wrapper/C-ABI surface and the CLI drive both hold one of
    these)."""

    def __init__(self, params, cfg, *, slots: int = 4, pages: int = 64,
                 page_size: int = 16, max_prompt: int = 64,
                 max_new_bound: int = 64, eos_id: Optional[int] = None,
                 max_queue: int = 64, max_wait: float = 0.002,
                 deadline: float = 30.0, dtype: str = 'f32',
                 prefix_share: int = 0,
                 spec_k: int = 0, draft=None, kv_host_mb: int = 0,
                 kv_disk_mb: int = 0, kv_dir: Optional[str] = None,
                 kv_share_dir: Optional[str] = None, shard: str = '',
                 prefill_workers: int = 0):
        from .batcher import DynamicBatcher
        stats = StatSet()
        self.engine = DecodeEngine(
            params, cfg, slots=slots, pages=pages, page_size=page_size,
            max_prompt=max_prompt, max_new_bound=max_new_bound,
            eos_id=eos_id, stats=stats, dtype=dtype,
            prefix_share=prefix_share,
            spec_k=spec_k, draft=draft, kv_host_mb=kv_host_mb,
            kv_disk_mb=kv_disk_mb, kv_dir=kv_dir,
            kv_share_dir=kv_share_dir, shard=shard,
            prefill_workers=prefill_workers)
        # with prefix sharing on, admission prices each request at its
        # ACTUAL prefill cost (a hit is just its tail), so a coalescing
        # window full of hits admits everything while a burst of cold
        # prompts closes early instead of stacking full prefills in
        # front of the decode loop
        cost_kw = {}
        if prefix_share > 0:
            cost_kw = {'cost_fn': self.engine.prefill_cost,
                       'max_cost': 2 * self.engine.max_prompt}
        self.batcher = DynamicBatcher(self.engine, max_queue=max_queue,
                                      max_wait=max_wait, deadline=deadline,
                                      stats=stats, **cost_kw)

    def submit_async(self, prompt, max_new: int, temperature: float = 0.0,
                     rng=None, deadline: Optional[float] = None):
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        return self.batcher.submit_async(
            prompt, deadline=deadline,
            meta={'max_new': max_new, 'temperature': temperature,
                  'rng': rng})

    def generate(self, prompt, max_new: int, temperature: float = 0.0,
                 rng=None, deadline: Optional[float] = None) -> np.ndarray:
        """Submit one prompt and block for its full token stream."""
        req = self.submit_async(prompt, max_new, temperature, rng,
                                deadline)
        self.batcher.wait(req)
        return req.result

    def report(self, name: str = 'decode') -> str:
        return self.engine.report(name)

    def close(self, timeout: Optional[float] = None) -> None:
        self.batcher.close(timeout)
        self.engine.close(timeout)
