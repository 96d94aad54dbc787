"""Data pipeline core: DataBatch, iterator interface, and the chain factory.

Chained-iterator architecture preserved from the reference
(``src/io/data.h:19-181``, factory ``src/io/data.cpp:23-74``): sources
(``mnist`` | ``imgbin`` | ``img``) are wrapped by augment+batch stages and
optional ``threadbuffer`` / ``membuffer`` prefetch/cache stages, all
assembled from the ordered config pairs of one ``data = .. iter = .. end``
section.  Batches carry NCHW numpy arrays (the host-side layout contract);
the net transposes to NHWC on device.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import numpy as np

from ..utils.thread_buffer import ThreadBuffer

ConfigEntry = Tuple[str, str]


class NormSpec:
    """Deferred input normalization: what the augment stage would have done
    on host ((x - mean) * scale, ``iter_augment_proc-inl.hpp:199-231``),
    carried alongside a raw uint8 batch so the jitted step applies it on
    device instead.  TPU-side redesign: the reference always ships float32
    to the device; shipping the decoded uint8 halves H2D bytes and skips
    the per-batch host cast (see ``device_normalize`` in iter_augment)."""

    __slots__ = ('mean_img', 'mean_vals', 'scale')

    def __init__(self, mean_img=None, mean_vals=None, scale=1.0):
        self.mean_img = mean_img            # (c, y, x) float32 or None
        self.mean_vals = mean_vals          # (c,) float32 or None
        self.scale = float(scale)

    def resolved_mean(self) -> np.ndarray:
        """The mean actually subtracted, with the host augment path's
        priority (per-channel ``mean_value`` outranks a mean image),
        broadcastable against (..., c, y, x).  Single source of truth for
        host ``apply`` and the trainer's device constants."""
        if self.mean_vals is not None:
            return np.asarray(self.mean_vals, np.float32)[:, None, None]
        if self.mean_img is not None:
            return np.asarray(self.mean_img, np.float32)
        return np.zeros((1, 1, 1), np.float32)

    def apply(self, data: np.ndarray) -> np.ndarray:
        """Host-side application of the deferred normalization — the same
        (x - mean) * scale the jitted step runs (trainer._apply_input_norm).
        Used where raw batches leave the device path, e.g. the C-ABI
        ``CXNIOGetData`` contract, which hands out post-augment float
        data."""
        out = np.asarray(data, np.float32)
        return (out - self.resolved_mean()) * self.scale


class DataBatch:
    """One minibatch (``src/io/data.h:83-181``)."""

    __slots__ = ('data', 'label', 'inst_index', 'num_batch_padd',
                 'pad_synthetic', 'extra_data', 'norm_spec')

    def __init__(self, data: np.ndarray, label: np.ndarray,
                 inst_index: Optional[np.ndarray] = None,
                 num_batch_padd: int = 0,
                 extra_data: Optional[List[np.ndarray]] = None,
                 pad_synthetic: bool = False,
                 norm_spec: Optional[NormSpec] = None):
        self.data = data                    # (b, c, y, x) float32, or uint8
        self.label = label                  # (b, label_width) float32
        self.inst_index = inst_index        # (b,) uint32 or None
        self.num_batch_padd = num_batch_padd
        # True when the padd rows are filler (round_batch=0 short tail) and
        # must be masked out of gradients; False when they are real wrapped
        # instances (round_batch=1) that the reference trains on
        self.pad_synthetic = pad_synthetic
        self.extra_data = extra_data or []
        # set when data is raw uint8 and the trainer must normalize on
        # device (device_normalize=1)
        self.norm_spec = norm_spec

    @property
    def batch_size(self) -> int:
        return self.data.shape[0]


class DataInst:
    """One instance (``src/io/data.h:41-57``)."""

    __slots__ = ('index', 'data', 'label', 'extra_data')

    def __init__(self, index: int, data: np.ndarray, label: np.ndarray,
                 extra_data: Optional[List[np.ndarray]] = None):
        self.index = index
        self.data = data                    # (c, y, x)
        self.label = label                  # (label_width,)
        self.extra_data = extra_data or []


class IIterator:
    """Reference iterator protocol: SetParam*, Init, then per-epoch
    BeforeFirst/Next/Value — exposed pythonically as ``__iter__``."""

    def set_param(self, name: str, val: str) -> None:
        pass

    def init(self) -> None:
        pass

    def get_norm_spec(self) -> Optional[NormSpec]:
        """The deferred-normalization spec of the augment stage in this
        chain, or None.  Wrappers delegate to their wrapped iterator."""
        base = getattr(self, 'base', None)
        return base.get_norm_spec() if base is not None else None

    def pipeline_stats(self):
        """The chain's ``utils.metric.StatSet`` of per-stage pipeline
        counters (decode/augment/collate ms, pool occupancy, buffer
        stalls), or None when no stage is instrumented (stats turn on
        with ``nworker``, doc/io.md).  Wrappers delegate."""
        base = getattr(self, 'base', None)
        return base.pipeline_stats() if base is not None else None

    def iter_thunks(self):
        """One epoch pass as zero-arg callables, each materializing the
        next ``DataInst`` — the submission stream of the parallel
        decode/augment pool (``utils/parallel_pool.py``).  Sources whose
        per-instance work is heavy (JPEG decode, ``iter_imbin``)
        override this to DEFER that work into the thunk so pool workers
        carry it; the default wraps ``__iter__`` (work already done on
        the calling thread, the pool still parallelizes augmentation).
        Thunk order must equal ``__iter__`` order — the pool's
        bitwise-identity contract hangs on it."""
        for inst in self:
            yield (lambda inst=inst: inst)

    def is_replay_stable(self) -> bool:
        """True when every ``__iter__`` replays the SAME item sequence —
        the contract supervised fault recovery relies on to re-wind to
        batch k (doc/fault_tolerance.md).  Iterators that reshuffle per
        epoch pass (imgbin/imgbinx with ``shuffle=1``) return False:
        recovery still restores exact params, but the replayed pass sees
        a fresh permutation.  Wrappers delegate to their wrapped
        iterator."""
        base = getattr(self, 'base', None)
        return base.is_replay_stable() if base is not None else True

    def __iter__(self) -> Iterator:
        raise NotImplementedError


class ThreadBufferIterator(IIterator):
    """Batch-level prefetch (``iter_batch_proc-inl.hpp:136-224``).

    ``buffer_deadline = <seconds>`` (config) arms a per-batch watchdog: a
    producer that misses the deadline raises
    ``runtime.faults.PipelineStallError`` instead of blocking the trainer
    forever (0 disables).  The buffer is batch-scoped for deterministic
    stall injection (doc/fault_tolerance.md).

    ``nworker = N`` (config) is accepted here — the natural place to
    size the pipeline — and cascades down the chain to the augment
    stage, which fans per-instance decode+augment across N pool threads
    (``utils/parallel_pool.py``); output stays bitwise identical for
    any N.  When the chain is instrumented (nworker set), this stage's
    producer/consumer stalls land on the same StatSet."""

    def __init__(self, base: IIterator, buffer_size: int = 2):
        self.base = base
        self._buffer_size = buffer_size
        self._deadline = None
        self._first_deadline = None
        self._buf = self._make_buf()

    def _make_buf(self) -> ThreadBuffer:
        # the FIRST batch of an epoch also pays epoch setup (page
        # permutation, cold decode/augment paths), so it gets a grace
        # multiple of the steady-state deadline unless the conf pins one
        first = self._first_deadline
        if first is None and self._deadline is not None:
            first = self._deadline * 5
        return ThreadBuffer(lambda: iter(self.base), self._buffer_size,
                            deadline=self._deadline, first_deadline=first,
                            fault_scope='batch')

    def set_param(self, name, val):
        if name in ('buffer_deadline', 'buffer_first_deadline'):
            if name == 'buffer_deadline':
                self._deadline = float(val) if float(val) > 0 else None
            else:
                self._first_deadline = \
                    float(val) if float(val) > 0 else None
            # join the old buffer's producers before replacing it — a
            # dropped-but-live producer would keep draining the shared
            # base iterator underneath the new buffer
            self._buf.close(timeout=1.0)
            self._buf = self._make_buf()
        self.base.set_param(name, val)

    def init(self):
        self.base.init()

    def close(self, timeout=None):
        """Join any live prefetch producers (see ThreadBuffer.close)."""
        return self._buf.close(timeout)

    def __iter__(self):
        # late-bound: the chain's StatSet exists only after set_param
        # cascaded an ``nworker`` key to the augment stage
        stats = self.base.pipeline_stats()
        self._buf.stats = stats
        if stats is not None and self._deadline is not None \
                and self._first_deadline is None:
            # pooled chains (nworker): the first batch also fills the
            # pool's in-flight window (nworker*4 instances), so the
            # default epoch-setup grace doubles — same rule as the
            # supervisor's watchdog (doc/fault_tolerance.md)
            self._buf._first_deadline = self._deadline * 10
        return iter(self._buf)


class DenseBufferIterator(IIterator):
    """Cache the first ``max_nbatch`` batches in RAM and loop over them
    (``iter_mem_buffer-inl.hpp:16-75``)."""

    def __init__(self, base: IIterator):
        self.base = base
        self.max_nbatch = 0
        self._cache: Optional[List[DataBatch]] = None

    def set_param(self, name, val):
        if name == 'max_nbatch':
            self.max_nbatch = int(val)
        self.base.set_param(name, val)

    def init(self):
        self.base.init()

    def __iter__(self):
        if self._cache is None:
            cache = []
            for batch in self.base:
                cache.append(batch)
                if self.max_nbatch and len(cache) >= self.max_nbatch:
                    break
            self._cache = cache
        return iter(self._cache)


def create_iterator(cfg: List[ConfigEntry]) -> IIterator:
    """Assemble an iterator chain from one config section
    (``src/io/data.cpp:23-74``)."""
    from .iter_batch import BatchAdaptIterator
    from .iter_mnist import MNISTIterator

    it: Optional[IIterator] = None
    for name, val in cfg:
        if name == 'iter':
            if val == 'mnist':
                assert it is None, 'mnist cannot chain over another iterator'
                it = MNISTIterator()
            elif val in ('imgbin', 'imgbinx', 'imgbin_stream', 'img'):
                assert it is None, f'{val} cannot chain over another iterator'
                from .iter_augment import AugmentIterator
                if val == 'img':
                    from .iter_img import ImageIterator
                    src = ImageIterator()
                elif val == 'imgbinx':
                    from .iter_imbin import ImageBinXIterator
                    src = ImageBinXIterator()
                elif val == 'imgbin_stream':
                    from .iter_stream import ImageBinStreamIterator
                    src = ImageBinStreamIterator()
                else:
                    from .iter_imbin import ImageBinIterator
                    src = ImageBinIterator()
                it = BatchAdaptIterator(AugmentIterator(src))
            elif val == 'synth_tokens':
                assert it is None, 'synth_tokens cannot chain over another'
                from .iter_tokens import SynthTokenIterator
                it = SynthTokenIterator()
            elif val == 'threadbuffer':
                assert it is not None, 'must specify input of threadbuffer'
                it = ThreadBufferIterator(it)
            elif val == 'membuffer':
                assert it is not None, 'must specify input of membuffer'
                it = DenseBufferIterator(it)
            elif val == 'attachtxt':
                from .iter_attach import AttachTxtIterator
                assert it is not None, 'must specify input of attachtxt'
                it = AttachTxtIterator(it)
            elif val == 'end':
                break
            else:
                raise ValueError(f'unknown iterator type {val}')
        elif it is not None:
            it.set_param(name, val)
    assert it is not None, 'must specify iterator by iter=itername'
    return it
