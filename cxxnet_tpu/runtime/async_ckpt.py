"""Asynchronous checkpointing: take the save path off the step loop.

The synchronous save path (``nnet/checkpoint.py``, ``nnet/sharded_ckpt.py``)
serializes the full param tree, fsyncs, and renames before the next batch
can run — at aggressive ``save_every`` settings (exactly what a
preemptible fleet wants) the step loop pays the full storage latency at
every boundary.  This module hides that latency the same way the training
step hides gradient communication (arXiv:1711.00705's overlap discipline,
applied to checkpoint I/O):

1. **Snapshot** — at the save boundary the param/opt trees are copied
   *on device* (:func:`snapshot_tree`): a cheap, non-blocking dispatch
   that creates fresh buffers, so the trainer's next donated step
   (``train_step`` donates params, opt_state and, where ``update_period
   > 1`` keeps one, grad_acc) cannot invalidate
   what the writer is about to read.  The device→host transfer happens in
   the background, off the step loop.
2. **Background write** — :class:`AsyncCheckpointer` hands the snapshot to
   a committer thread which materializes the host copy and writes the
   tree via ``sharded_ckpt.save_tree_native``: per-shard files written in
   parallel on a small pool (plain write+fsync — the DIRECTORY rename is
   the atomic unit, so per-file atomicity dances would only add fsyncs),
   one rename commits the step, and the crc32 ``ckpt_digest.json``
   sidecar (same format ``verify_step_dir`` checks, accumulated from the
   in-memory bytes, landed via ``atomic_write``) follows — so
   verification, quarantine, and ``restore_resilient`` treat async and
   sync checkpoints identically.
3. **Double buffer** — at most one save is in flight.  A second boundary
   arriving before the previous write commits blocks only until that
   commit lands (never mid-step), so a slow disk degrades save cadence,
   not step integrity.

Failure semantics match the sync path, one boundary late: the background
write runs under the same ``RetryPolicy`` and the same
``faults.checkpoint_write_attempt`` injection hook; an exhausted retry is
recorded in the ``FailureLog`` (``async_save_failed``) and re-raised at
the next barrier (``submit``/``wait``).  The restore path barriers with
:meth:`AsyncCheckpointer.drain` instead — a failed *save* must never
block *recovery*; restore simply falls back to the previous good step.

Validity gates (e.g. the supervisor's "never save a poisoned checkpoint"
NaN-streak rule) must be resolved at SNAPSHOT time, by the caller, before
``submit`` — once a snapshot is queued it will be committed.
"""

from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from . import faults


def snapshot_tree(tree):
    """Device-side copy of a pytree, safe against donation.

    Every ``jax.Array`` leaf is copied into a fresh device buffer (an
    async dispatch — the step loop does not wait for it); host leaves
    (numpy counters) are copied eagerly, since the trainer mutates its
    counters in place between boundaries.  The result is a snapshot the
    caller may hand to a background writer while training continues
    through donating steps."""
    import jax
    import jax.numpy as jnp

    def snap(x):
        if isinstance(x, jax.Array):
            y = jnp.copy(x)
            try:
                # start the device->host transfer now so the background
                # writer's np.asarray finds it already (or nearly) done
                y.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
            return y
        return np.copy(np.asarray(x))

    return jax.tree.map(snap, tree)


def host_tree(tree):
    """Materialize a (snapshot) pytree on host — the blocking half of the
    device→host copy, meant to run on the background writer thread."""
    import jax
    return jax.tree.map(np.asarray, tree)


class AsyncCheckpointer:
    """Background checkpoint writer: double-buffered, retry-wrapped,
    failure-logged (module docstring has the full contract).

    One instance serializes all its saves (a single committer thread);
    ``workers`` bounds the per-shard write parallelism *within* one save.
    """

    def __init__(self, workers: int = 2,
                 failure_log: Optional[faults.FailureLog] = None):
        self.workers = max(1, int(workers))
        # `is None`, not truthiness: an EMPTY FailureLog is falsy
        self.failure_log = (faults.global_failure_log()
                            if failure_log is None else failure_log)
        self._committer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix='ckpt_commit')
        # leaf-write pool, separate from the committer so a 1-worker
        # configuration cannot deadlock the orchestration on its own pool
        self._io = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix='ckpt_io')
        self._lock = threading.Lock()
        self._future: Optional[Future] = None
        self._in_flight = 0   # guarded-by: _lock (tests/metrics probe)
        self.commits = 0      # guarded-by: _lock
        self.submits = 0
        self._closed = False

    # -- core protocol -----------------------------------------------------
    def submit(self, fn: Callable[[], object], step: Optional[int] = None,
               label: str = 'ckpt') -> Future:
        """Queue ``fn()`` — the complete write (serialize-from-snapshot,
        atomic commit, digest) — on the background writer.

        Blocks until the PREVIOUS save commits (double buffer) and
        re-raises its deferred failure, so errors surface at the same
        boundary cadence the sync path has, one save late."""
        if self._closed:
            raise RuntimeError('AsyncCheckpointer is closed')
        self.wait()

        def task():
            from ..obs import span
            with self._lock:
                self._in_flight += 1
            try:
                with span('ckpt.commit', 'ckpt', step=step, label=label):
                    out = fn()
                with self._lock:
                    self.commits += 1
                return out
            except BaseException as e:
                self.failure_log.record(
                    'async_save_failed', f'{label}: {e!r}', step=step)
                raise
            finally:
                with self._lock:
                    self._in_flight -= 1

        self.submits += 1
        self._future = self._committer.submit(task)
        return self._future

    def wait(self) -> None:
        """Barrier: block until the in-flight save (if any) commits, and
        re-raise its failure.  The final save of a run must always pass
        through here — a process exiting with an uncommitted snapshot
        would silently lose its newest checkpoint."""
        f, self._future = self._future, None
        if f is not None:
            f.result()

    def drain(self) -> None:
        """Barrier for the RESTORE path: wait for the in-flight save but
        swallow its failure (already recorded in the failure log) — a
        failed save must not block recovery; restore falls back to the
        previous good checkpoint."""
        f, self._future = self._future, None
        if f is not None:
            try:
                f.result()
            except BaseException:   # noqa: BLE001 — recorded by task()
                pass

    def pending(self) -> bool:
        f = self._future
        return f is not None and not f.done()

    @property
    def io_pool(self) -> ThreadPoolExecutor:
        """The per-save shard-write pool (``workers`` wide) — submitted
        jobs that write trees themselves (e.g. the CLI's exact-sidecar
        job) pass this to ``save_tree_native`` so ``save_workers``
        governs every async write path, not just ``save_sharded_async``."""
        return self._io

    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight

    def close(self, wait: bool = True) -> None:
        """Drain and shut the pools down.  Idempotent."""
        if self._closed:
            return
        if wait:
            self.drain()
        self._closed = True
        self._committer.shutdown(wait=wait)
        self._io.shutdown(wait=wait)

    # -- convenience writers ----------------------------------------------
    def save_sharded_async(self, ckpt_dir: str, step: int, snapshot,
                           retry: Optional[faults.RetryPolicy] = None,
                           on_commit: Optional[Callable[[str], None]] = None
                           ) -> Future:
        """Queue a native sharded-tree save of ``snapshot`` (a
        :func:`snapshot_tree` result) at ``step``.  Device→host
        materialization, the per-leaf atomic writes (parallel over this
        checkpointer's io pool), the directory commit, and the digest all
        run on the background writer; ``on_commit(path)`` (e.g. pruning)
        runs there too, after the digest lands."""
        from ..nnet import sharded_ckpt

        def job():
            path = sharded_ckpt.save_tree_native(
                ckpt_dir, step, host_tree(snapshot), retry=retry,
                pool=self._io)
            if on_commit is not None:
                on_commit(path)
            return path

        return self.submit(job, step=step, label=f'save_sharded:step_{step}')
