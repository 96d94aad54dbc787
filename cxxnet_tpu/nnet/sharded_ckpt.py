"""Sharded checkpointing for mesh-partitioned models.

Two checkpoint systems coexist deliberately:

* the CNN trainer keeps the reference's byte-compatible single-file model
  format (``nnet/checkpoint.py`` — interop with reference-era tooling is
  the contract there);
* the beyond-reference distributed models (the 4D-parallel transformer)
  use orbax: every leaf is written with its sharding metadata, saves are
  atomic (temp dir + rename by orbax), and restore lays shards directly
  onto the target mesh — no host gathering a full replica, which is the
  property that matters once a model outgrows one host.

Directory layout: ``<ckpt_dir>/step_<n>/`` per save; ``latest_step`` scans
for the newest complete one (the ``continue=1`` idiom, reborn sharded).
"""

from __future__ import annotations

import json
import os
import re
import zlib
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..runtime import faults


def _checkpointer():
    import orbax.checkpoint as ocp
    return ocp


_CK = None


def _shared_ck():
    """One StandardCheckpointer per process: its async-commit machinery is
    reused across the training loop's periodic saves."""
    global _CK
    if _CK is None:
        _CK = _checkpointer().StandardCheckpointer()
    return _CK


def _epath(p: str):
    """Filesystem-agnostic path (local or cloud URL) via etils epath —
    an orbax dependency, so always present where this module works."""
    from etils import epath
    return epath.Path(p)


_STEP_RE = re.compile(r'^step_(\d+)$')


def step_dir(ckpt_dir: str, step: int) -> str:
    return os.fspath(_epath(ckpt_dir) / f'step_{step}')


def _absolute(p) -> str:
    # orbax requires absolute paths for local saves; cloud URLs pass
    # through untouched
    s = os.fspath(p)
    return s if '://' in s else os.path.abspath(s)


def _scan_steps(ckpt_dir: str, suffix: str = '') -> List[int]:
    """Step numbers of ``step_<n><suffix>`` dirs, newest first.  One
    scan serves intact and quarantined sets alike; orbax writes into a
    tmp dir and renames on commit, so a plain ``step_N`` dir is
    complete, and anything else (temp, ``.corrupt``) fails the anchored
    match."""
    base = _epath(ckpt_dir)
    if not base.exists():
        return []
    steps = []
    for child in base.iterdir():
        name = child.name
        if suffix:
            if not name.endswith(suffix):
                continue
            name = name[:-len(suffix)]
        m = _STEP_RE.match(name)
        if m and child.is_dir():
            steps.append(int(m.group(1)))
    return sorted(steps, reverse=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest complete checkpoint step in ``ckpt_dir`` (None if empty)."""
    steps = _scan_steps(ckpt_dir)
    return steps[0] if steps else None


def all_steps(ckpt_dir: str) -> List[int]:
    """Every complete checkpoint step in ``ckpt_dir``, newest first.
    Quarantined (``.corrupt``-suffixed) and in-flight temp dirs don't
    match ``step_<n>`` and are skipped."""
    return _scan_steps(ckpt_dir)


def quarantined_steps(ckpt_dir: str) -> List[int]:
    """Steps with a ``step_<n>.corrupt`` quarantine dir, newest first —
    the post-mortem set, so retention policies can bound it."""
    return _scan_steps(ckpt_dir, '.corrupt')


# --- integrity digest ----------------------------------------------------
#
# orbax's temp-dir + rename makes the *directory* appear atomically, but a
# later bit-rot / truncation of a shard file inside it is silent:
# tensorstore has no whole-file checksum we can rely on across drivers.
# Every committed checkpoint therefore gets a ``ckpt_digest.json`` sidecar
# (relpath -> [size, crc32]) written AFTER the commit lands; restore-side
# verification (``verify_step_dir``) catches truncated/flipped shards and
# lets ``restore_resilient`` fall back to the newest intact step.

_DIGEST_NAME = 'ckpt_digest.json'
_PENDING_DIGEST: List[Tuple[int, str]] = []


def _payload_files(path: str) -> List[str]:
    out = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f == _DIGEST_NAME:
                continue
            out.append(os.path.relpath(os.path.join(root, f), path))
    return sorted(out)


def _file_crc(p: str) -> int:
    crc = 0
    with open(p, 'rb') as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def write_digest(path: str) -> None:
    digest = {rel: [os.path.getsize(os.path.join(path, rel)),
                    _file_crc(os.path.join(path, rel))]
              for rel in _payload_files(path)}
    from .checkpoint import atomic_write
    with atomic_write(os.path.join(path, _DIGEST_NAME)) as f:
        f.write(json.dumps(digest).encode())


def verify_step_dir(path: str) -> Optional[str]:
    """Integrity-check one committed checkpoint dir; returns None when it
    verifies, else a human-readable reason.  A checkpoint written before
    digests existed (no sidecar) is treated as unverified-but-plausible:
    restore may still try it (and fall back if orbax rejects it)."""
    dig = os.path.join(path, _DIGEST_NAME)
    if not os.path.exists(dig):
        return None
    try:
        with open(dig) as f:
            digest = json.load(f)
    except (OSError, ValueError) as e:
        return f'unreadable digest: {e!r}'
    for rel, (size, crc) in digest.items():
        p = os.path.join(path, rel)
        if not os.path.exists(p):
            return f'missing shard file: {rel}'
        if os.path.getsize(p) != size:
            return f'truncated shard file: {rel}'
        if _file_crc(p) != crc:
            return f'corrupt shard file: {rel}'
    return None


# --- native tree format ---------------------------------------------------
#
# The async save path (runtime/async_ckpt.py) writes checkpoints WITHOUT
# orbax: one raw-bytes file per leaf (parallel, each through
# ``checkpoint.atomic_write``) plus a JSON manifest mapping tree paths to
# (file, dtype, shape), committed by directory rename — the same
# step_<n>-appears-atomically contract orbax gives, with the write
# parallelism under our control and no event-loop machinery on the hot
# path.  Both formats share ``ckpt_digest.json`` and the step-dir naming,
# so verification, quarantine, pruning, and resilient fallback treat them
# identically; ``restore_sharded`` dispatches on the manifest's presence.

_MANIFEST_NAME = 'tree_manifest.json'
_PACKED_NAME = 'packed_leaves.bin'
_PACK_LIMIT = 1 << 18        # leaves under 256 KiB share one blob file


def _np_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _flat_with_paths(tree) -> List[Tuple[str, object]]:
    """(path-string, leaf) pairs in deterministic tree order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _write_leaf(dirpath: str, fname: str,
                data) -> Tuple[int, int]:
    """Plain write+fsync of one leaf into the UNCOMMITTED temp dir — the
    directory rename is the atomic unit, so a per-leaf atomic_write dance
    would only add a rename and two fsyncs per file.  Returns
    (size, crc32) computed from the in-memory bytes, so the digest never
    re-reads what it just wrote."""
    with open(os.path.join(dirpath, fname), 'wb') as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    size = data.nbytes if isinstance(data, np.ndarray) else len(data)
    return size, zlib.crc32(data) & 0xFFFFFFFF


def save_tree_native(ckpt_dir: str, step: int, host_flat_tree, retry=None,
                     pool=None) -> str:
    """Write a host-materialized pytree as a native ``step_<n>``
    checkpoint: leaves in parallel over ``pool`` (a ThreadPoolExecutor;
    None = sequential), manifest last, then one directory rename commits
    the whole step.  An existing dir for the step is REPLACED (same
    contract as the supervisor's sync save).  The write retries whole
    under ``retry`` and passes through the fault-injection hook; the
    crc32 integrity sidecar (same ``ckpt_digest.json`` format
    ``verify_step_dir`` checks) is accumulated from the in-memory bytes
    during the write — no second read pass — and lands via
    ``atomic_write`` after the commit, then ``shard_committed`` fires:
    identical recovery surface to the orbax path."""
    path = _absolute(step_dir(ckpt_dir, step))
    tmp = f'{path}.tmp.{os.getpid()}'
    # np.require, not ascontiguousarray: the latter promotes 0-d leaves
    # (counters) to shape (1,), which would change the restored tree
    flat = [(keystr, np.require(np.asarray(leaf), requirements='C'))
            for keystr, leaf in _flat_with_paths(host_flat_tree)]
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry
    digest = {}

    def attempt():
        import shutil
        faults.checkpoint_write_attempt(path)
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        digest.clear()
        manifest = {}
        jobs = []
        # small leaves (biases, counters — most of the tree's FILE count,
        # none of its bytes) pack into one blob: per-file fsync cost, not
        # bandwidth, is what bounds the background writer's latency
        packed, off = [], 0
        for i, (keystr, arr) in enumerate(flat):
            if arr.nbytes < _PACK_LIMIT:
                manifest[keystr] = {'file': _PACKED_NAME,
                                    'dtype': str(arr.dtype),
                                    'shape': list(arr.shape),
                                    'offset': off}
                packed.append(arr)
                off += arr.nbytes
                continue
            fname = f'leaf_{i:05d}.bin'
            manifest[keystr] = {'file': fname, 'dtype': str(arr.dtype),
                                'shape': list(arr.shape)}
            if pool is None:
                digest[fname] = list(_write_leaf(tmp, fname, arr))
            else:
                jobs.append((fname, pool.submit(_write_leaf, tmp, fname,
                                                arr)))
        if packed:
            # .tobytes(), never bytes(): bytes() of a 0-d integer array
            # routes through __index__ and yields that many NUL bytes
            blob = b''.join(a.tobytes() for a in packed)
            if pool is None:
                digest[_PACKED_NAME] = list(
                    _write_leaf(tmp, _PACKED_NAME, blob))
            else:
                jobs.append((_PACKED_NAME,
                             pool.submit(_write_leaf, tmp, _PACKED_NAME,
                                         blob)))
        for fname, j in jobs:
            digest[fname] = list(j.result())
        mbytes = json.dumps(manifest).encode()
        digest[_MANIFEST_NAME] = [len(mbytes),
                                  zlib.crc32(mbytes) & 0xFFFFFFFF]
        _write_leaf(tmp, _MANIFEST_NAME, mbytes)
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        os.replace(tmp, path)
        try:   # make the commit rename itself durable (best effort,
               # same policy as checkpoint.atomic_write)
            dfd = os.open(os.path.dirname(path), os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass

    try:
        retry.call(attempt, op_name=f'save_native:step_{step}')
    finally:
        if os.path.isdir(tmp):
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)
    from .checkpoint import atomic_write
    with atomic_write(os.path.join(path, _DIGEST_NAME)) as f:
        f.write(json.dumps(digest).encode())
    faults.shard_committed(step, path)
    return path


def _restore_native(path: str, like):
    """Load a native-format step dir, placing every leaf per ``like``:
    jax leaves (or sharding-annotated ShapeDtypeStructs) are device_put
    with their sharding; host leaves stay numpy."""
    with open(os.path.join(path, _MANIFEST_NAME)) as f:
        manifest = json.load(f)
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    out = []
    packed = None                # the shared small-leaf blob, read once
    for kpath, leaf in flat:
        key = jax.tree_util.keystr(kpath)
        ent = manifest.get(key)
        if ent is None:
            raise ValueError(
                f'native checkpoint {path} has no leaf {key!r} '
                f'(restoring under a changed structure?)')
        dt = _np_dtype(ent['dtype'])
        n = int(np.prod(ent['shape'])) if ent['shape'] else 1
        if ent['file'] == _PACKED_NAME:
            if packed is None:
                with open(os.path.join(path, _PACKED_NAME), 'rb') as f:
                    packed = f.read()
            arr = np.frombuffer(packed, dt, count=n,
                                offset=ent.get('offset', 0)).reshape(
                ent['shape'])
            writable = False     # frombuffer views are read-only
        else:
            # big leaves stream straight from disk, one at a time —
            # holding every file's bytes until unflatten would double
            # peak restore memory on exactly the big-model case the
            # format exists for
            arr = np.fromfile(os.path.join(path, ent['file']), dtype=dt,
                              count=n).reshape(ent['shape'])
            writable = True
        sharding = getattr(leaf, 'sharding', None)
        if sharding is not None:
            arr = jax.device_put(arr, sharding)
        elif not writable:
            arr = arr.copy()
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def _flush_pending_digests() -> None:
    while _PENDING_DIGEST:
        step, path = _PENDING_DIGEST.pop()
        if os.path.isdir(path):
            write_digest(path)
            faults.shard_committed(step, path)


def save_sharded(ckpt_dir: str, step: int, params, block: bool = True,
                 retry: Optional[faults.RetryPolicy] = None) -> str:
    """Write ``params`` (a pytree of possibly-sharded jax.Arrays) at
    ``step``; returns the checkpoint path.  ``block=False`` lets the
    commit overlap subsequent training steps (the previous pending save is
    always completed first); callers must ``wait_for_saves()`` before
    exit or before reading the checkpoint back.

    The write is atomic (orbax temp-dir + rename: ``step_<n>`` only ever
    names a complete checkpoint), retried under ``retry`` (default
    ``faults.DEFAULT_IO_RETRY``), and followed by an integrity digest
    sidecar once the commit lands."""
    path = _absolute(step_dir(ckpt_dir, step))
    ck = _shared_ck()
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry

    def attempt():
        faults.checkpoint_write_attempt(path)
        ck.wait_until_finished()      # at most one save in flight
        _flush_pending_digests()
        ck.save(path, params)

    retry.call(attempt, op_name=f'save_sharded:step_{step}')
    _PENDING_DIGEST.append((step, path))
    if block:
        ck.wait_until_finished()
        _flush_pending_digests()
    return path


def wait_for_saves() -> None:
    """Block until every async ``save_sharded(..., block=False)`` commit
    has landed (and its integrity digest is written)."""
    if _CK is not None:
        _CK.wait_until_finished()
        _flush_pending_digests()


def _abstract_like(like):
    ocp = _checkpointer()

    def to_abstract(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return ocp.utils.to_shape_dtype_struct(x)

    return jax.tree.map(to_abstract, like)


def saved_keys(path: str) -> set:
    """Top-level keys of the tree a committed step dir holds, in either
    format, read from its metadata and not from its arrays."""
    if '://' not in path and \
            os.path.exists(os.path.join(path, _MANIFEST_NAME)):
        with open(os.path.join(path, _MANIFEST_NAME)) as f:
            tops = (re.match(r"\['([^']*)'\]", k) for k in json.load(f))
        return {m.group(1) for m in tops if m}
    return set(_shared_ck().metadata(path).item_metadata.tree)


def restore_sharded(ckpt_dir: str, like, step: Optional[int] = None,
                    retry: Optional[faults.RetryPolicy] = None):
    """Restore the checkpoint at ``step`` (default: latest) with every
    leaf placed per ``like``'s shapes/dtypes/shardings — ``like`` is a
    pytree of sharding-annotated ``jax.ShapeDtypeStruct`` (e.g.
    ``models.transformer.abstract_params``) or of live sharded arrays, or
    a function from the step's :func:`saved_keys` to such a tree, for a
    reader that takes a subtree only where the writer kept one.
    The storage read retries under ``retry`` (default
    ``faults.DEFAULT_IO_RETRY``).  Returns (params, step)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f'no checkpoints under {ckpt_dir}')
    path = _absolute(step_dir(ckpt_dir, step))
    # absence is a state, not a transient — fail now instead of sleeping
    # through the backoff schedule probing a dir that was never written
    # (cloud URLs skip the check and rely on the backend's error)
    if '://' not in path and not os.path.isdir(path):
        raise FileNotFoundError(f'no checkpoint dir {path}')
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry
    if callable(like):
        like = like(retry.call(lambda: saved_keys(path),
                               op_name=f'saved_keys:step_{step}'))
    if '://' not in path and \
            os.path.exists(os.path.join(path, _MANIFEST_NAME)):
        # async-written native format (runtime/async_ckpt.py): restored
        # with the same retry/placement contract as the orbax path
        params = retry.call(lambda: _restore_native(path, like),
                            op_name=f'restore_sharded:step_{step}')
        return params, step
    target = _abstract_like(like)
    params = retry.call(
        lambda: _shared_ck().restore(path, target),
        op_name=f'restore_sharded:step_{step}')
    return params, step


def quarantine_step(ckpt_dir: str, step: int, reason: str) -> None:
    """Rename a bad ``step_<n>`` dir to ``step_<n>.corrupt`` so every
    future ``latest_step``/``all_steps`` scan skips it without re-paying
    verification, while the bytes stay around for post-mortem."""
    src = _absolute(step_dir(ckpt_dir, step))
    if os.path.isdir(src):
        dst = src + '.corrupt'
        if os.path.exists(dst):
            import shutil
            shutil.rmtree(dst, ignore_errors=True)
        os.replace(src, dst)
    faults.global_failure_log().record(
        'ckpt_quarantined', f'step {step}: {reason}', step=step)


def restore_resilient(ckpt_dir: str, like,
                      retry: Optional[faults.RetryPolicy] = None):
    """Restore the newest checkpoint that passes integrity verification,
    falling back step by step: a corrupt/truncated shard (or an orbax
    restore failure) quarantines that step and tries the next older one.
    Raises ``faults.CheckpointCorruptError`` when nothing under
    ``ckpt_dir`` is restorable.  Returns (params, step)."""
    steps = all_steps(ckpt_dir)
    if not steps:
        raise FileNotFoundError(f'no checkpoints under {ckpt_dir}')
    log = faults.global_failure_log()
    quarantined = 0
    last_err: Optional[BaseException] = None
    for step in steps:
        path = _absolute(step_dir(ckpt_dir, step))
        reason = verify_step_dir(path)
        if reason is not None:
            quarantine_step(ckpt_dir, step, reason)
            quarantined += 1
            continue
        try:
            return restore_sharded(ckpt_dir, like, step, retry=retry)
        except (faults.RetryError, OSError, ValueError) as e:
            # NOT a quarantine: the digest verified, so the bytes are
            # intact — this failure is environmental (storage outage
            # outlasting the retry budget) or caller-side (restoring
            # under a changed net config raises ValueError on every
            # step).  Renaming the dir would destroy the only good
            # recovery point over a fault that may clear; skip it for
            # this call and leave the scan state alone.
            last_err = e
            log.record('ckpt_restore_failed', repr(e), step=step)
    if not quarantined and last_err is not None:
        # zero corruption was found — reporting CheckpointCorruptError
        # here would send the operator down the wrong runbook for what
        # is an outage or a caller-side mismatch
        raise last_err
    raise faults.CheckpointCorruptError(
        f'no intact checkpoint under {ckpt_dir} '
        f'({quarantined} of {len(steps)} candidates quarantined, '
        f'rest unrestorable)')
