"""Model-blob serialization (checkpoint weight payload).

Mirrors the reference layout (``SaveModel`` per layer: LayerParam struct +
weight tensors, ``fullc_layer-inl.hpp:46-60``): the blob is the
concatenation of every non-shared layer's record, in layer order.  Weight
layouts on disk follow the reference conventions so tooling stays
interoperable:

* fullc ``wmat``: ``(nhidden, nin)`` (in-memory we keep ``(nin, nhidden)``),
* conv ``wmat``: ``(ngroup, nch/g, nin/g * kh * kw)`` im2col layout
  (in-memory HWIO),
* 1-D ``bias``/slope tensors unchanged.

Tensors are stored self-describing as (uint32 ndim, uint32 shape[ndim],
float32 data), matching mshadow's shape+data ``SaveBinary`` convention.
The LayerParam struct (328 bytes: 18 fields + 64 reserved ints,
``layer/param.h:15-76``) is written for layers that save it in the
reference (fullc, conv, bias, fixconn); batch_norm/prelu save tensors only.
"""

from __future__ import annotations

import contextlib
import os
import struct
from typing import Callable, Dict

import jax.numpy as jnp
import numpy as np

from ..layers import base as lbase

_LAYER_PARAM = struct.Struct('<ifif f iiiiiiiii iiii 64i')
assert _LAYER_PARAM.size == 328


def _pack_layer_param(p: lbase.LayerParam) -> bytes:
    return _LAYER_PARAM.pack(
        p.num_hidden, p.init_sigma, p.init_sparse, p.init_uniform,
        p.init_bias, p.num_channel, p.random_type, p.num_group,
        p.kernel_height, p.kernel_width, p.stride, p.pad_y, p.pad_x,
        p.no_bias, p.temp_col_max, p.silent, p.num_input_channel,
        p.num_input_node, *([0] * 64))


def _write_tensor(out: bytearray, arr: np.ndarray) -> None:
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    out += struct.pack('<I', arr.ndim)
    out += struct.pack(f'<{arr.ndim}I', *arr.shape)
    out += arr.tobytes()


def _read_tensor(buf: bytes, pos: int):
    (ndim,) = struct.unpack_from('<I', buf, pos)
    pos += 4
    shape = struct.unpack_from(f'<{ndim}I', buf, pos)
    pos += 4 * ndim
    n = int(np.prod(shape)) if ndim else 1
    arr = np.frombuffer(buf, np.float32, count=n, offset=pos).reshape(shape)
    pos += 4 * n
    return arr.copy(), pos


# layers whose reference SaveModel begins with the LayerParam struct
_SAVES_PARAM_STRUCT = {lbase.kFullConnect, lbase.kConv, lbase.kBias,
                       lbase.kFixConnect}


def layer_fields(type_id: int):
    """Field save order per layer type (reference SaveModel order)."""
    if type_id in (lbase.kFullConnect, lbase.kConv, lbase.kBatchNorm):
        return ('wmat', 'bias')
    if type_id in (lbase.kPRelu, lbase.kBias):
        return ('bias',)
    # a layer with no reference file to match: every field it declares, in
    # that order
    cls = lbase.LAYER_REGISTRY.get(type_id)
    return tuple(cls.param_fields) if cls is not None else ()


def to_disk_layout(type_id: int, field: str, arr: np.ndarray,
                   num_group: int) -> np.ndarray:
    if type_id == lbase.kFullConnect and field == 'wmat':
        return arr.T                                  # (nin,nh) → (nh,nin)
    if type_id == lbase.kConv and field == 'wmat':
        kh, kw, cin_g, cout = arr.shape
        g = num_group
        # HWIO → (g, cout/g, cin_g, kh, kw) → (g, cout/g, cin_g*kh*kw)
        a = arr.transpose(3, 2, 0, 1).reshape(g, cout // g, cin_g, kh, kw)
        return a.reshape(g, cout // g, cin_g * kh * kw)
    return arr


def from_disk_layout(type_id: int, field: str, arr: np.ndarray,
                     layer) -> np.ndarray:
    if type_id == lbase.kFullConnect and field == 'wmat':
        return arr.T
    if type_id == lbase.kConv and field == 'wmat':
        g, cout_g, flat = arr.shape
        p = layer.param
        cin_g = flat // (p.kernel_height * p.kernel_width)
        a = arr.reshape(g, cout_g, cin_g, p.kernel_height, p.kernel_width)
        return a.transpose(3, 4, 2, 0, 1).reshape(
            p.kernel_height, p.kernel_width, cin_g, g * cout_g)
    return arr


def host_params(params) -> Dict[str, Dict[str, np.ndarray]]:
    """Materialize a param tree on host — the device→host half of
    serialization, split out so an async save (runtime/async_ckpt.py) can
    run it on the background writer instead of the step loop."""
    return {k: {f: np.asarray(v) for f, v in d.items()}
            for k, d in params.items()}


def params_to_blob(net, params) -> bytes:
    return serialize_blob(net, host_params(params))


def serialize_blob(net, host: Dict[str, Dict[str, np.ndarray]]) -> bytes:
    """Serialize an already-host-resident param snapshot to the reference
    model blob layout — pure CPU work, safe on a background thread (reads
    only the net's static layer structure)."""
    out = bytearray()
    for i, info in enumerate(net.cfg.layers):
        if net.layer_primary[i] != i or info.type == lbase.kSharedLayer:
            continue
        layer = net.layers[i]
        fields = layer_fields(info.type)
        if not fields:
            continue
        if info.type in _SAVES_PARAM_STRUCT:
            out += _pack_layer_param(layer.param)
        lp = host.get(str(i), {})
        for f in fields:
            if f not in lp:   # e.g. no_bias fullc still saves a bias slot
                n = layer.param.num_channel or max(layer.param.num_hidden, 1)
                arr = np.zeros((n,), np.float32)
            else:
                arr = to_disk_layout(info.type, f, lp[f],
                                     layer.param.num_group)
            _write_tensor(out, arr)
    return bytes(out)


def blob_to_raw(cfg_layers, blob: bytes) -> Dict[str, Dict[str, np.ndarray]]:
    """Parse a blob into disk-layout arrays keyed by layer index/field."""
    params: Dict[str, Dict[str, np.ndarray]] = {}
    pos = 0
    for i, info in enumerate(cfg_layers):
        if info.type == lbase.kSharedLayer:
            continue
        fields = layer_fields(info.type)
        if not fields:
            continue
        if info.type in _SAVES_PARAM_STRUCT:
            pos += _LAYER_PARAM.size
        rec = {}
        for f in fields:
            arr, pos = _read_tensor(blob, pos)
            rec[f] = arr
        params[str(i)] = rec
    return params


def record_to_memory(layer, type_id: int,
                     rec: Dict[str, np.ndarray]) -> Dict:
    """Disk-layout record → in-memory param dict for a built layer."""
    out = {}
    for f, arr in rec.items():
        if f == 'bias' and layer.param.no_bias and \
                type_id in (lbase.kFullConnect, lbase.kConv):
            continue   # slot present on disk but unused in memory
        out[f] = jnp.asarray(from_disk_layout(type_id, f, arr, layer))
    return out


# --- fault-tolerant model-file I/O ---------------------------------------
#
# The reference SaveModel wrote straight through the destination handle: a
# crash mid-write left a truncated file under the final name, which a later
# ``continue=1`` scan happily loaded.  All model-file writes now go through
# write-to-temp + fsync + atomic rename (a reader can only ever observe a
# complete file), and both directions are wrapped in the configurable
# retry-with-backoff policy from ``runtime.faults`` (doc/fault_tolerance.md).


@contextlib.contextmanager
def atomic_write(path: str):
    """Open a temp file next to ``path`` for writing; on clean exit fsync
    it, atomically rename it over ``path``, and fsync the directory so the
    rename itself survives a crash.  On error the temp file is removed and
    ``path`` is untouched — a partially-written checkpoint is never
    visible under the final name."""
    path = os.fspath(path)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f'.{os.path.basename(path)}.tmp.{os.getpid()}')
    try:
        with open(tmp, 'wb') as f:
            yield f
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        try:
            dfd = os.open(d, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass   # directory fsync is best-effort (not all FSes allow it)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def save_model_file(path: str, write_fn: Callable, retry=None) -> str:
    """Atomically write a model file: ``write_fn(fileobj)`` produces the
    bytes; the whole write retries under ``retry`` (default
    ``faults.DEFAULT_IO_RETRY``), with each attempt first passing through
    the fault-injection hook so injected storage errors exercise the same
    retry path real ones take."""
    from ..runtime import faults
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry

    def attempt():
        faults.checkpoint_write_attempt(path)
        with atomic_write(path) as f:
            write_fn(f)

    retry.call(attempt, op_name=f'save_model:{os.path.basename(path)}')
    return os.fspath(path)


def publish_model_file(path: str, write_fn: Callable, retry=None) -> str:
    """Atomic model-file publish for hot-reload watchers (the online
    pipeline's serving checkpoints, doc/online.md): like
    :func:`save_model_file` + :func:`write_model_digest`, but the digest
    sidecar is computed from the staged bytes and committed BEFORE the
    model file is renamed into place.  A watcher polling the directory
    can therefore never observe a model without its digest — the
    save-then-digest order of the train CLI leaves a brief no-sidecar
    window in which the registry's "unverified-but-plausible" policy
    would adopt the file unchecked.  The ``corrupt_model`` chaos hook
    fires on the STAGED file, between digest and rename, so an injected
    corruption is deterministically caught by digest verification —
    there is no instant at which the poisoned bytes are visible
    unverifiable."""
    import json

    from ..runtime import faults
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry
    path = os.fspath(path)
    d = os.path.dirname(os.path.abspath(path))
    tmp = os.path.join(d, f'.{os.path.basename(path)}.pub.{os.getpid()}')

    def attempt():
        faults.checkpoint_write_attempt(path)
        os.makedirs(d, exist_ok=True)
        try:
            with open(tmp, 'wb') as f:
                write_fn(f)
                f.flush()
                os.fsync(f.fileno())
            digest = {'size': os.path.getsize(tmp),
                      'crc32': file_crc32(tmp)}
            with atomic_write(model_digest_path(path)) as f:
                f.write(json.dumps(digest).encode())
            # chaos hook on the STAGED file: the digest above recorded
            # the good bytes, so a truncation here is caught by verify
            # the moment the file becomes visible
            faults.model_committed(path, staged=tmp)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    retry.call(attempt, op_name=f'publish_model:{os.path.basename(path)}')
    return path


def read_model_file(path: str, read_fn: Callable, retry=None):
    """Read a model file with retry: ``read_fn(fileobj)``'s return value is
    passed through.  A missing file raises immediately (not retryable —
    absence is a state, not a transient)."""
    from ..runtime import faults
    retry = faults.DEFAULT_IO_RETRY if retry is None else retry
    if not os.path.exists(path):
        raise FileNotFoundError(path)

    def attempt():
        with open(path, 'rb') as f:
            return read_fn(f)

    return retry.call(attempt, op_name=f'read_model:{os.path.basename(path)}')


def model_digest_path(path: str) -> str:
    return os.fspath(path) + '.crc32'


def file_crc32(path: str) -> int:
    """Chunked crc32 of a file's bytes."""
    import zlib
    crc = 0
    with open(path, 'rb') as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def write_model_digest(path: str) -> str:
    """Write the ``<model>.crc32`` integrity sidecar (JSON ``{size,
    crc32}``) next to a just-saved model file, atomically.  The model
    rename already guarantees *completeness*; the digest additionally
    catches silent byte corruption between writer and a hot-reloading
    reader (``serve/registry.py`` verifies it before swapping a new
    checkpoint into a live engine)."""
    import json

    from ..runtime import faults
    digest = {'size': os.path.getsize(path), 'crc32': file_crc32(path)}
    side = model_digest_path(path)
    with atomic_write(side) as f:
        f.write(json.dumps(digest).encode())
    # commit point for chaos drills: file + sidecar both durable — the
    # corrupt_model event truncates the model HERE so a hot-reloading
    # registry must catch the mismatch (runtime/faults.py)
    faults.model_committed(path)
    return side


def verify_model_digest(path: str):
    """Return None when ``path`` matches its digest sidecar (or no
    sidecar exists — unverified-but-plausible, the same policy as the
    sharded-checkpoint verifier), else a human-readable reason."""
    import json
    side = model_digest_path(path)
    if not os.path.exists(side):
        return None
    try:
        with open(side, 'rb') as f:
            digest = json.load(f)
        size = os.path.getsize(path)
    except (OSError, ValueError) as e:
        return f'unreadable digest sidecar: {e!r}'
    if not isinstance(digest, dict) \
            or not isinstance(digest.get('size'), int) \
            or not isinstance(digest.get('crc32'), int):
        # malformed-but-valid JSON must be a REASON, not a crash — the
        # registry blacklists on reasons; an escaping TypeError would
        # retry the broken sidecar forever
        return f'malformed digest sidecar: {digest!r}'
    if size != digest['size']:
        return f'size {size} != recorded {digest["size"]}'
    crc = file_crc32(path)
    if crc != digest['crc32']:
        return f'crc32 {crc:#x} != recorded {digest["crc32"]:#x}'
    return None


def blob_to_params(net, blob: bytes):
    raw = blob_to_raw(net.cfg.layers, blob)
    params = {}
    for i, info in enumerate(net.cfg.layers):
        key = str(i)
        if key not in raw:
            continue
        params[key] = record_to_memory(net.layers[i], info.type, raw[key])
    return params
