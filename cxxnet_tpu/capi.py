"""Python glue behind the native C ABI (runtime/cxxnet_wrapper.cc).

The reference exposed its C++ trainer through a C ABI
(``wrapper/cxxnet_wrapper.h:29-225``) so other languages could bind it.
Here the dependency points the other way — the trainer lives in
Python/JAX — so the native ``libcxxnetwrapper.so`` embeds CPython and
calls the flat functions in this module.  Each function takes only
C-friendly types (memoryviews, tuples, strings) and returns either a
contiguous float32 ``np.ndarray``, a ``str``, or ``None`` so the C layer
needs no per-call marshalling logic.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .wrapper import DataIter, Net


def _from_buffer(mv, shape: Tuple[int, ...]) -> np.ndarray:
    arr = np.frombuffer(mv, np.float32, count=int(np.prod(shape)))
    return arr.reshape(shape).copy()


def _as_f32(arr: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(arr, np.float32)


def _as_4d(arr: np.ndarray) -> np.ndarray:
    """Shape to 4-d (batch, c, y, x) the way reference nodes are laid out
    (matrices become (batch, 1, 1, len), layer/layer.h:44-55)."""
    arr = _as_f32(arr)
    if arr.ndim == 4:
        return arr
    if arr.ndim == 2:
        return arr.reshape(arr.shape[0], 1, 1, arr.shape[1])
    if arr.ndim == 1:
        return arr.reshape(arr.shape[0], 1, 1, 1)
    raise ValueError(f'cannot view shape {arr.shape} as 4-d node')


# ---- iterator surface (CXNIO*) ------------------------------------------

def io_create(cfg: str) -> DataIter:
    return DataIter(cfg)


def io_next(it: DataIter) -> int:
    return 1 if it.next() else 0


def io_before_first(it: DataIter) -> None:
    it.before_first()


def io_get_data(it: DataIter) -> np.ndarray:
    return _as_4d(it.get_data())


def io_get_label(it: DataIter) -> np.ndarray:
    lab = _as_f32(it.get_label())
    return lab if lab.ndim == 2 else lab.reshape(lab.shape[0], -1)


# ---- net surface (CXNNet*) ----------------------------------------------

def net_create(device: str, cfg: str) -> Net:
    return Net(dev=device or '', cfg=cfg)


def net_set_param(net: Net, name: str, val: str) -> None:
    net.set_param(name, val)


def net_init_model(net: Net) -> None:
    net.init_model()


def net_save_model(net: Net, fname: str) -> None:
    net.save_model(fname)


def net_load_model(net: Net, fname: str) -> None:
    net.load_model(fname)


def net_start_round(net: Net, rnd: int) -> None:
    net.start_round(rnd)


def net_set_weight(net: Net, mv, size: int, layer_name: str,
                   tag: str) -> None:
    cur = net.get_weight(layer_name, tag)
    if cur is None:
        raise KeyError(f'layer {layer_name} has no weight {tag}')
    if int(size) != cur.size:
        raise ValueError(f'set_weight: size {size} != {cur.size}')
    net.set_weight(_from_buffer(mv, cur.shape), layer_name, tag)


def net_get_weight(net: Net, layer_name: str,
                   tag: str) -> Optional[np.ndarray]:
    w = net.get_weight(layer_name, tag)
    return None if w is None else _as_f32(w)


def net_update_iter(net: Net, it: DataIter) -> None:
    net.update(it)


def net_update_batch(net: Net, data_mv, dshape, label_mv, lshape) -> None:
    net.update(_from_buffer(data_mv, tuple(dshape)),
               _from_buffer(label_mv, tuple(lshape)))


def net_predict_batch(net: Net, data_mv, dshape) -> np.ndarray:
    return _as_f32(net.predict(_from_buffer(data_mv, tuple(dshape))))


def net_predict_iter(net: Net, it: DataIter) -> np.ndarray:
    # Whole-iterator predict (CXNNetPredictIter).  The underlying path is
    # the pipelined predict_stream generator — per-batch host chunks with
    # pad rows already trimmed — so peak host memory beyond the returned
    # array is O(batch); the single concatenation happens only here, at
    # the ABI boundary (the C side needs one contiguous buffer).
    chunks = list(net.predict_stream(it))
    if not chunks:
        return np.empty((0,), np.float32)
    return _as_f32(np.concatenate(chunks, axis=0))


def net_extract_batch(net: Net, data_mv, dshape, node: str) -> np.ndarray:
    return _as_4d(net.extract(_from_buffer(data_mv, tuple(dshape)), node))


def net_extract_iter(net: Net, it: DataIter, node: str) -> np.ndarray:
    # Whole-iterator extract: same streaming path as net_predict_iter —
    # concatenate trimmed per-batch activations once, at the boundary.
    chunks = list(net.extract_stream(it, node))
    if not chunks:
        return np.empty((0, 1, 1, 1), np.float32)
    return _as_4d(np.concatenate(chunks, axis=0))


def net_evaluate(net: Net, it: DataIter, name: str) -> str:
    return net.evaluate(it, name)


# ---- serving surface (CXNNetServe*) --------------------------------------

def net_serve_start(net: Net, cfg: str) -> None:
    """Stand up the serving stack.  ``cfg`` is a compact ``k=v[;k=v...]``
    list (utils.config.parse_kv_list): ``buckets`` (``:``-separated, e.g.
    ``1:8:32``), ``max_queue``, ``max_wait`` (seconds), ``deadline``
    (seconds), ``warm`` (0/1), ``models`` (``|``-separated ``id:dir``
    fleet siblings), ``mem_budget`` (bytes), ``dtype`` (``f32``/
    ``bf16``/``int8`` quantized-inference tier), ``replicas`` (>=2 =
    data-parallel per-device engine replicas behind the one batcher).
    Empty string = all defaults."""
    from .utils.config import parse_kv_list
    kw = {}
    for key, val in parse_kv_list(cfg or ''):
        if key == 'buckets':
            kw['buckets'] = val.replace(':', ',')
        elif key == 'max_queue':
            kw['max_queue'] = int(val)
        elif key == 'max_wait':
            kw['max_wait'] = float(val)
        elif key == 'deadline':
            kw['deadline'] = float(val)
        elif key == 'warm':
            kw['warm'] = bool(int(val))
        elif key == 'models':
            kw['models'] = dict(seg.split(':', 1)
                                for seg in val.split('|') if seg)
        elif key == 'mem_budget':
            kw['mem_budget'] = int(val)
        elif key == 'dtype':
            kw['dtype'] = val
        elif key == 'replicas':
            kw['replicas'] = int(val)
        else:
            raise ValueError(f'unknown serve option: {key!r}')
    net.serve_start(**kw)


def net_serve_predict(net: Net, data_mv, dshape) -> np.ndarray:
    """One request through the micro-batcher: class id per row.  Typed
    serving errors (queue full, deadline) propagate as Python exceptions
    for the C layer's error surface."""
    return _as_f32(net.serve_predict(_from_buffer(data_mv, tuple(dshape))))


def net_serve_reload(net: Net, fname: str) -> None:
    net.serve_reload(fname)


def net_serve_stats(net: Net) -> str:
    return net.serve_stats()


def net_serve_stop(net: Net) -> None:
    net.serve_stop()


def net_obs_stats(net: Net) -> str:
    """The process-wide telemetry hub's ``/statusz`` JSON as one string
    (doc/observability.md) — the C embedder's machine-readable window
    into a live trainer/server without binding an HTTP port."""
    return net.obs_stats()


def net_obs_slos(net: Net) -> str:
    """The ``/slos`` JSON as one string: every attached SLO engine's
    typed verdicts (doc/observability.md "SLOs and burn rates") — the
    portless health surface for C embedders and the future autoscaler."""
    return net.obs_slos()


def net_obs_programs(net: Net) -> str:
    """The ``/programs`` JSON as one string: the compiler-truth program
    ledger — per-executable compile wall-ms, HLO cost and memory rows
    plus the recompile-sentinel totals (doc/observability.md "Programs,
    memory, and MFU")."""
    return net.obs_programs()


def net_autotune(net: Net, spec: str, probe_fn, task: str = 'train') -> str:
    """Run the grafttune search over ``spec`` with the embedding's
    measured probe (``probe_fn(candidate_dict) -> score``, higher
    better) and return the JSON receipt; ``best`` holds the tuned knobs
    (doc/autotune.md)."""
    return net.autotune(spec, probe_fn, task=task)


# ---- train-while-serve surface (CXNNetOnline*) ----------------------------

def net_online_start(net: Net, it: DataIter, cfg: str) -> None:
    """Start the train-while-serve loop (doc/online.md): training runs on
    a background thread over ``it`` while the colocated serving stack
    answers ``net_online_predict``.  ``cfg`` is a compact ``k=v[;k=v...]``
    list: ``model_dir`` (required), ``rounds``, ``save_every``,
    ``freshness_slo``/``freshness_strict``, ``reload``, ``buckets``
    (``:``-separated), ``max_queue``, ``max_wait``, ``deadline``,
    ``steps_per_dispatch``, ``watchdog_deadline``, ``dtype`` (the
    serving engine's quantized tier, ``f32``/``bf16``/``int8``)."""
    from .utils.config import parse_kv_list
    kw = {}
    ints = ('rounds', 'save_every', 'max_queue', 'steps_per_dispatch')
    floats = ('freshness_slo', 'reload', 'max_wait', 'deadline',
              'watchdog_deadline')
    for key, val in parse_kv_list(cfg or ''):
        if key == 'model_dir':
            kw['model_dir'] = val
        elif key == 'buckets':
            kw['buckets'] = val.replace(':', ',')
        elif key == 'dtype':
            kw['dtype'] = val
        elif key == 'freshness_strict':
            kw['freshness_strict'] = bool(int(val))
        elif key in ints:
            kw[key] = int(val)
        elif key in floats:
            kw[key] = float(val)
        else:
            raise ValueError(f'unknown online option: {key!r}')
    if 'model_dir' not in kw:
        raise ValueError('online cfg must set model_dir=')
    net.online_start(it, **kw)


def net_online_predict(net: Net, data_mv, dshape) -> np.ndarray:
    """One request through the live online stack: class id per row.
    Typed serving errors propagate as Python exceptions."""
    return _as_f32(net.online_predict(_from_buffer(data_mv, tuple(dshape))))


def net_online_stats(net: Net) -> str:
    return net.online_stats()


def net_online_wait(net: Net) -> str:
    """Block until the background training run finishes; returns its
    summary as one JSON line (freshness p50/p99, swaps, served,
    dropped, ...)."""
    import json
    return json.dumps(net.online_wait(), sort_keys=True)


def net_online_stop(net: Net) -> None:
    net.online_stop()


# ---- continuous decode surface (CXNLMServe*) ------------------------------

def lm_serve_start(cfg: str):
    """Stand up the continuous-batching decode stack (doc/serving.md
    "Continuous decode") for a transformer LM.  ``cfg`` is the compact
    ``k=v[;k=v...]`` spec :class:`wrapper.LMServe` parses: model spec
    ``vocab``/``d_model``/``heads``/``d_ff``/``stages``/``experts``,
    params from ``model_in`` (a ``%04d.lm`` tree) or ``seed`` init,
    engine shape ``slots``/``pages``/``page_size``/``max_prompt``/
    ``max_new``/``eos``, batcher knobs ``max_queue``/``max_wait``/
    ``deadline``, serving tier ``dtype`` (``f32``/``bf16``/``int8``), prefix
    sharing ``prefix_share`` (index page cap, 0 = off), greedy
    speculative decoding ``spec_k`` + ``draft.*`` draft-model keys, and
    the graftcache KV tiers ``kv_host_mb``/``kv_disk_mb``/``kv_dir``/
    ``kv_share_dir`` (doc/serving.md "Tiered KV cache"), plus
    graftshard's ``shard=tp:N`` tensor-parallel decode and
    ``prefill_workers=N`` disaggregated prefill (doc/serving.md
    "Sharded serving").
    Returns the service handle the other ``lm_serve_*`` calls take."""
    from .wrapper import LMServe
    return LMServe.from_spec(cfg)


def lm_serve_generate(svc, prompt_mv, n: int, max_new: int,
                      temperature: float = 0.0, seed: int = 0) -> np.ndarray:
    """One decode request through the admission-controlled stack: blocks
    for the full stream, returns contiguous int32 token ids (the stream
    ends at the engine's EOS when configured).  Typed serving errors
    propagate as Python exceptions for the C error surface."""
    prompt = np.frombuffer(prompt_mv, np.int32, count=int(n))[None]
    rng = None
    if temperature > 0:
        import jax
        rng = jax.random.PRNGKey(int(seed))
    toks = svc.generate(prompt, int(max_new), float(temperature), rng)
    return np.ascontiguousarray(toks, np.int32)


def lm_serve_stats(svc) -> str:
    return svc.report()


def lm_serve_scenario(svc, spec: str, time_scale: float = 1.0) -> str:
    """Drive a seeded adversarial traffic scenario (``serve.scenario=``
    grammar — doc/serving.md "Scenarios and autoscaling") against the
    service and return the reconciled ledger summary as a JSON string
    (submitted / per-bucket terminal counts / p50 / p99 seconds).
    Deterministic: the same spec replays the same storm bit for bit."""
    import json
    return json.dumps(svc.run_scenario(spec, time_scale=float(time_scale)),
                      sort_keys=True)


def lm_serve_autoscale(svc, policy: str):
    """Attach an SLO-driven autoscaler (``serve.autoscale=`` grammar)
    over the service's live admission caps; returns the scaler handle
    (its ``close()`` detaches — call before ``lm_serve_stop``)."""
    return svc.autoscale(policy)


def lm_serve_stop(svc) -> None:
    svc.close()
