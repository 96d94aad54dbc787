"""On-chip op timing with the per-dispatch cost cancelled.

A timing that issues one dispatch per measured call includes the host's
dispatch cost, which for a sub-millisecond op can be most of the number.
(The method dates from a remote chip whose dispatch cost ~7 ms and did
not pipeline: a 19-GFLOP and a 2-GFLOP matmul both "measured" ~7.3 ms.
On a local chip the floor is far lower; ROADMAP S1 re-tests the method
against the profiler's device durations.)

The measurement runs the op N times inside ONE jitted computation and
divides out N:

    t_per_iter = (t(loop_N) - t(loop_1)) / (N - 1)

which cancels the constant dispatch cost exactly.  The loop body
chains a f32 scalar through each iteration's output and perturbs the
first input with it, so iterations form a serial data dependency: XLA can
neither hoist the (otherwise loop-invariant) op out of the while loop nor
dead-code-eliminate it.  The added work is one fused elementwise pass
over the first input plus an 8-byte extract — noise for compute-bound
ops; at most one extra memory pass for bandwidth-bound ones, and it lands
on both sides of any A/B comparison equally.

The returned per-iter time is measured by fetching the loop's scalar
result to host, which cannot return before the device has finished.

Tools built on this call ``cxxnet_tpu.utils.backend.enable_compile_cache``
in their ``main`` before the first compile (kernel A/B sweeps compile
dozens of loop programs).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def make_loop(fn, length: int):
    """Jitted fn running ``fn(*args)`` ``length`` times serially on-device,
    returning a f32 scalar data-dependent on every iteration."""

    def run(*args):
        def body(s, _):
            eps = (s * 1e-30).astype(args[0].dtype)
            # perturb ONE element, not all of them: `a + eps` distributes
            # through linear ops — XLA can rewrite dot(a+eps, b) as
            # dot(a,b) + eps*colsum(b), hoist the loop-invariant dot out
            # of the scan, and "measure" above-peak FLOP rates (the r3
            # matmul receipts showed 249 TF/s on a 197-peak chip — the
            # tell).  A scatter-add into [0,...,0] forces a genuine
            # re-execution; its cost is one copy pass over args[0],
            # the same bandwidth the old broadcast-add already paid.
            a0 = args[0].at[(0,) * args[0].ndim].add(eps)
            out = fn(a0, *args[1:])
            # consume EVERY output leaf through a non-factorable reduction:
            # a single-element carry (out[0]) lets XLA push the slice into
            # the op and compute one row of a matmul / one window of an
            # LRN instead of the op ("measuring" negative microseconds),
            # and an unconsumed leaf (e.g. the 2nd grad of a fwd+bwd
            # probe) is dead code.  max|.| cannot be algebraically pushed
            # through dot/conv/reduce_window; its cost is one bandwidth
            # pass per leaf, identical on both sides of an A/B pair.
            for leaf in jax.tree.leaves(out):
                s = s + jnp.max(jnp.abs(leaf)).astype(jnp.float32)
            return s * 0.5, None

        s, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=length)
        return s

    return jax.jit(run)


def grad_probe(fn, nargs: int = None):
    """fwd+bwd probe for A/B kernel comparisons: value_and_grad of
    ``0.5*sum(fn(*args)**2)`` wrt EVERY array argument.

    Two traps this construction avoids: ``grad(sum(fn))`` has a constant
    all-ones cotangent, which XLA algebra can exploit — for a matmul it
    simplifies the backward to a column-sum reduction AND dead-code-
    eliminates the forward (grad-only output) entirely, so the "XLA side"
    of the comparison measures a degenerate program.  Squaring makes the
    cotangent the forward output itself (forward must run, backward gets a
    dense data-dependent cotangent, like a real training step), and
    returning the value keeps the forward live."""

    def probe(*args):
        n = len(args) if nargs is None else nargs

        def loss(*a):
            out = fn(*a)
            return 0.5 * jnp.sum(out.astype(jnp.float32) ** 2)

        val, grads = jax.value_and_grad(
            loss, argnums=tuple(range(n)))(*args)
        return (val,) + tuple(grads)

    return probe


def time_op(fn, args, iters: int = None, reps: int = 5,
            target_s: float = 0.15) -> float:
    """Per-iteration seconds of ``fn(*args)`` on device, dispatch cost
    cancelled via the N-vs-1 difference quotient.

    Each endpoint takes the MIN over ``reps`` runs before the quotient:
    the dispatch cost is a constant floor plus positive jitter spikes,
    so min is the right noise rejector — a median quotient of noisy
    single runs can even go negative for sub-ms ops.
    ``iters`` is sized adaptively (from a 50-iter probe) so each timed
    run carries ~``target_s`` seconds of real compute, keeping the signal
    well above the residual jitter for sub-100us ops."""
    f_1 = make_loop(fn, 1)
    float(np.asarray(f_1(*args)))        # compile + warm
    if iters is None:
        f_probe = make_loop(fn, 50)
        float(np.asarray(f_probe(*args)))
        t = []
        for _ in range(2):
            t0 = time.perf_counter()
            float(np.asarray(f_probe(*args)))
            t.append(time.perf_counter() - t0)
        est = min(t) / 50                # overhead/50 inflates est: fine
        iters = int(min(2000, max(50, target_s / max(est, 1e-7))))
    f_n = make_loop(fn, iters)
    float(np.asarray(f_n(*args)))
    t1s, tns = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(f_1(*args)))
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(np.asarray(f_n(*args)))
        tns.append(time.perf_counter() - t0)
    return (min(tns) - min(t1s)) / (iters - 1)


def atomic_receipt_dump(path, payload, partial: bool) -> None:
    """Atomic (tmp + os.replace) JSON receipt write — THE dump helper for
    every receipt-producing tool; keep the contract here, next to the
    timing loop, not copy-pasted per tool.

    ``partial=True`` marks a receipt whose rows are not all final; call
    once more with ``partial=False`` when they are.  Rewrite after EVERY
    row: a run killed mid-suite (the chip tool's time limit) must never
    cost a finished measurement, and the tmp+replace means a mid-write
    kill can't leave a truncated non-empty unparseable file."""
    import json
    if not path:
        return
    payload = dict(payload)
    if partial:
        payload['partial'] = True
    else:
        payload.pop('partial', None)
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
