"""Seeded violation twin: a kernel reaching ``pallas_call`` through a
helper's PARAMETER — the ``_call(kernel, ...)`` indirection that was
this rule's documented soundness hole.  The helper itself is clean; the
violation lives in the kernel body the caller hands it, positionally in
one case and by keyword (through a ``partial`` wrapper) in the other.
"""
import functools
import time

import jax.numpy as jnp
from jax.experimental import pallas as pl


def _call(kernel, x):
    # clean forwarding helper: the parameter lands in pallas_call's
    # function position, so the CALLER's argument is the traced body
    return pl.pallas_call(kernel, out_shape=x)(x)


def _call_kw(x, kernel=None):
    # keyword-passed kernel, forwarded through an inline partial
    return pl.pallas_call(functools.partial(kernel), out_shape=x)(x)


def _sync_kernel(x_ref, o_ref):
    peak = float(x_ref[0, 0])          # BAD: device->host sync
    o_ref[:] = x_ref[:] * peak


def _clock_kernel(x_ref, o_ref):
    # BAD: wall clock baked in at trace time
    o_ref[:] = x_ref[:] * time.monotonic()


def scale(x):
    return _call(_sync_kernel, x)


def stamp(x):
    return _call_kw(x, kernel=_clock_kernel)
