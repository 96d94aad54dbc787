"""How a process meets its JAX backend: where compiled programs are cached,
whether it was deliberately held to the CPU, and the check a measurement
makes before it reports a device number.

Entry points call these (the CLI, ``chip_smoke.py``, the bench scripts,
the timing tools); importing the module touches no backend.
"""

from __future__ import annotations

import os

_CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'
_met = False      # meet_backend has recorded this process's first touch


class BackendUnavailable(RuntimeError):
    """A run that needs the chip found another backend."""


def enable_compile_cache() -> str:
    """Turn the persistent XLA compilation cache on and return its
    directory.  Placement comes from outside: when
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set here; otherwise the cache is ``<checkout>/.jax_cache``
    (git-ignored), derived from the package's own location — never from
    the working directory, a pid or the clock: the path is part of what
    lets a later process find the entries again.

    JAX initialises the cache once, at the first compile of the process,
    so this must run before that compile to have any effect."""
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    import jax
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path


def cpu_pinned() -> bool:
    """Whether the process was deliberately held to the CPU
    (``JAX_PLATFORMS=cpu``, which is where ``jax_platforms`` comes from)
    — the correctness mode of the tests and the CPU drives."""
    import jax
    plats = [p.strip() for p in (jax.config.jax_platforms or '').split(',')
             if p.strip()]
    return bool(plats) and all(p == 'cpu' for p in plats)


def meet_backend() -> str:
    """``jax.default_backend()``; the process's first call is its first
    touch of the backend (loading the runtime, reaching the chip: seconds
    on a TPU) and runs inside the hub span ``entry.backend`` [platform,
    devices], recorded once a process.  Both ways in come through here:
    :func:`require_chip` (the benchmark, ``chip_smoke.py``) and the
    trainer's device lookup (the CLI)."""
    global _met
    import jax
    if _met:
        return jax.default_backend()
    from ..obs import span
    with span('entry.backend', 'entry') as sp:
        backend = jax.default_backend()
        sp.attrs.update(platform=backend, devices=jax.device_count())
    _met = True
    return backend


def require_chip() -> str:
    """The backend a device measurement may run on: ``'tpu'``, or
    ``'cpu'`` when the caller pinned it (a correctness run, stamped as
    such).  Anything else — above all JAX's silent fall to the CPU when
    it finds no accelerator — raises."""
    import jax
    backend = meet_backend()
    if backend == 'tpu' or cpu_pinned():
        return backend
    raise BackendUnavailable(
        f"JAX backend is {backend!r} ({jax.devices()[0]}), not 'tpu'; pin "
        f'JAX_PLATFORMS=cpu for a correctness run on the CPU')
