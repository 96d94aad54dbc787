"""``entry.compile_s`` - LAYER entry/backend (``main.py``,
``utils/backend.py``); UNIT s; MOVES ``setup_s``; every cell.

What JAX itself reports as backend compilation during set-up
(``jax.monitoring``, ``/jax/core/compile/backend_compile_duration``): real
compilation in a checkout's first run, retrieval from the persistent cache
after it."""

LAYER, UNIT, MOVES = 'entry', 's', 'setup_s'


def read(run):
    return run.setup_compile_s
