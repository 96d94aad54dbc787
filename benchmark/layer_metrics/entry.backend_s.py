"""``entry.backend_s`` - LAYER entry/backend (``utils/backend.meet_backend``);
UNIT s; MOVES ``setup_s``; every cell.

The program's span ``entry.backend`` around the process's first touch of the
JAX backend: loading the runtime and reaching the chip.  What of ``setup_s``
comes before it is the interpreter and the imports."""

from benchmark import program_spans

LAYER, UNIT, MOVES = 'entry', 's', 'setup_s'


def read(run):
    return program_spans.seconds(run, 'entry.backend')
