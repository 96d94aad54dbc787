"""``net.init_s`` - LAYER net (``NetTrainer.init_model``); UNIT s; MOVES
``setup_s``; every cell.

Sum of the program's ``net.init_model`` spans before the window: building
the net from the conf, the parameters' initialisation (leaf by leaf, on the
device) and placing parameters, optimizer state and gradient accumulator on
the mesh."""

from benchmark import program_spans

LAYER, UNIT, MOVES = 'net', 's', 'setup_s'


def read(run):
    return program_spans.seconds(run, 'net.init_model')
