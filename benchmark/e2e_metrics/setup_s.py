"""``setup_s`` (s, lower is better; source: host clock).

From the start of the process to the opening of the window: interpreter,
imports, reaching the chip, the trainer's init, the inputs, compilation or
retrieval from the cache, and the warm-up steps."""


def read(run):
    return run.setup_s
