"""How sharp the comparison with the plain reference is for the
``laguna-s21-ep32`` cell, on the chip:

    python3 -m benchmark.selftest.laguna_sensitivity [--seed n] [--steps n]

``glm_sensitivity``'s run (train ``--steps`` steps, take the program's
evaluation-mode probabilities on the check sequence once, put that sequence
through one real training step, print the comparison's numbers against the
reference as it is and against each variant of its probe) with this cell's
name and this family's probe (``references/laguna_moe.PROBE``): the window
left out, the window a block too wide, key/value heads strided (``h % 8``
for ``h // (H / 8)``), the gate left out, rotary on the whole head in a full
layer, YaRN left out, a sigmoid router, every product's operands rounded to
float8 (the nearest precision below the bf16 the configuration states); and,
for the step alone, an eighth of the tokens dropped from the loss and the
loss over every other token.  The limits have to hold the first and refuse
each of the others.  Not part of a run; PERF.md records what it printed.
"""

from __future__ import annotations

import sys

from . import glm_sensitivity

CELL = 'laguna-s21-ep32-seq8k'


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if '--workload' not in argv:
        argv = ['--workload', CELL] + argv
    return glm_sensitivity.main(argv)


if __name__ == '__main__':
    sys.exit(main())
