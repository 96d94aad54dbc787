"""``fed_samples_per_s`` (images/s, higher is better; source: host clock).

Samples of the steps completed in the window over the window's wall, drain
included, with the conf's own input chain feeding the stepper: what an epoch
costs.  No cell of ``BENCHMARK.json`` reports it yet: the one host thread
that bounds it spread too widely on the driver's machine to be admitted
(PERF.md, Findings and Open questions;
``selftest/fixtures/fed_cell/entries.json`` holds the entries that switch the
cell on)."""


def read(run):
    return run.samples_per_s()
