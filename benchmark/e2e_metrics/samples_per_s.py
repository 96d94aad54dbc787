"""``samples_per_s`` (images/s, higher is better; source: host clock).

Samples of the steps completed in the window over the window's wall, drain
included, with the input already on the device: what the step itself costs,
over all the chips of the cell.  The same arithmetic as
``fed_samples_per_s``; a name of its own because a device-bound number
spreads less than a host-bound one, and one bound serves every cell of a
metric."""


def read(run):
    return run.samples_per_s()
