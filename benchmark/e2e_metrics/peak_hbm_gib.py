"""``peak_hbm_gib`` (GiB, lower is better; the contract's source for what the
benchmark reads itself: ``host_clock``).

The fullest of the cell's chips when the window closes: the allocator's
``peak_bytes_in_use`` plus the ``peak_bytes_reserved`` the runtime holds for
the loaded programs' scratch (``run.memory_peak``).  What caps the batch and
the model a user can train, and where work moved into memory shows."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
