"""The benchmark: cells of BENCHMARK.json run one at a time by ``run.py``.

Everything that decides a number lives here, outside the program under
test: traffic, the timed window, the trace reduction, the table of peaks,
the analytic operation counts, the plain reference and the comparison that
decides ``correct``.  See README.md.
"""
