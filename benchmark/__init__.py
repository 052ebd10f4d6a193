"""The benchmark: one command runs one cell once on the chip (run.py).

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name BENCHMARK.json gives it:
configs/<config>.json, traffic/<traffic>.json, end_to_end/<metric>.py,
layer_metrics/<metric>.py.  A new kind of program is a new file in
drivers/.  The yardstick (traffic generation, arithmetic, trace reduction,
peaks, operation counts, plain references, the comparison behind
``correct``) lives here and imports nothing from the program but the
system under test."""
