"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
from the root of a checkout. Everything that belongs to one configuration,
one traffic mix, one per-layer metric or one kind of driver sits in a file
of its own that the harness finds by name:

* ``bench/configs/<config>.json`` (the sizes) and the plain reference the
  file names, beside it;
* ``bench/mixes/<traffic>.json``: what the window runs, and which driver;
* ``bench/limits/<workload>.json``: the limits of the correctness check;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/drivers/<driver>.py``: one module per kind of work.
"""
