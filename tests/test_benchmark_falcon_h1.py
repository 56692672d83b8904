"""The benchmark's own tests of the ``falcon-h1-34b-shortchat`` cell, run
in tier-1 as well: the runner at a tiny size on the CPU (unbroken it is
``correct``, the float8 control is not, the planted state faults are
what they say), every name of the cell, the configuration against the
catalog's config, the FLOP and byte functions against a hand count,
and ``BENCHMARK.json`` against the files. They live with the benchmark
(``benchmarks/tests/test_falcon_h1.py``); this file only collects them.
"""

from benchmarks.tests.test_falcon_h1 import *  # noqa: F401,F403
