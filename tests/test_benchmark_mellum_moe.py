"""The benchmark's own tests of the ``mellum2-12b-mixedlen`` cell, run
in tier-1 as well: the runner at a tiny size on the CPU (unbroken it is
``correct``; the float8 control, a window layer that sees one block too
few and one that sees one block too many are not), every name of the
cell, the FLOP and byte functions against a hand count, and
``BENCHMARK.json`` against the files. They live with the benchmark
(``benchmarks/tests/test_mellum_moe.py``); this file only collects them.
"""

from benchmarks.tests.test_mellum_moe import *  # noqa: F401,F403
