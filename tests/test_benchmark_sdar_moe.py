"""The benchmark's own tests of the ``sdar-30b-a3b-blockgen`` cell, run
in tier-1 as well: the runner at a tiny size on the CPU (unbroken it is
``correct``; an altered token, a skipped commit, the rule turned round
and the float8 control are not), every per-layer name of the cell, the
FLOP and byte functions against a hand count, and ``BENCHMARK.json``
against the files. They live with the benchmark
(``benchmarks/tests/test_sdar_moe.py``); this file only collects them.
"""

from benchmarks.tests.test_sdar_moe import *  # noqa: F401,F403
