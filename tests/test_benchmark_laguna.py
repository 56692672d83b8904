"""The benchmark's own tests of the ``laguna-s-2.1-codegen`` cell, run
in tier-1 as well: the runner at a tiny size on the CPU with half the
experts held (unbroken it is ``correct``; the float8 control and a
program without its head gates, without its shared expert or turning
the whole of a full layer's head are not), every name of the cell, the
readers of its two new metrics, the FLOP and byte functions against a
hand count, and ``BENCHMARK.json`` against the files. They live with
the benchmark (``benchmarks/tests/test_laguna.py``); this file only
collects them.
"""

from benchmarks.tests.test_laguna import *  # noqa: F401,F403
