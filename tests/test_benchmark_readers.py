"""The benchmark's own tests of its readers, run in tier-1 as well: the
``residual`` reader on dumps made by hand, and the two metrics of the
decode engine's pauses on a tiny engine's dump. They live with the
benchmark (``benchmarks/tests/test_readers.py``); this file only
collects them.
"""

from benchmarks.tests.test_readers import *  # noqa: F401,F403
