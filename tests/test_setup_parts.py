"""`setup_s` by part as the benchmark reads it (ISSUE 49): tier-1 runs the
harness's own test file, `perfbench/tests/test_setup_parts.py`, so the
repo's count holds the eight readers of the process ledger to their
synthetic records and each new `BENCHMARK.json` entry to its reader.

CPU, no chip, seconds.  The cases live there because `pytest
perfbench/tests` is the harness's own judgement of itself
(`selfcheck.py --all`); loaded here by path, since `perfbench/` is a
directory of scripts and no package."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_setup_parts"))
