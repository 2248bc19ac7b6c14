"""`probe_window_share` as the benchmark reads it (ISSUE 45): tier-1 runs the
harness's own test file, `perfbench/tests/test_probe_window.py`, so the
repo's count holds the new `BENCHMARK.json` entry to its reader and the
reader to records with and without the two fields.

CPU, no chip, seconds.  Loaded by path, as `tests/test_probe_live.py` loads
its file: `perfbench/` is a directory of scripts and no package."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_probe_window"))
