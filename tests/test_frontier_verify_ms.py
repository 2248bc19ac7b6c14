"""`frontier_verify_ms` as the benchmark reads it (ISSUE 46): tier-1 runs the
harness's own test file, `perfbench/tests/test_frontier_verify.py`, so the
repo's count holds the new `BENCHMARK.json` entry to its reader and the
reader to spans with and without `frontier-verify`.

CPU, no chip, seconds.  Loaded by path, as `tests/test_probe_window_share.py`
loads its file: `perfbench/` is a directory of scripts and no package."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_frontier_verify"))
