"""The SYMMETRY cell as the benchmark reads it (ISSUE 38): tier-1 runs the
harness's own test file, `perfbench/tests/test_symmetry.py`, so the repo's
count holds the cell's golden to its derivation and to the unreduced job,
`canonreduce` to its synthetic trace and each new `BENCHMARK.json` entry to
its reader.

CPU, no chip, seconds.  The cases live there because `pytest
perfbench/tests` is the harness's own judgement of itself
(`selfcheck.py --all`); loaded here by path, since `perfbench/` is a
directory of scripts and no package."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_symmetry"))
