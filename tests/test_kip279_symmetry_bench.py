"""The 5-broker Kip279 cell under SYMMETRY as the benchmark reads it (ISSUE
47): tier-1 runs the harness's own test file,
`perfbench/tests/test_kip279_symmetry.py`, so the repo's count holds the
cell's golden to its derivation and to the oracle's orbit sizes, the
configuration to the cfg a user runs, and each new `BENCHMARK.json` entry to
its reader.

CPU, no chip, seconds.  The cases live there because `pytest
perfbench/tests` is the harness's own judgement of itself
(`selfcheck.py --all`); loaded here by path, since `perfbench/` is a
directory of scripts and no package.  The engine's side of the cell is
`tests/test_kip279_symmetry_cell.py`."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_kip279_symmetry"))
