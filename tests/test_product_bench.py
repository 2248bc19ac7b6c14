"""The 5-broker x 3-partition cell as the benchmark reads it (ISSUE 44):
tier-1 runs the harness's own test file, `perfbench/tests/test_product.py`,
so the repo's count holds the cell's golden to its derivation and to the
threefold convolution of the one-partition golden, the configuration to the
cfg a user runs, and each new `BENCHMARK.json` entry to its reader.

CPU, no chip, seconds (the rehearsal there is `slow`).  The cases live there
because `pytest perfbench/tests` is the harness's own judgement of itself
(`selfcheck.py --all`); loaded here by path, since `perfbench/` is a
directory of scripts and no package.  The engine's side of the cell is
`tests/test_product_cell.py`."""

from helpers import perfbench_tests

# the tests and the fixtures they ask for, collected as this module's own
globals().update(perfbench_tests("test_product"))
