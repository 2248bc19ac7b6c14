"""The SYMMETRY cell as the benchmark reads it (ISSUE 38): tier-1 runs the
harness's own test file, `perfbench/tests/test_symmetry.py`, so the repo's
count holds the cell's golden to its derivation and to the unreduced job,
`canonreduce` to its synthetic trace and each new `BENCHMARK.json` entry to
its reader.

CPU, no chip, seconds.  The cases live there because `pytest
perfbench/tests` is the harness's own judgement of itself
(`selfcheck.py --all`); loaded here by path, since `perfbench/` is a
directory of scripts and no package."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "test_symmetry.py")
_spec = importlib.util.spec_from_file_location(
    "perfbench_test_symmetry", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

# the tests and the fixtures they ask for, collected as this module's own
globals().update({k: v for k, v in vars(_mod).items()
                  if not k.startswith("_") and k != "pytest"})
