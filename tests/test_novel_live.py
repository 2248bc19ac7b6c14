"""`novel_live_share` as the benchmark reads it (ISSUE 37): tier-1 runs the
harness's own test file, `perfbench/tests/test_novel_live.py`, so the
repo's count holds the new `BENCHMARK.json` entry to its reader and the
reader to records with and without the two fields.

CPU, no chip, seconds.  Loaded by path, as `tests/test_parts.py` loads its
file: `perfbench/` is a directory of scripts and no package."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "test_novel_live.py")
_spec = importlib.util.spec_from_file_location("perfbench_test_novel_live", _PATH)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

# the tests and the fixture they ask for, collected as this module's own
globals().update({k: v for k, v in vars(_mod).items()
                  if not k.startswith("_") and k != "pytest"})
