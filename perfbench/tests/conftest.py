"""One test whose subject moved, held by name meanwhile (PR 32).

`test_probe_rounds.py::test_reader_says_what_benchmark_json_says` (PR 31)
finds its entry as `bench["per_layer"][-1]` and holds that entry's cells to
the five of PR 31.  PR 32 appends two metrics after it and its own cell to
that entry's `workloads`, and may edit no file the benchmark already has
(the driver refuses a PR that does), so the test is expected to fail here,
and `strict`: the day a `benchmark` PR repairs the line it passes, this
hook fails the run, and the hook is deleted with it (PERF.md section 7).

Nothing it asserted is switched off: `test_asyncisr.py::
test_an_entry_says_what_its_reader_says[probe_rounds_share]` makes the same
four assertions on the entry found BY NAME (its name, its cells, its keys
against the reader's META, its `moves` among the end-to-end metrics), as it
does for every per-layer entry.
"""

import pytest

STALE = "test_probe_rounds.py::test_reader_says_what_benchmark_json_says"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                reason="finds its entry at per_layer[-1] and pins five "
                       "cells; held by name in test_asyncisr.py",
                strict=True))
