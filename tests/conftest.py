"""Force tests onto a virtual 8-device CPU platform.

The sharded-frontier path (parallel/) must be exercisable in CI without TPU
hardware; the chip itself is exercised by `chip_smoke.py`, not by this suite.
"""

import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from kafka_specification_tpu.models.emitted import ref_path  # noqa: E402
from kafka_specification_tpu.utils.platform_guard import (  # noqa: E402
    enable_compile_cache,
)

# Persistent compilation cache: the suite's wall time is dominated by
# XLA:CPU compiles of the per-model level steps; cached AOT results make
# re-runs start warm (the cache directory is gitignored).
enable_compile_cache()

# The upstream corpus (hachikuji/kafka-specification), where the emitted
# model builder looks for it.  A test that opens the checkout carries
# `needs_reference`: where it is not mounted the test says so and skips,
# so the suite's exit code speaks for the tests that can run.
REFERENCE = ref_path()
needs_reference = pytest.mark.skipif(
    not REFERENCE.is_dir(),
    reason=f"no reference checkout at {REFERENCE} (set KSPEC_REFERENCE)",
)
