"""Platform and compile-cache selection shared by every entry point
(CLI, perfbench/, scripts/, tests).

The program runs in-process on whatever platform JAX gives it; nothing
here probes a platform or falls back to another.  An accelerator belongs
to one process at a time, so a parent that spawns jax children must not
import jax itself (`cpu_env` and `compile_cache_dir` are jax-free).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cpu_env(base_env=None) -> dict:
    """A child-process environment pinned to the CPU platform."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def pin_cpu_in_process() -> None:
    """Pin the CURRENT process to CPU (for --cpu flags / scripts).

    Must run before anything initializes an XLA backend.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")


def compile_cache_dir():
    """The directory this program sets in code, or None when the
    environment already placed the cache.

    The one rule: where JAX_COMPILATION_CACHE_DIR is set, JAX reads it
    itself and the program sets no directory; otherwise the cache is
    `<checkout>/.jax_cache` — a fixed path, shared by the CLI, the tests
    and the benchmark, so every process of a run warms the same entries.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 0.5) -> None:
    """Turn on JAX's persistent compilation cache under the rule above.

    `min_compile_secs` is the smallest compile worth an entry: the CLI
    passes 0 (a toy config is nothing but small programs), the test
    suite keeps JAX-side lookups for its thousands of trivial jits off
    the disk.
    """
    import jax

    from ..obs.ledger import PROCESS

    PROCESS.install()  # "JAX imported", and its build events from here on
    cache_dir = compile_cache_dir()
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )


def device_stamp() -> dict:
    """What JAX runs on, as the run records name it: platform,
    device_kind and device count (initializes the backend)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def device_label(stamp: dict) -> str:
    """One-line rendering of a `device_stamp()` for stderr banners."""
    return (f"{stamp['platform']} ({stamp['device_kind']} "
            f"x{stamp['device_count']})")
