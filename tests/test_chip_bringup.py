"""Chip bring-up contracts that hold WITHOUT a chip: the program never
hides a missing accelerator behind the CPU, and the compile cache is
placed by one rule (utils/platform_guard).

The chip itself is exercised by `chip_smoke.py` on a machine that has
one; these tests pin what that script and the CLI do on a box that does
not.
"""

import os
import shutil
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env=None, cwd=_REPO, timeout=120):
    t0 = time.monotonic()
    p = subprocess.run(argv, env=env, cwd=cwd, timeout=timeout,
                       capture_output=True, text=True)
    return p, time.monotonic() - t0


def test_chip_smoke_fails_without_chip(tmp_path):
    """A chipless box: the smoke exits non-zero in seconds with JAX's own
    reason and prints no result (run from a scratch checkout view so its
    artifacts stay out of the repo)."""
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    for name in ("kafka_specification_tpu", "configs"):
        os.symlink(os.path.join(_REPO, name), tmp_path / name)
    p, wall = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert wall < 30, wall
    assert "Unable to initialize backend 'tpu'" in p.stderr, p.stderr[-800:]
    assert "kspec-verdict" not in p.stdout and '"ok"' not in p.stdout
    # only the first phase ran: nothing after a failed assertion
    assert not (tmp_path / "chiprun_out" / "chip_smoke" / "p2.stderr").exists()


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    p, _ = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_cli_check_demanded_tpu_is_an_error_not_a_cpu_run(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "tpu",
           "KSPEC_RUNS_ROOT": str(tmp_path)}
    p, _ = _run(
        [sys.executable, "-m", "kafka_specification_tpu.utils.cli", "check",
         "configs/IdSequence.cfg", "--hand", "--json"], env=env)
    assert p.returncode != 0
    assert "Unable to initialize backend 'tpu'" in p.stderr, p.stderr[-800:]
    assert "retrying on CPU" not in p.stderr
    assert "kspec-verdict" not in p.stdout


def _cache_dir_in_child(env_value):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = (
        "import jax\n"
        "from kafka_specification_tpu.utils.platform_guard import (\n"
        "    compile_cache_dir, enable_compile_cache)\n"
        "enable_compile_cache()\n"
        "print(compile_cache_dir())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    p, _ = _run([sys.executable, "-c", code], env=env)
    assert p.returncode == 0, p.stderr[-800:]
    return p.stdout.split()


def test_compile_cache_placed_from_outside_or_in_checkout(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets no
    directory in code (JAX reads the variable itself); otherwise the cache
    is the fixed <checkout>/.jax_cache."""
    outside = str(tmp_path / "cache")
    assert _cache_dir_in_child(outside) == ["None", outside]
    in_checkout = os.path.join(_REPO, ".jax_cache")
    assert _cache_dir_in_child(None) == [in_checkout, in_checkout]
