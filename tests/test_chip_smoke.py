"""``chip_smoke.py`` and the compile-cache helper every entry point calls.

The smoke's phases run here at a tiny size on the CPU (their control flow
and checks); the script itself must refuse to run without a TPU, and
without the rest of the checkout, printing no ``"ok": true`` line.
"""
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, describe_failure, run_devices_subprocess

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    out = _run_script(SMOKE, REPO)
    assert out.returncode != 0, describe_failure(out)
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_without_the_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    out = _run_script(str(alone), str(tmp_path))
    assert out.returncode != 0, describe_failure(out)
    assert '"ok": true' not in out.stdout


_TINY_PHASES = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
from repro.configs import get_smoke
meter = cs.CompileMeter()
cs.phase_a(meter, rows=256, features=1000, stream_rows=128, stream_epochs=2)
cs.phase_a_four_chips(meter, chips=4, rows_per_chip=64, features=1000)
cs.phase_b(meter, get_smoke("qwen2-1.5b"), prompt_lens=(5, 17, 9, 30, 12, 3),
           max_new=4, max_seq=64)
print("TINY PHASES OK")
"""


def test_chip_smoke_phases_pass_at_tiny_size(tmp_path):
    """Every phase and check of the smoke, with its shapes cut down: the
    resident, streaming and kernel checks of phase A, the three schedules
    on a 4-device mesh, and serving the qwen2-1.5b smoke config."""
    out = run_devices_subprocess(
        _TINY_PHASES.format(repo=REPO), devices=4,
        env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert "TINY PHASES OK" in out.stdout
    assert "FAIL" not in out.stdout
    assert out.stdout.count("check: ") == 11, out.stdout


_CACHE_PROBE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache is
    the fixed ``.jax_cache/`` at the checkout root."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = str(tmp_path / "elsewhere")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                         capture_output=True, text=True, env=env,
                         cwd=str(tmp_path), timeout=120)
    assert out.returncode == 0, describe_failure(out)
    assert out.stdout.split() == [want, want]
