"""chip_smoke.py never reports a result off the chip, and the compile cache
lands where the environment says.

Each case runs a fresh interpreter held to the CPU, so nothing here loads
the TPU library.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _python(args, cwd, **env):
    """Run a CPU-held interpreter; ``env`` entries set to None are unset."""
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    full_env = {k: v for k, v in full_env.items() if v is not None}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full_env,
                          capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_chip_smoke_refuses_cpu():
    out = _python(["chip_smoke.py"], ROOT)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "not 'tpu'" in out.stderr
    assert not _has_result(out.stdout)


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _python(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)


_COMPILE_ONE = (
    "import sys; sys.path.insert(0, {src!r})\n"
    "from repro.launch import compile_cache\n"
    "{patch}"
    "print(compile_cache.enable_compile_cache())\n"
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")


def test_compile_cache_env_dir_is_the_only_one(tmp_path):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    code = _COMPILE_ONE.format(
        src=str(ROOT / "src"),
        patch=f"compile_cache.DEFAULT_CACHE_DIR = {str(default_dir)!r}\n")
    out = _python(["-c", code], tmp_path,
                  JAX_COMPILATION_CACHE_DIR=str(env_dir),
                  JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == str(env_dir)
    assert any(env_dir.iterdir())
    assert not default_dir.exists()


def test_compile_cache_default_dir_is_fixed(tmp_path):
    default_dir = tmp_path / "default"
    code = _COMPILE_ONE.format(
        src=str(ROOT / "src"),
        patch=f"compile_cache.DEFAULT_CACHE_DIR = {str(default_dir)!r}\n")
    out = _python(["-c", code], tmp_path, JAX_COMPILATION_CACHE_DIR=None,
                  JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == str(default_dir)
    assert any(default_dir.iterdir())


def test_compile_cache_default_is_in_checkout():
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


def test_importing_repro_sets_no_cache_dir(tmp_path):
    code = ("import sys; sys.path.insert(0, {src!r})\n"
            "import repro, repro.fl, repro.core, repro.launch.compile_cache\n"
            "import jax\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            ).format(src=str(ROOT / "src"))
    out = _python(["-c", code], tmp_path, JAX_COMPILATION_CACHE_DIR=None)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
