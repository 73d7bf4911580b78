"""runtime/device.py: the platform decides interpretation and the cache."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.runtime import device

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_pallas_interpreted_exactly_on_cpu(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert device.pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        device.pallas_interpret()


# a fresh process: JAX reads JAX_COMPILATION_CACHE_DIR when it starts, and
# its cache directory is fixed once the first program is cached
_CHILD = """
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
import repro
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
with repro.Client({lake!r}) as client:
    print(client.compile_cache_dir)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_directory(tmp_path, from_env):
    """Entries land where the variable says, else in the checkout's
    ``.jax_cache`` — run from a copy of ``src`` so the checkout is the
    test's own."""
    checkout = tmp_path / "checkout"
    shutil.copytree(REPO_ROOT / "src", checkout / "src")
    default_dir = checkout / ".jax_cache"
    env = {k: v for k, v in os.environ.items() if k != device.CACHE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    expected = default_dir
    if from_env:
        expected = tmp_path / "jax_cache"
        env[device.CACHE_ENV] = str(expected)
    script = _CHILD.format(src=str(checkout / "src"), lake=str(tmp_path / "lake"))
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=str(tmp_path), timeout=240,
    )
    assert proc.returncode == 0, proc.stderr
    assert Path(proc.stdout.strip()).resolve() == expected.resolve()
    assert any(p.is_file() for p in expected.rglob("*")), "no compile entry"
    assert default_dir.exists() is not from_env
