"""The entry points' persistent compile cache goes where it is told.

Each case runs in a fresh interpreter: the cache directory is process
state that JAX reads from the environment at import.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

PROBE = """
import jax
from repro.launch.compile_cache import configure_compile_cache
print(configure_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout.split()


@pytest.mark.parametrize("env_dir", [None, "set"], ids=["unset", "set"])
def test_compile_cache_dir(env_dir, tmp_path):
    if env_dir is None:
        want = str(REPO / ".jax_cache")
    else:
        env_dir = want = str(tmp_path / "cache")
    chosen, seen_by_jax = _probe(env_dir)
    assert chosen == want
    assert seen_by_jax == want
