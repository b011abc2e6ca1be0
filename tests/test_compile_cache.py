"""The persistent compilation cache goes where the environment says, or
to one fixed directory in the checkout — never anywhere else.

Each case runs in a fresh interpreter: JAX decides once per process
whether (and where) its cache lives."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.exec import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_default_dir_is_fixed_in_checkout():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_entries_land_in_one_place(tmp_path, from_env):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    code = textwrap.dedent(f"""
        import pathlib
        from repro.exec import compile_cache
        compile_cache.DEFAULT_DIR = pathlib.Path({str(default_dir)!r})
        print(compile_cache.enable_compile_cache())
        import jax, jax.numpy as jnp
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    want, other = (env_dir, default_dir) if from_env else \
        (default_dir, env_dir)
    assert res.stdout.split() == [str(want)]
    assert any(want.iterdir()), "no cache entry written"
    assert not other.exists()
