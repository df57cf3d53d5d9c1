"""Process-level setup: the persistent compile cache helper.

The helper must run before a process's first compile, and the test
processes keep the cache off (see conftest.py), so each case runs in a
fresh CPU-only interpreter.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = """
import json, pathlib, sys
from repro.utils import platform as rplat
if sys.argv[1]:  # stand in for a checkout at that path
    rplat._CHECKOUT = pathlib.Path(sys.argv[1])
path = rplat.enable_compile_cache()
if sys.argv[2] == "compile":
    import jax, jax.numpy as jnp
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps(path))
"""


def _run(tmp_path, root, action, cache_var=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if cache_var is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_var
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, root, action], env=env,
        cwd=tmp_path, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def _entries(path: pathlib.Path):
    return [p for p in path.rglob("*") if p.is_file()] if path.exists() \
        else []


@pytest.mark.parametrize("var_set", [False, True], ids=["default", "env"])
def test_compile_cache_lands_in_one_place(tmp_path, var_set):
    root = tmp_path / "checkout"
    named = tmp_path / "named"
    path = _run(tmp_path, str(root), "compile",
                str(named) if var_set else None)
    default = root / ".jax_cache"
    if var_set:
        assert path == str(named)
        assert _entries(named) and not _entries(default)
    else:
        assert path == str(default)
        assert _entries(default) and not _entries(named)


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    assert _run(tmp_path, "", "none") == str(REPO / ".jax_cache")
