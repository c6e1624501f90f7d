"""The compile-cache helper: one directory for every process of every run,
taken from outside when $JAX_COMPILATION_CACHE_DIR says so — and never on
under pytest, whose compile-count pins need every compile to happen."""

import json
import os
import subprocess
import sys

from paddlebox_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = (
    "import json, sys; sys.path.insert(0, sys.argv[1]);"
    "from paddlebox_tpu.utils.compile_cache import enable_compile_cache;"
    "got = enable_compile_cache(); import jax;"
    "print(json.dumps({'returned': got,"
    " 'configured': jax.config.jax_compilation_cache_dir}))"
)


def _entry_point(cwd, **env_over) -> dict:
    """What an entry-point process ends up with (pytest's marker variable
    removed: the child is a run of its own)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTEST_CURRENT_TEST", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_over)
    r = subprocess.run(
        [sys.executable, "-c", _PROBE, REPO], capture_output=True, text=True,
        timeout=120, env=env, cwd=cwd, check=True,
    )
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_directory_from_the_environment_is_left_alone():
    got = _entry_point(REPO, JAX_COMPILATION_CACHE_DIR="/x")
    assert got == {"returned": "/x", "configured": "/x"}


def test_default_is_one_fixed_path_in_the_checkout(tmp_path):
    a = _entry_point(REPO)
    b = _entry_point(str(tmp_path))  # another process, another cwd
    want = os.path.join(REPO, ".jax_cache")
    assert a == b == {"returned": want, "configured": want}
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_off_under_pytest():
    assert "PYTEST_CURRENT_TEST" in os.environ
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None
