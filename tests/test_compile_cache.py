"""Where the entry points keep JAX's persistent compilation cache: in
``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise in one fixed,
git-ignored directory inside the checkout. Each case runs in a fresh
interpreter, because JAX reads the variable when it is imported."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODE = ("import jax; from repro.launch.compile_cache import init_compile_cache; "
        "print(init_compile_cache()); print(jax.config.jax_compilation_cache_dir); "
        "print(jax.config.jax_persistent_cache_min_compile_time_secs, "
        "jax.config.jax_persistent_cache_min_entry_size_bytes)")


def _cache_dirs(env_dir):
    """(directory init_compile_cache returns, directory JAX then uses);
    every compile is kept, whatever its time or size."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", CODE], env=env, text=True,
                          capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, used, min_secs, min_bytes = proc.stdout.split()
    assert float(min_secs) == 0 and int(min_bytes) == 0
    return returned, used


def test_env_var_is_the_cache_and_nothing_else_is_set(tmp_path):
    assert _cache_dirs(str(tmp_path)) == (str(tmp_path), str(tmp_path))


@pytest.mark.parametrize("unset", [None, ""], ids=["unset", "empty"])
def test_without_env_var_the_fixed_checkout_dir(unset):
    assert _cache_dirs(unset) == (str(CHECKOUT_CACHE_DIR),) * 2
    assert CHECKOUT_CACHE_DIR.parent == Path(ROOT).resolve()
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
