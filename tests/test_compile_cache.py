"""Where ``repro.launch.compile_cache.enable`` puts the persistent compilation
cache: in ``$JAX_COMPILATION_CACHE_DIR`` when that is set (and nowhere else),
otherwise in ``.jax_cache/`` at the checkout root.

Each case runs in a fresh CPU-only interpreter, since JAX fixes its cache
directory per process. The jitted function carries a constant unique to
the run, so its entry is always new and never a cache hit.
"""
import os
import pathlib
import subprocess
import sys
import uuid

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def _compile_once(env_dir: str | None) -> str:
    salt = uuid.uuid4().int % 1_000_003
    code = (
        "from repro.launch import compile_cache\n"
        "print(compile_cache.enable())\n"
        "import jax, jax.numpy as jnp\n"
        f"jax.jit(lambda a: a * {salt} + 1)(jnp.ones(8)).block_until_ready()\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env[compile_cache.ENV] = env_dir
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    return out.stdout.strip().splitlines()[-1]


def _entries(path: pathlib.Path) -> set[str]:
    return set(os.listdir(path)) if path.is_dir() else set()


def test_default_dir_is_fixed_at_checkout_root():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"


def test_env_dir_gets_every_entry(tmp_path):
    before = _entries(compile_cache.DEFAULT_DIR)
    assert _compile_once(str(tmp_path)) == str(tmp_path)
    assert any(e.startswith("jit__lambda") for e in _entries(tmp_path))
    assert _entries(compile_cache.DEFAULT_DIR) == before


def test_unset_env_uses_checkout_cache():
    before = _entries(compile_cache.DEFAULT_DIR)
    assert _compile_once(None) == str(compile_cache.DEFAULT_DIR)
    new = _entries(compile_cache.DEFAULT_DIR) - before
    assert any(e.startswith("jit__lambda") for e in new)
