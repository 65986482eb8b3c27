"""What the chip entry points promise before any chip is involved:
``chip_smoke.py`` refuses a non-TPU platform, and the persistent compile
cache lives at ``$JAX_COMPILATION_CACHE_DIR`` or at the fixed, git-ignored
``<repo>/.jax_cache``. Children run with ``JAX_PLATFORMS=cpu``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.pathsep.join(
                    [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]),
                **env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=120, env=full, cwd=REPO)


def test_chip_smoke_refuses_cpu():
    r = _run([str(REPO / "chip_smoke.py")])
    assert r.returncode != 0
    assert "'cpu'" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout


def test_env_cache_dir_is_the_only_one(tmp_path):
    r = _run(["-c", (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")],
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())


def test_cached_program_keeps_its_own_op_names(tmp_path):
    """Two programs that differ only in a named scope are two cache
    entries: the second compiles and its ops carry its own scope, not the
    cached program's."""
    r = _run(["-c", (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "def f(scope):\n"
        "    def g(x):\n"
        "        with jax.named_scope(scope):\n"
        "            return jnp.sin(x) * 2\n"
        "    return jax.jit(g).lower(jnp.ones(8)).compile().as_text()\n"
        "print('/old/' in f('old'), '/new/' in f('new'))\n")],
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["True", "True"]
    assert len(list(tmp_path.iterdir())) >= 2


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
