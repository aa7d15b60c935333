"""The command-line entry points' persistent compile cache location.

Each case runs in a child process: the cache directory is process-wide
jax configuration, and the child holds no accelerator (CPU only).
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_CHILD = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_CACHE_DIR, configure_compile_cache
used = configure_compile_cache()
print("USED", used)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", DEFAULT_CACHE_DIR)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(17)).block_until_ready()
"""


def _run(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c",
                           _CHILD.format(compile=compile_)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(line.split(" ", 1) for line in proc.stdout.splitlines()
                if line.split(" ", 1)[0] in ("USED", "CONFIG", "DEFAULT"))


def test_env_dir_wins_and_receives_entries(tmp_path):
    cache = tmp_path / "jaxcache"
    out = _run(cache, compile_=True)
    assert out["USED"] == out["CONFIG"] == str(cache)
    assert any(cache.iterdir())


def test_default_is_fixed_path_in_checkout():
    out = _run(None, compile_=False)
    assert out["USED"] == out["CONFIG"] == out["DEFAULT"] \
        == str(REPO / ".jax_cache")


_SCOPED = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
def step(x):
    if {scoped}:
        with jax.named_scope("sweep.gather"):
            return jnp.sin(x) * 3.0
    return jnp.sin(x) * 3.0
text = jax.jit(step).lower(jnp.ones(17)).compile().as_text()
print("SCOPED", "sweep.gather" in text)
"""


def test_cached_executable_keeps_the_scopes_it_was_compiled_with(tmp_path):
    """A program that differs from a cached one only in its named scopes
    compiles its own executable: profiles read the scopes from the
    executable's op metadata."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))
    seen = []
    for scoped in (False, True):
        proc = subprocess.run([sys.executable, "-c",
                               _SCOPED.format(scoped=scoped)],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.append(proc.stdout.split("SCOPED ", 1)[1].strip())
    assert seen == ["False", "True"]
