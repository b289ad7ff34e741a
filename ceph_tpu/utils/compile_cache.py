"""One place that decides where JAX's persistent compile cache lives.

Every process that compiles for the device (bench.py, the test
conftest, tools/vstart.py, chip_smoke.py) calls :func:`configure`
before its first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here.  Otherwise the cache is
``<checkout>/.jax_cache`` as a normalised absolute path: the path is
part of the cache key, so it must be the same string in every process
of a checkout, and never a temp name, pid or time.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Point JAX at the compile cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    # exported so child processes land in the same directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    # the default (1 s) skips most of the small per-shape executables
    # a cluster dispatches; they add up to minutes per cold start
    min_s = os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    # jax reads the environment at import only; it may already be in
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_s))
    return path
