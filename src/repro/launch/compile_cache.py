"""Persistent JAX compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path inside the checkout, because the cache only hits when the
directory stays put between runs. ``chip_smoke.py``, ``serve.main`` and
``train.main`` call :func:`enable_compile_cache` before their first
compile; library code and tests never do.

A disaggregated server (prefill and decode on meshes of their own) runs
with the persistent cache off. On a TPU v5e 2x2 host, every process
whose decode step on the decode chips [2, 3] was loaded from the cache
halted the chips ("The program continuator has halted unexpectedly"),
whether the entry came from another machine, another process on the
same machine or the same process; every process that compiled that
step passed, including ones that loaded the prefill step from the
cache. A decode step loaded onto chip 0 on a one-chip host runs.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> repo root / .jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> pathlib.Path:
    """Where compiled programs are cached: the env var, else DEFAULT_DIR."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else DEFAULT_DIR


def enable_compile_cache(disaggregated: bool = False
                         ) -> pathlib.Path | None:
    """Returns the cache directory, or None with ``disaggregated``, where
    the persistent cache is turned off for the whole process (it is
    decided at the first compile, so call this before it)."""
    if disaggregated:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return compile_cache_dir()
