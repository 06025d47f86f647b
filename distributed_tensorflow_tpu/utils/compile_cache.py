"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, the examples,
``tests/conftest.py``): the cache is wherever
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at one fixed path
under the checkout. The path must not move between runs — a cache in a
tempdir or under a pid never hits — so it is derived from this file's
location and nothing else.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".cache", "dtx_jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX read it at import and
    this changes nothing. Unset, the cache goes to ``DEFAULT_DIR`` and
    the variable is exported so child processes agree with their
    parent. Call before the first compile.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    os.environ[ENV_VAR] = DEFAULT_DIR
    return DEFAULT_DIR
