"""Where JAX keeps its persistent compilation cache.

Every entry point (``repro.launch.serve``, ``chip_smoke.py``,
``benchmarks/run.py``, ``benchmarks/serve_bench.py``) calls
:func:`configure` before it compiles anything.  When
``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path (never a temp name, pid or time),
so a later run from the same checkout finds what an earlier one
compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure() -> str:
    """Place JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
