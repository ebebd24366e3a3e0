"""Where JAX keeps compiled programs between runs.

The entry points (`repro.launch.serve`, `repro.launch.train` and the
repository's `chip_smoke.py`) call `use_compile_cache()` once, before
they compile anything.  Importing this module, or any library module,
changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The variable JAX itself reads at import for its cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The cache used when `CACHE_ENV` is unset: one fixed directory at the
#: root of the checkout.  The path is part of each entry's key, so it
#: must not move between runs (no temporary name, process id or time).
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
    uses it and nothing is set here; otherwise the cache goes to
    `CHECKOUT_CACHE`."""
    configured = os.environ.get(CACHE_ENV)
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
