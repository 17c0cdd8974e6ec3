"""Launch entry points (CLI): train, serve, recycle, mesh helpers.

Each module is runnable as ``python -m repro.launch.<name>``; this package
marker makes ``repro.launch`` a regular (non-namespace) package so tooling
that walks packages (pytest rootdir scans, pkgutil) sees it like every
other ``repro`` subpackage.
"""

import os
import pathlib

# the checkout root (src/repro/launch/__init__.py -> three levels up)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home on a TPU, and
    return the directory in use ("" where there is none).

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here overrides it. Otherwise a TPU process keeps its cache in
    ``<checkout>/.jax_cache`` — fixed, never built from a temp name, a pid
    or the time, so a second run of the same programs hits it. Off a TPU
    nothing is set: CPU compiles are short and uncached. Call this at the
    start of an entry point, before its first compile."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    if jax.default_backend() != "tpu":
        return ""
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

