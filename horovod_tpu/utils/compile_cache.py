"""The one place that decides where XLA's persistent compile cache lives.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Where it is not, the cache is
``<checkout>/.jax_cache``: a fixed path, because the path is part of
the cache key — a directory named from a pid, the time or a temporary
name never hits. An installed package has no checkout around it (no
``bench.py`` beside the package) and writes nothing: JAX's own default
stands. Every entry point that compiles (``benchmarks/run.py``,
``tools/profile_step.py``, ``tools/serve_bench.py``,
``horovod_tpu.serve.worker``, ``chip_smoke.py``) calls :func:`enable` before its first compile.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[str]:
    """The directory to set in code, or ``None`` when the environment
    already placed the cache or this is not a checkout (a pure function
    of ``environ`` and of where the package sits)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if not os.path.isfile(os.path.join(_CHECKOUT, "bench.py")):
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> Optional[str]:
    """Point JAX at the persistent cache; returns the directory in use
    (``None``: an installed package with nothing set, no cache)."""
    import jax

    path = cache_dir()
    if path is None:
        return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    jax.config.update("jax_compilation_cache_dir", path)
    return path
