"""JAX's persistent compilation cache, for the program's entry points.

:func:`enable` is called first by every ``main()`` that compiles the train
step (``repro.launch.train``, ``chip_smoke.py``, ``benchmarks/run.py``) and
never at import, so importing the library changes no JAX setting.
"""

from __future__ import annotations

import contextlib
import os
import pathlib

import jax

#: fixed cache location inside the checkout (listed in .gitignore); never a
#: temp name, a pid or a time, so the next run in this checkout finds it
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Turn the persistent cache on.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))


@contextlib.contextmanager
def disabled():
    """Persistent cache off inside the block.

    For compiles against a described TPU topology with no chip attached:
    their entries are written but cannot be read back without the chip, so
    a second compile would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
