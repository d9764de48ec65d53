"""Where a process started from this checkout keeps JAX's persistent
compilation cache.

Called from the process entry points only (``python -m client_tpu.serve``,
``python -m client_tpu.perf``, ``chip_smoke.py``, ``benchmark/run.py``),
before they import jax — never from library code: a library that moved
the cache would move it under every program that imports it.

The directory is part of every cache entry's address, so it must not move
between runs: no temp name, pid or timestamp.  An operator places it with
``JAX_COMPILATION_CACHE_DIR``; unset, it is ``.jax_cache`` at the root of
the checkout.  Either way JAX reads the variable itself (it is the
environment spelling of ``jax_compilation_cache_dir``), so no directory is
ever set through ``jax.config``, and child processes inherit the choice.
"""

import os
import sys

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "enable_compile_cache() must run before jax is imported: jax "
            "reads its cache settings from the environment at import"
        )
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _CHECKOUT_CACHE
    # cache every executable: a cold start on the chip is many sub-second
    # compiles as well as a few long ones
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]
