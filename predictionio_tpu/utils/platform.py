"""JAX process set-up: which platform, and where compiled programs are kept.

Both are settled through JAX's own environment variables, before JAX is
imported, so this module never imports JAX, a command that never compiles
(``pio app new``, ``pio import``) pays nothing, and every child process
(train workers, fleet workers, grid workers, bench phases) inherits the
same settings.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache, from this file's location: the directory is part
# of the cache key, so it must not depend on the working directory. This
# is the checkout only when the package runs from a source tree. Installed
# with pip the same expression names site-packages/.jax_cache, which may be
# read-only or shared: an installed image sets JAX_COMPILATION_CACHE_DIR
# (the Dockerfile does)
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_jax() -> None:
    """Call before the first ``import jax`` of the process.

    Platform: with ``JAX_PLATFORMS`` unset JAX tries the accelerator,
    warns once and carries on on the CPU, and a train would then persist
    and serve CPU results with exit code 0. The framework is written for
    the TPU, so unset means ``tpu`` and a missing chip fails at backend
    start-up. ``JAX_PLATFORMS=cpu`` is the one way to run on the CPU.

    Compile cache: ``JAX_COMPILATION_CACHE_DIR`` set from outside is read
    by JAX itself and left alone; unset, the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR`` (a source checkout's ``.jax_cache``; an
    installed package must be given the variable). The serving bucket
    programs compile in well under JAX's default one-second admission
    threshold, so the threshold drops to zero unless the caller set one. A
    CPU run gets no default cache: its compiles are quick, and jaxlib
    0.9.0's XLA:CPU loader logs a multi-kilobyte machine-feature warning
    for every program it loads back.
    """
    env = os.environ
    if not env.get("JAX_PLATFORMS"):
        env["JAX_PLATFORMS"] = "tpu"
    if not env.get("JAX_COMPILATION_CACHE_DIR") and env["JAX_PLATFORMS"] != "cpu":
        env["JAX_COMPILATION_CACHE_DIR"] = str(DEFAULT_COMPILE_CACHE_DIR)
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
