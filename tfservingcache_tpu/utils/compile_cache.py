"""Where this process keeps JAX's persistent compilation cache.

One rule, applied once at process start by the entry points (``tpuserve
serve`` / ``warm``, ``chip_smoke.py`` through ``serve``):

  1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing in
     this repo calls ``jax.config.update("jax_compilation_cache_dir", ...)``
     — whoever placed the cache from outside (a deploy image, the chip tool)
     finds every entry there again;
  2. else the operator's ``serving.compile_cache_dir``, when given;
  3. else ``DEFAULT_DIR``: one fixed, git-ignored directory inside the
     checkout.

The directory is part of the cache key, so it is never built from a
temporary name, a pid or the time: a directory that moves never hits. JAX
initialises the persistent cache once per process, at the first compile —
decide before it, not per runtime.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def resolve(operator_dir: str = "") -> str:
    """The directory the rule above picks (pure: touches neither JAX nor
    the disk)."""
    return os.environ.get(ENV_VAR) or operator_dir or DEFAULT_DIR


def configure(operator_dir: str = "") -> str:
    """Apply the rule above; -> the directory in effect."""
    import jax

    # cache every program, however quick its compile: with JAX's 1 s floor a
    # program whose compile time straddles the floor is written by whichever
    # run happens to be slow, and a warm restart still adds entries
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = resolve(operator_dir)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def entry_count(path: str) -> int:
    """Compiled programs in ``path`` (0 when it does not exist yet)."""
    try:
        return sum(1 for f in os.listdir(path) if not f.startswith("."))
    except OSError:
        return 0
