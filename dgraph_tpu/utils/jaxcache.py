"""Where JAX's persistent compilation cache lives: the ONE place that
decides it, for the server binary and every bench script.

A cold program at deployed widths compiles for seconds to minutes
(PERF.md), and the cache's directory is part of its key, so a directory
that moves never hits.  Hence:

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads the
  variable itself and this module sets nothing — whoever runs the process
  placed the cache;
- otherwise one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored) — never the postings directory, a temporary name, a pid or
  a time;
- an explicit directory (``--compile_cache <dir>``) overrides the fixed
  one; ``""`` turns the cache off.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_FIXED = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure(choice: str = "auto") -> str:
    """Turn the persistent compilation cache on and return the directory
    it uses ('' = off).  ``choice``: "auto" (the fixed in-checkout
    directory), a directory, or "" (off); ignored when the environment
    already names one."""
    placed = os.environ.get(ENV, "")
    if placed:
        return placed
    if not choice:
        return ""
    import jax

    path = _FIXED if choice == "auto" else choice
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # the served programs' small shapes compile in under JAX's default
    # 1 s floor and would otherwise never be cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def in_use() -> str:
    """The directory JAX's cache is pointed at right now ('' = off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or ""
