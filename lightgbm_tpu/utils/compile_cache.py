"""Where compiled programs persist between processes: one rule.

Every process start pays the full jit compile bill (minutes at bench
shapes on a TPU) unless jax reloads serialized executables from its
persistent compilation cache. The directory is chosen here and nowhere
else:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it at import and owns
  the directory; this module reports the path. Nothing in the repo
  calls ``jax.config.update("jax_compilation_cache_dir", ...)`` then.
* unset, on a TPU: the fixed ``<checkout>/.jax_cache_tpu`` (listed in
  ``.gitignore``). The path is part of jax's cache key, so it must not
  move between runs: never a temp name, a pid or a time.
* unset, on any other backend: no persistent cache. XLA:CPU entries
  embed the build host's feature set and can crash when loaded on a
  different one (tests/conftest.py).

Whoever placed the directory, jax's two "worth caching" floors are
lowered to cache every program, unless the operator set them through
jax's own ``JAX_PERSISTENT_CACHE_MIN_*`` variables.

Serving AOT predict artifacts (serving/aot.py) live in ``<cache>/aot``
under the same rule, so the npz bundle and the executables it replays
share one lifecycle.
"""

from __future__ import annotations

import os
from typing import Optional

from .device import on_tpu
from .log import log_info

TPU_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache_tpu")

# process-global latch: jax.config.update is process-global, so the
# first enable wins and later calls (every booster construction) only
# report it
_STATE = {"enabled_dir": None}


def resolve_cache_dir() -> Optional[str]:
    """The persistent cache directory this process uses, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    return TPU_CACHE_DIR if on_tpu() else None


def artifact_dir() -> Optional[str]:
    """``<cache>/aot`` for serving AOT predict artifacts, created on
    demand; None when this process keeps no persistent cache (an
    artifact without the executables it replays buys nothing) or
    cannot write there (an installed package's read-only tree)."""
    base = resolve_cache_dir()
    if base is None:
        return None
    path = os.path.join(base, "aot")
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return None
    return path


def maybe_enable_compile_cache() -> Optional[str]:
    """Point jax's persistent compilation cache at the resolved
    directory (module docstring). Returns the active directory, or
    None when there is none. Idempotent; every training and serving
    entry point calls it before its first compile."""
    if _STATE["enabled_dir"] is not None:
        return _STATE["enabled_dir"]
    path = resolve_cache_dir()
    if path is None:
        return None
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or quick to compile: a smoke
    # run is ~370 programs averaging a quarter of a second each, all
    # under jax's default 1 s floor. An operator's own floors stand.
    for flag, everything in (
            ("jax_persistent_cache_min_compile_time_secs", 0.0),
            ("jax_persistent_cache_min_entry_size_bytes", -1)):
        if not os.environ.get(flag.upper(), "").strip():
            jax.config.update(flag, everything)
    log_info(f"persistent compilation cache at {path}")
    _STATE["enabled_dir"] = path
    return path
