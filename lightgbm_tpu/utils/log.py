"""Leveled logging (analog of include/LightGBM/utils/log.h:19-132).

``log_fatal`` raises (the reference's ``Log::Fatal`` throws
std::runtime_error, log.h:99-111); levels map to the ``verbosity`` parameter
the same way (<0 fatal only, 0 +warning, 1 +info, >1 +debug).
"""

from __future__ import annotations

import sys

_LEVEL = 1  # matches default verbosity=1


class LightGBMError(RuntimeError):
    pass


def set_verbosity(level: int) -> None:
    global _LEVEL
    _LEVEL = level


def get_verbosity() -> int:
    return _LEVEL


def _emit(tag: str, msg: str) -> None:
    sys.stdout.write(f"[LightGBM-TPU] [{tag}] {msg}\n")
    sys.stdout.flush()


def log_debug(msg: str) -> None:
    if _LEVEL > 1:
        _emit("Debug", msg)


def log_info(msg: str) -> None:
    if _LEVEL >= 1:
        _emit("Info", msg)


def log_warning(msg: str) -> None:
    if _LEVEL >= 0:
        _emit("Warning", msg)


def log_fatal(msg: str) -> None:
    raise LightGBMError(msg)


def annotate(name: str):
    """Named trace region (jax.profiler.TraceAnnotation) so device
    profiles show grow/predict/eval phases by name; no-op cost when no
    trace is being captured."""
    import jax
    return jax.profiler.TraceAnnotation(name)
