"""What the benchmark observes of JAX itself: the device, compilations
and device memory.
"""

from __future__ import annotations

from typing import Any, Dict, List


class NoAccelerator(Exception):
    """JAX found no TPU, fewer chips than the cell asks for, or a chip
    the peak table does not hold."""


def device_report(chips: int, allow_cpu: bool = False) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them. Raises
    ``NoAccelerator`` unless the platform is a TPU with at least
    ``chips`` chips of a kind in the peak table (``allow_cpu``: the
    tests' CPU rehearsal)."""
    import jax

    from .peaks import PEAKS
    devs = jax.devices()
    info = {"platform": str(devs[0].platform),
            "kind": str(devs[0].device_kind), "count": len(devs)}
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, JAX reports {info}")
    if allow_cpu:
        return info
    if info["platform"] != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX reports {info}")
    if info["kind"] not in PEAKS:
        raise NoAccelerator(
            f"device_kind {info['kind']!r} is not in benchmarks/peaks.py "
            f"({sorted(PEAKS)})")
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices;
    0 where the backend reports nothing (the CPU rehearsal)."""
    import jax
    peaks: List[int] = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileWatch:
    """Counts JAX's own compile and persistent-cache events from the
    moment it is made (``jax.monitoring`` has no unregister, so make
    one per process). A program loaded from the persistent cache still
    counts as a compile event here: the window must see none of either
    kind."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("/backend_compile_duration"):
            self.compiles += 1
            self.compile_s += float(duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
