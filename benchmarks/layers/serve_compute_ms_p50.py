"""Layer: predict. Median over requests of the engine's own
``compute_ms``: binning the batch's rows on the host, the device scan
and the copy back."""

from ..stats import percentile


def read(facts):
    values = facts.get("compute_ms")
    return percentile(values, 50) if values else None
