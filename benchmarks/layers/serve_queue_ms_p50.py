"""Layer: serving_queue. Median over requests of the engine's own
``queue_ms``: submit to the batch being pulled."""

from ..stats import percentile


def read(facts):
    values = facts.get("queue_ms")
    return percentile(values, 50) if values else None
