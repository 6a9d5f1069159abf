"""Layer: load_generator (the benchmark's own). 99th percentile of
sent minus due: a starved generator must not read as a fast server."""

from ..stats import percentile


def read(facts):
    values = facts.get("late_ms")
    return percentile(values, 99) if values else None
