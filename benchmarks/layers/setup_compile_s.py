"""Layer: compile. Seconds of set-up under any of the program's
``compile`` records, their union: jax's tracing and lowering, and
XLA's compile or the persistent cache's load."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "compile")
