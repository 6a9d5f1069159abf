"""Layer: compile. Seconds of set-up under the backend ``compile``
records that the persistent cache did not serve (``cache`` ``miss`` or
``none``): what a warm cache would have saved. A part of
``setup_compile_s``."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "compile_miss")
