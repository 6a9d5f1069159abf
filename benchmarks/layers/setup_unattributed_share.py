"""Layer: device. Percent of ``setup_s`` that no span or compile record
of the program covers: imports and runtime start-up, the benchmark's
generator on a data-cache miss, its warm-up AUC."""

from .. import setup_spans


def read(facts):
    return setup_spans.unattributed_share(facts)
