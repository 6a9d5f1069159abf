"""Layer: data. Seconds of set-up under ``lgbm.data.load_binary`` (the
read and the inflate of a saved table) and ``lgbm.data.save_binary``
(the deflate and the write), less the compiles inside."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "table_io")
