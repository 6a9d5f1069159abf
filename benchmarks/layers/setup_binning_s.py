"""Layer: data. Seconds of set-up under the program's
``lgbm.data.construct`` spans (one a table built or loaded: the row
sample and ``find_bin``, values to bin bytes, the bundle plan and the
bundling), less their ``lgbm.data.load_binary`` children and the
compiles inside; about 0 where the table came from the data cache."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "binning")
