"""Layer: data. Seconds of set-up under the program's two host spans of
a sparse table's construction, ``lgbm.data.bundle_plan`` (the bundle
plan from the columns' non-default rows) and ``lgbm.data.extract`` (the
stored entries binned and written into the bundled byte matrix), on the
host's clock. 0 where the table came from the data cache, since an
earlier run bundled it; ``None`` on a program that has no such spans."""


def read(facts):
    return facts.get("bundle_s")
