"""Layer: data. Seconds to construct the binned ``Dataset`` (or to load
it from the benchmark's cache), on the host's clock, in set-up."""


def read(facts):
    return facts.get("dataset_construct_s")
