"""Layer: data. Physical byte columns of the training matrix over the
table's logical features, as the dataset's counters report them
(``data.bundle_columns`` / ``data.bundle_features``): what the bundling
came to. 1 for an unbundled table; ``None`` where the kind hands no
logical width."""


def read(facts):
    logical = facts.get("logical_features")
    if not logical:
        return None
    return facts["features"] / logical
