"""The training matrix's row, for the layers on both sides of it:
``data/bundling.py`` decides which columns a row holds, ``ops/`` builds
and streams the rows. No JAX here: the host-side planner reads it."""

GH_COLS = 13       # payload bytes after the feature columns
ROW_TILE = 128     # a row is padded to whole tiles of this many bytes


def matrix_cols(num_features: int) -> int:
    """Bytes of a matrix row that holds ``num_features`` byte columns."""
    return -(-(num_features + GH_COLS) // ROW_TILE) * ROW_TILE
