"""Published peaks of the chips the benchmark runs on, and the bytes
the algorithm needs.

Kept with the benchmark so that the yardstick does not move with the
program (copied from ``lightgbm_tpu/utils/roofline.py``, which a later
PR may delete or change). A device that is not in the table is an
error, never a default.
"""

from __future__ import annotations

from typing import Dict

# jax.devices()[0].device_kind -> one chip's published peaks. Source:
# Google Cloud documentation, "TPU v5e" system architecture page: one
# chip has 197 TFLOP/s in bf16, 393 TOP/s in int8 and 16 GB of HBM2e at
# 819 GB/s. A v5e chip reports the kind "TPU v5 lite" (read off the
# device, PR 21). Add a row only with a kind read off a device and a
# cited peak.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "mxu_tflops": 197.0},
}

ROW_ID_BYTES = 4    # row ids ride the training matrix as 4 u8 columns
GH_BYTES = 12       # gradient, hessian and count, f32 each


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; "
            f"benchmarks/peaks.py holds {sorted(PEAKS)}")
    return PEAKS[device_kind]


def hist_bytes_per_row(num_features: int) -> int:
    """A histogram pass reads each row's bin bytes and its gradient,
    hessian and count once."""
    return num_features + GH_BYTES


def part_bytes_per_row(num_features: int) -> int:
    """A partition pass reads the row and writes it back."""
    return 2 * (num_features + GH_BYTES + ROW_ID_BYTES)


def iter_bytes_per_row(num_features: int) -> int:
    """Lower bound for one boosting iteration: one histogram pass and
    one partition pass over the whole matrix. Leaf-wise growth touches
    a row once per level, so the real traffic is several times this."""
    return hist_bytes_per_row(num_features) \
        + part_bytes_per_row(num_features)


def tree_bytes(internal_counts, smaller_child_counts,
               num_features: int) -> float:
    """Bytes the algorithm needs to grow one tree: each split partitions
    its parent's rows and builds the histogram of the smaller child (the
    sibling comes by subtraction); the root histogram reads every row
    once."""
    split_rows = float(sum(internal_counts))
    hist_rows = float(sum(smaller_child_counts))
    root_rows = float(internal_counts[0]) if len(internal_counts) else 0.0
    return split_rows * part_bytes_per_row(num_features) \
        + (hist_rows + root_rows) * hist_bytes_per_row(num_features)
