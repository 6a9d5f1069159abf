"""Published inter-chip interconnect (ICI) bandwidth of the chips the
benchmark runs on, and the bytes the data-parallel algorithm must move.

Kept with the benchmark, beside ``peaks.py``, so that the yardstick
does not move with the program. A device that is not in the table is
an error, never a default.
"""

from __future__ import annotations

from typing import Dict

# jax.devices()[0].device_kind -> one chip's inter-chip interconnect
# bandwidth, GB/s. Source: Google Cloud documentation, "TPU v5e" system
# architecture page: 1,600 Gbps of interchip interconnect (ICI) a chip,
# 200 GB/s. A v5e chip reports the kind "TPU v5 lite".
ICI_GBPS: Dict[str, float] = {"TPU v5 lite": 200.0}

F32 = 4
# one chip's best split of a child as the winner exchange needs it: its
# gain, feature, threshold and default direction, the left child's
# gradient, hessian and count sums, both outputs and a categorical flag
# (a numeric split needs no category bitset), four bytes each
WINNER_BYTES = 10 * F32


def ici_gbps(device_kind: str) -> float:
    if device_kind not in ICI_GBPS:
        raise KeyError(
            f"no published ICI bandwidth for device_kind={device_kind!r}; "
            f"benchmarks/ici.py holds {sorted(ICI_GBPS)}")
    return ICI_GBPS[device_kind]


def histogram_bytes(features: int, bins: int) -> int:
    """One leaf's ``[features, bins, 3]`` float32 histogram: gradient,
    hessian and count a bin."""
    return features * bins * 3 * F32


def split_bytes(features: int, bins: int, chips: int) -> float:
    """What one chip must send a split in a ring over ``chips``: the
    reduce-scatter of the smaller child's histogram passes on (d-1)/d
    of it, and each of the two children's winners goes to the d-1
    others."""
    d = chips
    return (d - 1) / d * histogram_bytes(features, bins) \
        + 2 * (d - 1) * WINNER_BYTES

