"""Hardware peak table + roofline normalization for the benchmarks.

A raw "Mrow/s" number says nothing about how much headroom remains;
normalizing to the device's HBM bandwidth (the binding resource for the
u8-matrix streaming kernels) and listing the MXU peak for context turns
each measurement into a fraction of physically-possible. The table is
keyed by the exact ``device_kind`` string JAX reports and holds only
devices this repo has run on; a device that is not in it is an error,
never a default or an "n/a".

Byte-cost model (documented here, used by bench.py):

* ``histogram_segment`` streams each row's ``F`` bin bytes plus the 12
  gh payload bytes (g, h, count f32) once per call:
  ``HIST_BYTES_PER_ROW(F) = F + 12``.
* ``partition_segment`` reads AND rewrites the row (matrix + ws
  scratch): ``PART_BYTES_PER_ROW(F) = 2 * (F + 12 + ROW_ID_BYTES)``.
* one boosting iteration's LOWER BOUND is one histogram pass over the
  full matrix plus ~one partition pass (leaf-wise splitting touches
  each row O(depth) times; the lower bound is what the published
  baseline's row-iters/s metric implies): ``ITER_BYTES_PER_ROW(F)``.

Fractions computed against these models are therefore lower bounds on
utilization — honest in the direction that cannot overclaim.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# jax.devices()[0].device_kind -> published per-chip peaks. Source:
# Google Cloud documentation, "TPU v5e" system architecture page (one
# chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
# A TPU v5e chip reports the kind "TPU v5 lite" (chip_smoke.py, jax
# 0.9.0 / libtpu 0.0.34). Add a row only with a kind string read off
# the device and a cited peak.
_DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "mxu_tflops": 197.0},
}

ROW_ID_BYTES = 4  # row ids ride the matrix as 4 u8 columns


def hist_bytes_per_row(num_features: int) -> int:
    return num_features + 12


def part_bytes_per_row(num_features: int) -> int:
    return 2 * (num_features + 12 + ROW_ID_BYTES)


def iter_bytes_per_row(num_features: int) -> int:
    """Lower-bound HBM traffic per row-iteration of boosting (one
    histogram pass + one partition pass of the training matrix)."""
    return hist_bytes_per_row(num_features) \
        + part_bytes_per_row(num_features)


def device_peaks(device=None) -> Dict[str, Any]:
    """Peak table row for the current (or given) jax device:
    ``{"device_kind", "backend", "hbm_gbps", "mxu_tflops"}``. Raises
    ``LightGBMError`` for a ``device_kind`` the table does not hold
    (every CPU host included): a roofline share against a guessed
    peak is not a measurement."""
    import jax

    from .log import LightGBMError
    d = device if device is not None else jax.devices()[0]
    kind = str(d.device_kind)
    if kind not in _DEVICE_PEAKS:
        raise LightGBMError(
            f"no published peaks for device_kind={kind!r} (platform "
            f"{d.platform!r}); utils/roofline.py holds "
            f"{sorted(_DEVICE_PEAKS)}")
    return {"device_kind": kind, "backend": str(d.platform),
            **_DEVICE_PEAKS[kind]}


def normalize(rows_per_s: float, bytes_per_row: float,
              peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Roofline fields for one measured streaming rate against the
    device's HBM peak (``peaks`` defaults to :func:`device_peaks`)."""
    if peaks is None:
        peaks = device_peaks()
    achieved = rows_per_s * bytes_per_row / 1e9
    return {
        "bytes_per_row": bytes_per_row,
        "achieved_gbps": round(achieved, 3),
        "hbm_peak_gbps": peaks["hbm_gbps"],
        "hbm_frac": round(achieved / peaks["hbm_gbps"], 4),
    }


def bench_roofline(rows_per_s: float, num_features: int) -> Dict[str, Any]:
    """The bench.py JSON block: device identity + peaks + the
    iteration-lower-bound normalization of the headline throughput."""
    peaks = device_peaks()
    out = dict(peaks)
    out.update(normalize(rows_per_s, iter_bytes_per_row(num_features),
                         peaks))
    out.pop("hbm_gbps")  # normalize() reports hbm_peak_gbps
    return out
