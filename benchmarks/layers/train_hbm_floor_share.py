"""Layer: end_to_end, named for what it is. The training rate times the
lower-bound bytes a row-iteration needs (one histogram and one
partition pass), over the HBM peak, percent. A utilisation of the whole
run, not a kernel's roofline share. From the untraced steps."""

from ..peaks import iter_bytes_per_row, peaks_for


def read(facts):
    rate = facts.get("rate_untraced_mrow_iters_per_s")
    if rate is None or facts.get("device_kind") is None:
        return None
    try:
        peak = peaks_for(facts["device_kind"])["hbm_gbps"] * 1e9
    except KeyError:
        return None
    return 100.0 * rate * 1e6 * iter_bytes_per_row(facts["features"]) \
        / (peak * facts["chips"])
