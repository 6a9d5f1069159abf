"""Layer: device, in serving cells (a per-layer metric names the one
end-to-end metric it moves, so the serving cells have their own)."""

from . import device_idle_share


def read(facts):
    return device_idle_share.read(facts)
