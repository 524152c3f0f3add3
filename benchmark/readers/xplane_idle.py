"""Reader ``xplane_idle``: the device's idle share in percent: 1 minus the
union of the device's operation intervals over the traced window, on the chip
that idled most."""

from __future__ import annotations


def read(ctx):
    reduced = ctx.rec.data.get("xplane")
    if reduced is None or reduced["window_s"] <= 0:
        return None
    worst = reduced["busy_by_chip_s"][reduced["worst_chip"]]
    return 100.0 * (1.0 - worst / reduced["window_s"])
