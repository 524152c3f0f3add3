"""Reader ``route_load``: how unevenly the window's tokens were routed over
the experts this chip holds: the busiest held expert's token-slots over the
mean, from the program's routing counters summed over the window's untraced
rounds (a driver leaves them under ``route_slots``). 1 is a perfect balance.
"""

from __future__ import annotations

import numpy as np


def read(ctx):
    slots = ctx.rec.data.get("route_slots")
    if slots is None:
        return None
    slots = np.asarray(slots, np.float64)
    if slots.size == 0 or slots.mean() <= 0:
        return None
    return float(slots.max() / slots.mean())
