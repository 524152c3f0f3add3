"""Reader ``reply_field``: a statistic of one field of the scoring replies the
load generator collected in the window (``queue_ms``, ``batch_size``,
``bucket`` from the server's reply; ``late_ms`` and ``latency_ms`` from the
generator's own clock).

args: ``field``, ``stat`` (``p50`` | ``p95`` | ``p99`` | ``mean`` | ``max``).
"""

from __future__ import annotations

import numpy as np


def read(ctx, *, field, stat):
    replies = ctx.rec.data.get("replies")
    if replies is None or field not in replies or len(replies[field]) == 0:
        return None
    x = np.asarray(replies[field], np.float64)
    if stat.startswith("p"):
        return float(np.percentile(x, float(stat[1:])))
    return float(getattr(np, stat)(x))
