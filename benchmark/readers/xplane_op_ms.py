"""Reader ``xplane_op_ms``: device duration in milliseconds of the events on a
line of the trace whose name matches ``pattern``: the ``median`` (or ``mean``,
``sum``) over the traced window. With ``line: modules`` this is one program's
time per dispatch; with ``line: ops`` one kernel's.

args: ``pattern`` (regex on the event name), ``line`` (``modules`` | ``ops`` |
``async``), ``stat``.
"""

from __future__ import annotations

import numpy as np

from ..reduce import xplane


def read(ctx, *, pattern, line="modules", stat="median"):
    reduced = ctx.rec.data.get("xplane")
    if reduced is None:
        return None
    ns = xplane.select(reduced, line, pattern)
    if len(ns) == 0:
        return None
    return float(getattr(np, stat)(ns)) / 1e6
