"""Reader ``bench_span``: a statistic of the benchmark's own host-clock spans
over the window's untraced part.

args: ``name`` (the span), ``stat``:
  ``median`` | ``mean`` | ``sum``  seconds of the span, times ``scale``;
  ``share``   100 x seconds inside ``name`` over seconds inside ``of``;
  ``rate``    total of the attribute ``counter`` over total seconds;
  ``per``     total seconds over total of ``counter``, times ``scale``.
``phase`` picks the spans of another phase of the run (``setup``).
"""

from __future__ import annotations

import numpy as np

from ..harness import total_rate


def read(ctx, *, name, stat, of=None, counter=None, scale=1.0, phase="window"):
    secs = ctx.rec.seconds(name, phase)
    if len(secs) == 0:
        return None
    if stat in ("median", "mean", "sum"):
        return float(getattr(np, stat)(secs)) * scale
    if stat == "share":
        whole = ctx.rec.seconds(of, phase).sum()
        return 100.0 * float(secs.sum() / whole) if whole else None
    count = ctx.rec.total(name, counter, phase)
    if stat == "rate":
        return total_rate(ctx.rec, name, counter, phase) * scale
    if stat == "per":
        return secs.sum() / count * scale if count else None
    raise ValueError(f"bench_span: unknown stat {stat!r}")
