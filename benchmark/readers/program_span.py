"""Reader ``program_span``: host time inside the program's own annotations
(``fedtpu:<name>``, reduce/program_spans.py) in the traced window.

args: ``pattern`` (regex, matched in full against the annotation's name),
``stat``:
  ``median``  of the lengths of the matching events, times ``scale``;
  ``per``     total length of the matching events over the number of events
              named ``per_name`` (``fit``: per fit), times ``scale``.
Lengths are nanoseconds: ``scale`` 0.001 gives us, 1e-06 ms. Each matching
name's count, total, least, 10th percentile, median and largest go to an
earlier line, so that a pattern over several names prints them apart, and a
launch that waited for a free slot on the device (the median of
``dispatch/...`` where the host runs ahead) can be told from one that did not
(the 10th percentile).
"""

from __future__ import annotations

import re

import numpy as np

from ..reduce import program_spans


def read(ctx, *, pattern, stat="median", per_name=None, scale=1.0):
    table = program_spans.of(ctx)
    if not table:
        return None
    found = program_spans.lengths(table, re.compile(pattern))
    if not found:
        return None
    if not ctx.rehearsal:
        ctx.say(
            f"program_span/{pattern}: "
            + "; ".join(
                f"{name} x{len(ns)} total {ns.sum() / 1e6:.3f} ms, min {ns.min() / 1e3:.1f} "
                f"p10 {np.percentile(ns, 10) / 1e3:.1f} median {np.median(ns) / 1e3:.1f} max {ns.max() / 1e3:.1f} us"
                for name, ns in sorted(found.items())
            )
        )
    every = np.concatenate(list(found.values()))
    if stat == "median":
        return float(np.median(every)) * scale
    if stat == "per":
        n = sum(len(v) for v in program_spans.lengths(table, re.compile(re.escape(per_name))).values())
        return float(every.sum()) / n * scale if n else None
    raise ValueError(f"program_span: unknown stat {stat!r}")
