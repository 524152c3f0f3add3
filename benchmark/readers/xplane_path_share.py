"""Reader ``xplane_path_share``: device time of the ``XLA Ops`` events whose
``op_name`` path (reduce/scope_ops.py) matches the regular expression
``holds`` and not ``lacks``, as a percentage of device busy time in the traced
window (per chip, averaged): what ``xplane_scope_share`` reads for a scope,
for what no single scope names. JAX writes the PASS into every path
(``jvp(...)`` a forward, ``transpose(jvp(...))`` a backward, with
``rematted_computation`` a recomputed forward) and the program it belongs to
at its head (``jit(engine_train_step)/``), so a backward pass's share, or the
share of a program's time that lies under none of the program's named scopes,
is such a pair. The time is the union of the events' intervals, as a scope's
is; an event that found no path counts nowhere. Where the driver left no
program text, or no event found its path, the reader returns nothing.

args: ``holds``, ``lacks`` (regular expressions searched in the path;
``lacks`` may be left out).
"""

from __future__ import annotations

import re

import numpy as np

from ..reduce import scope_ops, xplane


def read(ctx, *, holds, lacks=None):
    table = scope_ops.of(ctx)
    reduced = ctx.rec.data.get("xplane")
    if table is None or reduced is None or reduced["busy_s"] <= 0 or not table["matched"]:
        return None
    yes, no = re.compile(holds), re.compile(lacks) if lacks else None
    lo, hi = table["window"]
    ns = 0.0
    for paths, start, dur in table["chips"].values():
        verdict: dict[str, bool] = {}
        keep = np.fromiter(
            (verdict.setdefault(p, bool(p and yes.search(p) and not (no and no.search(p)))) for p in paths),
            bool, len(paths),
        )
        ns += xplane.union_ns(start[keep], dur[keep], lo, hi)
    return 100.0 * (ns / 1e9 / len(reduced["chips"])) / reduced["busy_s"]
