"""Reader ``xplane_scope_share``: device time of the ``XLA Ops`` events that
ran under a ``jax.named_scope`` of the program, as a percentage of device busy
time in the traced window (per chip, averaged).

An event's scope is its instruction's ``op_name`` path in the compiled
program's text (reduce/scope_ops.py: the trace's events carry none on this
installation). A fusion carries the path of its root operation, so a fusion
that joins operations of two scopes counts under its root's; a ``while``
covers its body, so the time is the union of the intervals. Where the driver
left no program text, or no event found its path, the reader returns nothing.

args: ``scope`` (a path fragment such as ``kda`` or ``moe/experts``: matched
between slashes).
"""

from __future__ import annotations

from ..reduce import scope_ops


def read(ctx, *, scope):
    table = scope_ops.of(ctx)
    reduced = ctx.rec.data.get("xplane")
    if table is None or reduced is None or reduced["busy_s"] <= 0:
        return None
    ns = scope_ops.time_under(table, scope)
    if ns is None:
        return None
    return 100.0 * (ns / 1e9 / len(reduced["chips"])) / reduced["busy_s"]
