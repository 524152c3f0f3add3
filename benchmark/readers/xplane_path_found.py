"""Reader ``xplane_path_found``: the share of the traced window's operation
time whose ``XLA Ops`` event found an ``op_name`` path in the compiled
programs' texts (reduce/scope_ops.py's ``matched``; nested operations counted
each, as that line prints it), in percent: how much of the device's time the
scope readers can see at all. A launch that moves out of the texts' reach (a
program the driver left no text of, an instruction the compiler renamed) shows
here before it shows as a scope's share falling. Nothing where the driver left
no program text.
"""

from __future__ import annotations

from ..reduce import scope_ops


def read(ctx):
    table = scope_ops.of(ctx)
    return None if table is None else 100.0 * table["matched"]
