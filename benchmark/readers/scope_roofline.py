"""Reader ``scope_roofline``: how close the device time under one
``jax.named_scope`` of the program comes to the roofline, in percent: the
least time the chips could take for the work the traced spans required inside
that scope (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s;
the counts are the configuration's family's ``scope_work``, forward and
backward, from the counters a driver put on the spans; the peaks
benchmark/peaks.json) over the device time of the ``XLA Ops`` events under the
scope (reduce/scope_ops.py). A recomputed forward pass is in the time and not
in the work. Which roof binds is printed on an earlier line.

args: ``scope``, ``span`` (default ``fit``).
"""

from __future__ import annotations

from .. import flops
from ..reduce import scope_ops


def read(ctx, *, scope, span="fit"):
    table = scope_ops.of(ctx)
    reduced = ctx.rec.data.get("xplane")
    work = getattr(ctx.family, "scope_work", None)
    if table is None or reduced is None or work is None:
        return None
    ns = scope_ops.time_under(table, scope)
    counters = ctx.rec.counters(span, phase="traced")
    if not ns or not counters.get("tokens"):
        return None
    need = work(ctx.model, scope, **counters)
    if need is None:
        return None
    chips = len(reduced["chips"])
    floor, roof = flops.roofline_floor_s(need[0], need[1], ctx.peaks(), chips)
    busy = ns / 1e9 / chips
    ctx.say(
        f"roofline/{scope}: {counters['tokens']:.0f} tokens in the traced {span} need "
        f"{need[0] / 1e12:.3f} TFLOP and at least {need[1] / 1e9:.3f} GB under the scope: floor "
        f"{floor:.4f} s, set by the {roof} roof; device time under the scope {busy:.4f} s"
    )
    return 100.0 * floor / busy
