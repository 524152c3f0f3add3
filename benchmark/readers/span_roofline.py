"""Reader ``span_roofline``: how close the device's own busy time inside a
benchmark span comes to the roofline, in percent: the least time the chips
could take for the work the span required (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s: the counts are the configuration's
family's, benchmark/families/<family>.py; the peaks benchmark/peaks.json) over
the device busy time inside that span of the traced window (xplane). Which
roof binds is printed on an earlier line.

The work is training steps: ``counter`` rows a span, and the span's ``steps``
attribute counts the optimizer steps, each of which moves the state once. The
family gets the sum of every other counter a driver put on the spans too.

args: ``span``, ``counter``.
"""

from __future__ import annotations

from .. import flops
from ..reduce import xplane


def read(ctx, *, span="fit", counter="rows"):
    reduced = ctx.rec.data.get("xplane")
    if reduced is None:
        return None
    busy = xplane.busy_inside(reduced, span)
    counters = ctx.rec.counters(span, phase="traced")
    rows = counters.pop(counter, 0.0)
    if busy <= 0 or rows <= 0:
        return None
    peaks = ctx.peaks()
    chips = len(reduced["chips"])
    steps = counters.get("steps", 0.0)
    need_f = ctx.family.train_step_flops(ctx.model, rows, **counters)
    need_b = ctx.family.train_step_bytes(ctx.model, **counters)
    floor, roof = flops.roofline_floor_s(need_f, need_b, peaks, chips)
    ctx.say(
        f"roofline/{span}: {rows:.0f} rows in {steps:.0f} step(s) need "
        f"{need_f / 1e12:.3f} TFLOP and at least {need_b / 1e9:.3f} GB: floor "
        f"{floor:.4f} s on {chips} chip(s), set by the {roof} roof; device busy "
        f"{busy:.4f} s"
    )
    return 100.0 * floor / busy
