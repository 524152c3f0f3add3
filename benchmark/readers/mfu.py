"""Reader ``mfu``: model FLOP/s utilisation of the training calls, in percent:
the FLOPs the forward and backward passes require for the rows through the
``span`` calls of the window (the configuration's family,
benchmark/families/<family>.py, no recompute) over the seconds inside those
calls (total over total), over chips times the chip's published bf16 peak
(benchmark/peaks.json). The family also gets the sum of every other counter a
driver put on those spans.

args: ``span`` (default ``fit``), ``counter`` (default ``rows``).
"""

from __future__ import annotations


def read(ctx, *, span="fit", counter="rows"):
    counters = ctx.rec.counters(span)
    rows = counters.pop(counter, 0.0)
    if rows <= 0:
        return None
    peaks = ctx.peaks()
    chips = ctx.rec.data.get("chips", ctx.chips)
    need = ctx.family.train_step_flops(ctx.model, rows, **counters)
    return 100.0 * need / float(ctx.rec.seconds(span).sum()) / (chips * peaks["bf16_flops_per_s"])
