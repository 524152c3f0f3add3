"""Reader ``mfu``: model FLOP/s utilisation of the training calls, in percent:
the FLOPs the forward and backward passes require per row (benchmark/flops.py,
no recompute) times rows per second inside the ``span`` calls (total over
total), over chips
times the chip's published bf16 peak (benchmark/peaks.json).

args: ``span`` (default ``fit``), ``counter`` (default ``rows``).
"""

from __future__ import annotations

from .. import flops
from ..harness import total_rate


def read(ctx, *, span="fit", counter="rows"):
    if not ctx.rec.select(span):
        return None
    peaks = ctx.peaks()
    chips = ctx.rec.data.get("chips", ctx.chips)
    need = flops.train_step_flops(ctx.model, 1) * total_rate(ctx.rec, span, counter)
    return 100.0 * need / (chips * peaks["bf16_flops_per_s"])
