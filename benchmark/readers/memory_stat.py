"""Reader ``memory_stat``: the peak bytes held on the fullest chip through
set-up and window (live arrays + program scratch, harness.memory_peak_bytes),
times ``scale``."""

from __future__ import annotations


def read(ctx, *, scale=1.0):
    peak = ctx.memory[0]
    return peak * scale if peak else None
