"""Reader ``monitoring_event``: a total of JAX's own monitoring events
(harness.CompileMeter) in one phase of the run. The traced rounds are part of
the window.

args: ``event`` (``backend_compile_s`` | ``compiles`` | ``cache_hits`` |
``cache_misses``), ``phase`` (``setup`` | ``window``).
"""

from __future__ import annotations


def read(ctx, *, event, phase="setup"):
    return ctx.meter.get(phase, event)
