"""Reader ``registry_ratio``: one counter family of the program's own metrics
registry (``obs/metrics.py::default_registry``, the process-wide instance that
a deployment scrapes) over another, summed over their labels, in percent, over
the whole run, set-up included: as ``ledger_count`` reads the program's
compile ledger. An earlier line gives both totals. Nothing where the program
published neither family (another model; a program from before the counter).

args: ``over``, ``under`` (counter names).
"""

from __future__ import annotations

from ..harness import pkg


def read(ctx, *, over, under):
    families = pkg("obs.metrics").default_registry().snapshot()["families"]
    if over not in families or under not in families:
        return None
    top, bottom = (sum(s["value"] for s in families[name]["samples"]) for name in (over, under))
    if bottom <= 0:
        return None
    ctx.say(f"registry_ratio: {over} {top:.0f} over {under} {bottom:.0f} in this process (every fit and evaluation)")
    return 100.0 * top / bottom
