"""Reader ``ledger_seconds``: wall seconds the program's own compile ledger
(``obs/profile.py::CompileLedger``, the process-wide instance) attributes to
traces at the ``sites`` in the whole run, set-up included: the length of
every call of a site's program during which its Python body ran, which is
trace + lower + backend compile or cache retrieval (and the launch itself).
The twin of ``ledger_count``: that one says how often a site traced, this
one what the traces cost. A cell launches one of the listed sites; the
others add 0. An earlier line gives every site of the run.

args: ``sites`` (list of ledger sites).
"""

from __future__ import annotations

from ..harness import pkg


def read(ctx, *, sites):
    report = pkg("obs.profile").default_ledger().report()["sites"]
    known = {site: rec for site, rec in report.items() if "trace_s" in rec}
    if not any(site in known for site in sites):
        return None
    ctx.say(
        "ledger_seconds: "
        + "; ".join(f"{site} {rec['trace_s']:.2f} s in {rec['compiles']} trace(s)" for site, rec in known.items())
    )
    return float(sum(known[site]["trace_s"] for site in sites if site in known))
