"""Reader ``ledger_count``: traces the program's own compile ledger
(``obs/profile.py::CompileLedger``, the process-wide instance) counted at the
``sites`` in the whole run, set-up included: how often the Python body of
each site's program ran, which is once per compilation the site asked for.
A cell launches one of the listed sites; the others count 0. An earlier line
gives every site of the run with its signatures.

args: ``sites`` (list of ledger sites).
"""

from __future__ import annotations

from ..harness import pkg


def read(ctx, *, sites):
    ledger = pkg("obs.profile").default_ledger()
    known = ledger.report()["sites"]
    if not any(site in known for site in sites):
        return None
    ctx.say(
        "ledger_count: "
        + "; ".join(f"{site} {known[site]['compiles']} trace(s) {ledger.compile_counts(site)}" for site in known)
    )
    return float(sum(known[site]["compiles"] for site in sites if site in known))
