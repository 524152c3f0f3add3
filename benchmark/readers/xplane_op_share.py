"""Reader ``xplane_op_share``: device time in the operations whose HLO opcode
matches ``pattern``, as a percentage of device busy time in the traced window
(per chip, averaged). A new kernel's share is a data file naming its pattern.

args: ``pattern`` (regex), ``by`` (``opcode`` | ``name``), ``line``
(``ops`` | ``async``).
"""

from __future__ import annotations

from ..reduce import xplane


def read(ctx, *, pattern, by="opcode", line="ops"):
    reduced = ctx.rec.data.get("xplane")
    if reduced is None or reduced["busy_s"] <= 0:
        return None
    ns = xplane.select(reduced, line, pattern, by=by).sum()
    return 100.0 * (ns / 1e9 / len(reduced["chips"])) / reduced["busy_s"]
