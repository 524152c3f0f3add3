"""Reader ``program_span_idle``: the device's idle milliseconds per traced
round while the program's driving thread was inside its phase ``name``
(``inside: true``) or anywhere else in the window (``inside: false``). Idle
is reduce/xplane.py's: gaps of 20 us and more between the operations of the
chip that idled most, each cut at the annotations' edges
(reduce/program_spans.py). A phase is an annotation with no ``/`` in its name
(``fit``, ``eval``, ``agg``, ``reset``, ``round_anchor``); phases do not nest.
Two earlier lines give the whole split: by phase, and by innermost
annotation (``fit/unstack``, ``dispatch/<site>`` ...).

args: ``name``, ``inside``.
"""

from __future__ import annotations

from ..reduce import program_spans


def _split(ctx, table: dict, reduced: dict) -> dict:
    """Both splits, made and printed once a run."""
    if "idle" not in table:
        line = program_spans.main_line(table["spans"])
        spans = [s for s in table["spans"] if s[3] == line]
        table["idle"] = {
            "phase": program_spans.idle_by_annotation(reduced, [s for s in spans if "/" not in s[0]]),
            "innermost": program_spans.idle_by_annotation(reduced, spans),
            "rounds": program_spans.rounds_in(reduced),
        }
        for key in ("phase", "innermost"):
            ctx.say(
                f"program_span_idle by {key} ({table['idle']['rounds']} round(s), ms): "
                + "; ".join(
                    f"{k} {v / 1e6:.3f}"
                    for k, v in sorted(table["idle"][key].items(), key=lambda kv: -kv[1])
                )
            )
    return table["idle"]


def read(ctx, *, name, inside=True):
    table = program_spans.of(ctx)
    reduced = ctx.rec.data.get("xplane")
    if not table or not table["spans"] or reduced is None:
        return None
    idle = _split(ctx, table, reduced)
    under = idle["phase"].get(name, 0.0)
    ns = under if inside else sum(idle["phase"].values()) - under
    return ns / 1e6 / idle["rounds"]
