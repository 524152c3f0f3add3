"""Device time by the program's ``jax.named_scope``s, from a profiler trace
and the compiled programs' own text.

An ``XLA Ops`` event of a TPU trace stands for one HLO instruction and is
named by its text (``%fusion.3751 = ...``). On this installation (v5e, jax
0.9.0; looked at in PR 28) the event carries NOTHING of the instruction's
``op_name`` path: its only stats are ``device_duration_ps``,
``device_offset_ps`` and ``Time Scale Multiplier``. The path
(``jit(engine_train_step)/.../encoder/layer_1/kda/kda/chunks/while/body/...``:
the module path flax gives plus the program's own scopes) is in the compiled
program's text, as each instruction's ``metadata={op_name="..."}``, under the
same instruction name. A driver leaves the texts of the programs it timed
under ``hlo_texts`` (``jitted.lower(...).compile().as_text()`` after the
window: the executable is the one that ran, nothing is traced or compiled
again), and an event is matched by its owning program (the ``XLA Modules``
event that covers it) and its instruction name.

A ``while`` instruction's event covers its body's events, and a fusion
carries the path of its root, so a scope's time is the UNION of the intervals
of the events under it, per chip.
"""

from __future__ import annotations

import re

import numpy as np

from . import xplane

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)
_EVENT = re.compile(r"^%?([\w.\-]+) = ")


def paths_by_program(texts) -> dict[str, dict[str, str]]:
    """``{program: {instruction: op_name path}}`` from compiled programs'
    texts."""
    out: dict[str, dict[str, str]] = {}
    for text in texts:
        m = _MODULE.match(text)
        if m:
            out[m.group(1)] = dict(_INSTRUCTION.findall(text))
    return out


def of(ctx):
    """Per chip used, the window's events with the path each runs under:
    ``{"chips": {chip: (paths, start, dur)}, "matched": share of the events'
    time that found a path}``, made once a run; None without a device trace
    or without the programs' texts."""
    data = ctx.rec.data
    if "scope_ops" not in data:
        ready = not ctx.rehearsal and "xplane" in data and data.get("hlo_texts")
        data["scope_ops"] = load(ctx) if ready else None
    return data["scope_ops"]


def load(ctx) -> dict:
    reduced = ctx.rec.data["xplane"]
    lo, hi = reduced["window"]
    programs = paths_by_program(ctx.rec.data["hlo_texts"])
    chips, total, found = {}, 0.0, 0.0
    for c in reduced["chips"]:
        tables = reduced["trace"]["chips"][c]
        names, start, dur = tables["ops"]
        mnames, mstart, mdur = tables.get("modules", ([], np.zeros(0), np.zeros(0)))
        inside = np.flatnonzero((start >= lo) & (start < hi))
        owner = np.searchsorted(mstart, start, side="right") - 1
        paths = []
        for i in inside:
            o = int(owner[i])
            program = xplane.short_module(mnames[o]) if 0 <= o < len(mnames) and start[i] < mstart[o] + mdur[o] else ""
            m = _EVENT.match(names[i])
            path = programs.get(program, {}).get(m.group(1), "") if m else ""
            paths.append(path)
            total += dur[i]
            found += dur[i] if path else 0.0
        chips[c] = (paths, start[inside], dur[inside])
    share = found / total if total else 0.0
    ctx.say(
        f"scope_ops: the texts of {sorted(programs)} name {sum(len(p) for p in programs.values())} instructions with "
        f"a path; {100 * share:.1f}% of the window's operation time (nested operations counted each) found its path"
    )
    return {"chips": chips, "matched": share, "window": (lo, hi)}


def time_under(table: dict, scope: str):
    """Nanoseconds of device time, summed over the chips used, in which an
    operation under ``scope`` (a path fragment between slashes) ran; None
    where no event of the trace found a path at all."""
    if not table["matched"]:
        return None
    rx = re.compile(r"/" + re.escape(scope) + r"(?=/)")
    lo, hi = table["window"]
    total = 0.0
    for paths, start, dur in table["chips"].values():
        verdict: dict[str, bool] = {}
        keep = np.fromiter((verdict.setdefault(p, bool(rx.search(p))) for p in paths), bool, len(paths))
        total += xplane.union_ns(start[keep], dur[keep], lo, hi)
    return total
