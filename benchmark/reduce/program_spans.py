"""The program's own annotations in a profiler trace, and the device's idle
time laid against them.

The program marks its phases, batches and launches with
``jax.profiler.TraceAnnotation``s named ``fedtpu:<name>`` (its
``obs/trace.py::annotate``; the vocabulary is ``ANNOTATIONS`` there). They
land on the lines of the ``/host:CPU`` plane, on the clock of the device's
operations and of the benchmark's own ``bench:`` spans (reduce/xplane.py).
A program without them (a parent commit) gives an empty table, and every
reader of it reports nothing.

Read once per run and kept in ``ctx.rec.data["program_spans"]``:
``spans`` as ``(name, t0, t1, line)`` sorted by start, and the traced
``window``. Idle time follows reduce/xplane.py's rule: gaps of
``MIN_GAP_NS`` and more between the operations of the chip that idled most.
"""

from __future__ import annotations

import numpy as np

from . import xplane

PREFIX = "fedtpu:"
KEY = "program_spans"
#: What a gap outside every annotation is filed under.
NO_ANNOTATION = "no annotation"


def load(path: str) -> list[tuple[str, float, float, str]]:
    """Every ``fedtpu:`` event of every line of ``/host:CPU``."""
    import jax

    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    t0 = float(e.start_ns)
                    spans.append((e.name[len(PREFIX):], t0, t0 + float(e.duration_ns), line.name))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans


def of(ctx) -> dict | None:
    """The run's table, or None where nothing was traced."""
    data = ctx.rec.data
    if KEY not in data:
        table = None
        if ctx.trace_path:
            reduced = data.get("xplane")
            # A CPU rehearsal has no device plane and so no reduction; the
            # window is still the benchmark's ``traced`` span.
            window = reduced["window"] if reduced else xplane.window_of(xplane.load(ctx.trace_path))
            table = {"spans": load(ctx.trace_path), "window": window}
            if not ctx.rehearsal:
                # What the profiler and the annotations cost while they are
                # on: the traced rounds' own seconds, beside the untraced
                # rounds' on an earlier line (harness.say_rounds).
                ctx.say(
                    f"program_spans: {len(table['spans'])} annotation(s) of the program in the traced "
                    "window; traced on the host clock: "
                    + "; ".join(
                        f"{part} {' '.join(f'{x:.4f}' for x in ctx.rec.seconds(part, 'traced'))} s"
                        for part in ("round", "fit", "eval", "agg", "reset")
                        if len(ctx.rec.seconds(part, "traced"))
                    )
                )
        data[KEY] = table
    return data[KEY]


def lengths(table: dict, rx) -> dict[str, np.ndarray]:
    """Per annotation name that ``rx`` matches in full: the lengths (ns) of
    its events that start inside the window."""
    lo, hi = table["window"]
    out: dict[str, list[float]] = {}
    for name, t0, t1, _ in table["spans"]:
        if lo <= t0 < hi and rx.fullmatch(name):
            out.setdefault(name, []).append(t1 - t0)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def main_line(spans) -> str | None:
    """The line (thread) that carries most annotations: the one that drives
    the device."""
    counts: dict[str, int] = {}
    for *_, line in spans:
        counts[line] = counts.get(line, 0) + 1
    return max(counts, key=counts.get) if counts else None


def innermost(spans) -> list[tuple[float, float, str]]:
    """One thread's annotations, which nest, flattened to ``(t0, t1, name)``
    pieces that do not overlap: every instant under its innermost
    annotation."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []  # (name, end), outermost first
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, name))
            cursor = max(cursor, end)

    for name, t0, t1, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(t0)
        if stack and t0 > cursor:
            pieces.append((cursor, t0, stack[-1][0]))
        cursor = t0
        stack.append((name, t1))
    close_until(float("inf"))
    return pieces


def device_gaps(reduced: dict) -> np.ndarray:
    """``[n, 2]`` idle intervals (ns) of ``MIN_GAP_NS`` and more inside the
    window on the chip that idled most: reduce/xplane.py's rule, kept as
    intervals."""
    lo, hi = reduced["window"]
    _, start, dur = reduced["trace"]["chips"][reduced["worst_chip"]]["ops"]
    keep = (start + dur > lo) & (start < hi)
    s, e = start[keep], (start + dur)[keep]
    reach = np.maximum.accumulate(e) if len(e) else e
    g0 = np.maximum(np.concatenate(([lo], reach)), lo)
    g1 = np.minimum(np.concatenate((s, [hi])), hi)
    gaps = np.stack([g0, g1], axis=1)
    return gaps[g1 - g0 >= xplane.MIN_GAP_NS]


def idle_by_annotation(reduced: dict, spans) -> dict[str, float]:
    """The device's idle nanoseconds (``device_gaps``) by the innermost of
    ``spans`` (one thread's annotations) that covers them; what none covers
    is filed under ``NO_ANNOTATION``. The values sum to the gaps' total."""
    gaps = device_gaps(reduced)
    g0, glen = gaps[:, 0], gaps[:, 1] - gaps[:, 0]
    out: dict[str, float] = {}
    covered = 0.0
    for t0, t1, name in innermost(spans):
        ns = xplane.union_ns(g0, glen, t0, t1)
        if ns:
            out[name] = out.get(name, 0.0) + ns
            covered += ns
    out[NO_ANNOTATION] = max(float(glen.sum()) - covered, 0.0)
    return out


def rounds_in(reduced: dict) -> int:
    """Whole benchmark rounds (``bench:round``) inside the window; 1 where
    the trace names none."""
    lo, hi = reduced["window"]
    return max(1, sum(1 for n, t0, t1 in reduced["trace"]["spans"] if n == "round" and lo <= t0 and t1 <= hi))
