"""From a profiler trace (``.xplane.pb``) to numbers: busy and idle time per
chip, the operations that took most device time, the idle gaps by what the
host was doing, and the raw tables the per-layer readers select from.

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds (looked
at by hand on the v5e, jax 0.9.0, PR 22): one plane ``/device:TPU:<n>`` per
chip with the lines ``XLA Modules`` (one event per program execution, named
``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (the operations of the compute
stream, one after another, named by their HLO text), ``Async XLA Ops``
(copies and collectives in flight beside them) and ``Steps``; and one plane
``/host:CPU`` whose line ``python3`` carries the ``TraceAnnotation``s of the
process. All on one clock, in nanoseconds from the start of the session, so
the benchmark's own spans (``bench:<name>``, harness.Recorder) lie beside the
device's operations.

Busy time is the union of the intervals of ``XLA Ops`` inside the window: a
program that waits inside for a copy or a collective is not busy then. The
window is the ``bench:traced`` span when there is one.
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "traced"
#: Spans that only group others; a gap is labelled by what is inside them.
OUTER_SPANS = (WINDOW_SPAN, "round")
#: Device gaps shorter than this are the launch overhead between two
#: operations of one program, not idleness a host could fill.
MIN_GAP_NS = 20_000

_OPCODE = re.compile(r"(?<![\w%.\-])([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[[^\]]*\]")
_KIND = re.compile(r"kind=k(\w+)")
_HLO = re.compile(r"^%?([\w.\-]+) = (.*)$", re.S)


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name (``fusion``,
    ``all-reduce-start``, ``copy`` ...)."""
    m = _HLO.match(name)
    found = _OPCODE.search(m.group(2) if m else name)
    return found.group(1) if found else ""


def short_op(name: str) -> str:
    """``%fusion.351 = (f32[30522,768]{...}, ...) fusion(...), kind=kLoop``
    -> ``fusion/Loop f32[30522,768]``: the opcode, the fusion kind and the
    first output shape. Operations of one program that agree in all three
    (the same matmul in every layer) share the label and are summed."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    rest = m.group(2)
    op = _OPCODE.search(rest)
    shape = _SHAPE.search(rest)
    kind = _KIND.search(rest)
    label = op.group(1) if op else "?"
    if kind:
        label += f"/{kind.group(1)}"
    if shape:
        label += f" {shape.group(0)}"
    return label


def short_module(name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return name.split("(", 1)[0]


def _events(line) -> tuple[list[str], np.ndarray, np.ndarray]:
    names, start, dur = [], [], []
    for e in line.events:
        names.append(e.name)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    start = np.asarray(start, np.float64)
    dur = np.asarray(dur, np.float64)
    order = np.argsort(start, kind="stable")
    return [names[i] for i in order], start[order], dur[order]


def load(path: str) -> dict:
    """The trace as plain tables: per chip the ops, async ops and modules;
    and the benchmark's spans from the host plane."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips: dict[int, dict] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            tables = {}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules", "Async XLA Ops": "async"}.get(line.name)
                if key:
                    tables[key] = _events(line)
            chips[int(m.group(1))] = tables
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns, e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s[1])
    return {"chips": chips, "spans": spans}


def union_ns(start: np.ndarray, dur: np.ndarray, lo: float, hi: float) -> float:
    """Length of the union of [start, start+dur) clipped to [lo, hi)."""
    if len(start) == 0:
        return 0.0
    s = np.clip(start, lo, hi)
    e = np.clip(start + dur, lo, hi)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    # An interval adds what lies beyond everything that started before it.
    prev = np.concatenate(([lo], reach[:-1]))
    return float(np.maximum(e - np.maximum(s, prev), 0.0).sum())


def window_of(trace: dict) -> tuple[float, float]:
    for name, t0, t1 in trace["spans"]:
        if name == WINDOW_SPAN:
            return t0, t1
    inner = [(t0, t1) for _, t0, t1 in trace["spans"]]
    if inner:
        return min(t for t, _ in inner), max(t for _, t in inner)
    lo = min(t["ops"][1][0] for t in trace["chips"].values() if len(t.get("ops", ((), (), ()))[1]))
    hi = max((t["ops"][1] + t["ops"][2]).max() for t in trace["chips"].values() if len(t["ops"][1]))
    return float(lo), float(hi)


def span_at(spans, t: float, default: str) -> str:
    """The innermost benchmark span that covers time ``t``."""
    best, best_len = None, None
    for name, t0, t1 in spans:
        if t0 <= t < t1 and name not in OUTER_SPANS:
            if best is None or (t1 - t0) < best_len:
                best, best_len = name, t1 - t0
    return best if best is not None else default


def in_spans(trace: dict, name: str, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of the benchmark span ``name`` inside the window."""
    return [(max(t0, lo), min(t1, hi)) for n, t0, t1 in trace["spans"] if n == name and t1 > lo and t0 < hi]


def reduce(path: str, *, chips: int, gap_label: str = "between spans") -> dict:
    """Everything the result line and the readers need from one trace."""
    trace = load(path)
    lo, hi = window_of(trace)
    window_ns = hi - lo
    used = sorted(trace["chips"])[:chips] if chips else sorted(trace["chips"])
    used = [c for c in used if len(trace["chips"][c].get("ops", ((), (), ()))[1])]
    if not used:
        raise RuntimeError(f"no device operation in the trace {path}")
    busy = {}
    for c in used:
        _, start, dur = trace["chips"][c]["ops"]
        busy[c] = union_ns(start, dur, lo, hi)
    worst = min(busy, key=busy.get)

    # Operations by device time, named program:op, averaged over the chips.
    by_op: dict[str, float] = {}
    instances: dict[str, set] = {}
    by_module: dict[str, list[float]] = {}
    for c in used:
        names, start, dur = trace["chips"][c]["ops"]
        mnames, mstart, mdur = trace["chips"][c].get("modules", ([], np.zeros(0), np.zeros(0)))
        inside = (start >= lo) & (start < hi)
        owner = np.searchsorted(mstart, start, side="right") - 1
        cache: dict[tuple[int, str], str] = {}
        for i in np.flatnonzero(inside):
            o = int(owner[i])
            mod = short_module(mnames[o]) if 0 <= o < len(mnames) and start[i] < mstart[o] + mdur[o] else "?"
            key = (o if mod != "?" else -1, names[i])
            label = cache.get(key)
            if label is None:
                label = cache[key] = f"{mod}:{short_op(names[i])}"
            by_op[label] = by_op.get(label, 0.0) + dur[i] / len(used)
            instances.setdefault(label, set()).add(names[i])
        for n, s, d in zip(mnames, mstart, mdur):
            if lo <= s < hi:
                rec = by_module.setdefault(short_module(n), [0.0, 0])
                rec[0] += d / len(used)
                rec[1] += 1
    top_ops = [
        (f"{label} x{len(instances[label])}", t)
        for label, t in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    ]

    # Idle gaps on the chip that idled most, by the benchmark's own span
    # that covers each and the program the device ran next.
    names, start, dur = trace["chips"][worst]["ops"]
    mnames, mstart, _ = trace["chips"][worst].get("modules", ([], np.zeros(0), np.zeros(0)))
    keep = (start + dur > lo) & (start < hi)
    s, e = start[keep], (start + dur)[keep]
    reach = np.maximum.accumulate(e) if len(e) else e
    gap_start = np.concatenate(([lo], reach))
    gap_end = np.concatenate((s, [hi]))
    gaps: dict[str, float] = {}
    for g0, g1 in zip(gap_start, gap_end):
        if g1 - g0 < MIN_GAP_NS:
            continue
        label = span_at(trace["spans"], (g0 + g1) / 2, gap_label)
        nxt = int(np.searchsorted(mstart, g1 - 1, side="left"))
        if nxt < len(mnames) and mstart[nxt] < hi:
            label += f": before {short_module(mnames[nxt])}"
        else:
            label += ": at the window's end"
        gaps[label] = gaps.get(label, 0.0) + (g1 - g0)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]

    busy_s = float(np.mean(list(busy.values()))) / 1e9
    window_s = window_ns / 1e9
    report = [
        f"trace: window {window_s:.4f} s on {len(used)} chip(s); busy per chip "
        + ", ".join(f"{c}: {busy[c] / 1e9:.4f} s" for c in used)
        + f"; idle share of the chip that idled most {100 * (1 - busy[worst] / window_ns):.2f}%",
        "trace: programs by device time: "
        + "; ".join(
            f"{n} {v[0] / 1e9:.4f} s in {v[1] // len(used)} run(s)"
            for n, v in sorted(by_module.items(), key=lambda kv: -kv[1][0])[:8]
        ),
    ]
    return {
        "trace": trace,
        "window": (lo, hi),
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_by_chip_s": {c: busy[c] / 1e9 for c in used},
        "worst_chip": worst,
        "chips": used,
        "breakdown": {
            "device_ops": [[n, float(t) / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, float(t) / 1e9] for n, t in top_gaps],
        },
        "report": report,
    }


def busy_inside(reduced: dict, span: str) -> float:
    """Seconds of device busy time inside the benchmark span ``span`` of the
    traced window, averaged over the chips used."""
    trace = reduced["trace"]
    lo, hi = reduced["window"]
    total = 0.0
    for c in reduced["chips"]:
        _, start, dur = trace["chips"][c]["ops"]
        for t0, t1 in in_spans(trace, span, lo, hi):
            total += union_ns(start, dur, t0, t1)
    return total / len(reduced["chips"]) / 1e9


def select(reduced: dict, line: str, pattern: str, *, by: str = "name") -> np.ndarray:
    """Durations (ns) of the events on ``line`` (``ops`` | ``async`` |
    ``modules``) inside the window whose name (``by="name"``) or HLO opcode
    (``by="opcode"``) matches ``pattern``, over all chips used."""
    rx = re.compile(pattern)
    lo, hi = reduced["window"]
    out = []
    for c in reduced["chips"]:
        names, start, dur = reduced["trace"]["chips"][c].get(line, ([], np.zeros(0), np.zeros(0)))
        verdict: dict[str, bool] = {}
        for i in np.flatnonzero((start >= lo) & (start < hi)):
            n = names[i]
            hit = verdict.get(n)
            if hit is None:
                hit = verdict[n] = bool(rx.search(opcode(n) if by == "opcode" else n))
            if hit:
                out.append(dur[i])
    return np.asarray(out, np.float64)
