#!/usr/bin/env python3
"""Checks of the readers of the program's own annotations, on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/selftest/program_spans.py

``program_span`` and ``ledger_count`` against a trace recorded here, now, by
the benchmark's own recipe (one tiny-preset federated round on the packed
path under ``bench:traced``; host plane only: a CPU trace has no device
plane), ``program_span_idle`` and the flattening of nested annotations
against hand-made tables with known answers, the idle intervals against the
trace recorded on the v5e at PR 22, and all of them against a program
without annotations (a parent commit), where they report nothing.
``tests/test_annotations.py`` runs this file, so the suite guards this part
of the yardstick. Prints one line a check and exits non-zero if any failed.
Nothing printed here is a speed.
"""

from __future__ import annotations

import os
import re
import sys
import tempfile
import time
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

CELL = "distilbert-fed-round-c8"
US = 1_000.0  # ns


def context(workdir: str):
    """A rehearsal context of the flagship cell, as selftest/run.py builds."""
    import jax

    from benchmark import harness

    jax.config.update("jax_enable_compilation_cache", False)
    ctx = harness.Context(
        workload=CELL, seed=5, seconds=0.0, trace=True, rehearsal=True, chips=1,
        config=harness.load_json("configs", "distilbert-base-l128.json"),
        traffic=harness.load_json("traffic", "fed-round-c8.json"),
        cell=harness.load_json("cells", f"{CELL}.json"), t_start=time.perf_counter(),
        workdir=workdir, rec=harness.Recorder(), meter=None, devices=jax.devices(),
    )
    ctx.said = []
    ctx.say = ctx.said.append
    return ctx


def spec_args(metric: str) -> dict:
    from benchmark import harness

    return harness.load_json("layer_metrics", f"{metric}.json")["args"]


# ------------------------------------------------------------ recorded trace
def check_recorded(ctx) -> str:
    """One traced round of the packed path: the table holds what the program
    emitted, and the host readers give what the table implies."""
    from benchmark import harness
    from benchmark.drivers import fed_round
    from benchmark.readers import ledger_count, program_span, program_span_idle
    from benchmark.reduce import program_spans

    b = fed_round.build(ctx)
    for r in range(2):
        fed_round.one_round(ctx, b, r)
    with ctx.profiler():
        with ctx.rec.span("traced"):
            fed_round.one_round(ctx, b, 2)
    fed_round.one_round(ctx, b, 3)  # after the window: in no reading
    assert ctx.trace_path, "the profiler wrote no trace"
    table = program_spans.of(ctx)
    assert program_spans.of(ctx) is table  # read once
    lo, hi = table["window"]
    C, steps = b["C"], b["steps_per_fit"] // b["C"]
    eval_steps = b["prepared"].stacked.labels.shape[1] // b["prepared"].batch_size
    names = [s[0] for s in table["spans"]]
    count = {n: names.count(n) for n in set(names)}
    assert count == {
        "fit": 1, "fit/unstack": 1, "fit/restack": 1, "fit/loss_read": 1, "fit/next_batch": steps + 1,
        "dispatch/fed.packed_step": C * steps, "eval": 2, "eval/read": 2,
        "dispatch/fed.eval_step": 2 * eval_steps, "agg": 1, "reset": 1,
    }, count
    assert all(lo <= t0 and t1 <= hi for _, t0, t1, _ in table["spans"])
    length = {n: [t1 - t0 for m, t0, t1, _ in table["spans"] if m == n] for n in count}

    ctx.rehearsal = False  # let the reader print its earlier line
    edges = program_span.read(ctx, **spec_args("fit_edges_ms"))
    assert np.isclose(edges, (length["fit/unstack"][0] + length["fit/restack"][0]) / 1e6, rtol=1e-12)
    assert "fit/restack x1" in ctx.said[-1] and "fit/unstack x1" in ctx.said[-1], ctx.said[-1]
    dispatch = program_span.read(ctx, **spec_args("dispatch_us"))
    assert np.isclose(dispatch, np.median(length["dispatch/fed.packed_step"]) / 1e3, rtol=1e-12)
    batch = program_span.read(ctx, **spec_args("next_batch_us"))
    assert np.isclose(batch, np.median(length["fit/next_batch"]) / 1e3, rtol=1e-12)
    ctx.rehearsal = True
    assert program_span.read(ctx, pattern="no/such_annotation") is None
    # No device plane, no reduction: the idle reader reports nothing.
    assert program_span_idle.read(ctx, **spec_args("fit_idle_ms")) is None

    # Flattened, the driving thread's annotations cover what the phases
    # cover, once.
    assert {s[3] for s in table["spans"]} == {program_spans.main_line(table["spans"])}
    pieces = program_spans.innermost(table["spans"])
    assert all(a[1] <= b_[0] for a, b_ in zip(pieces, pieces[1:]))
    phases = sum(t1 - t0 for n, t0, t1, _ in table["spans"] if "/" not in n)
    assert np.isclose(sum(t1 - t0 for t0, t1, _ in pieces), phases, rtol=1e-12)

    traces = ledger_count.read(ctx, **spec_args("step_compiles"))
    ledger = harness.pkg("obs.profile").default_ledger()
    assert traces == sum(ledger.compile_counts("fed.packed_step").values()) >= 1, traces
    assert "fed.packed_step" in ctx.said[-1]
    assert ledger_count.read(ctx, sites=["no.such_site"]) is None
    return f"{len(names)} annotations of {len(count)} names in one traced round, {traces:.0f} step trace(s)"


# ------------------------------------------------------------- by hand: idle
def check_idle_by_hand() -> str:
    """A device that idles 4 times, laid against annotations by hand."""
    from benchmark.readers import program_span_idle
    from benchmark.reduce import program_spans, xplane

    # Device operations (us): busy 0-100, 150-400, 430-435, 900-1000,
    # 1010-1200. Gaps: 100-150 (50), 400-430 (30), 435-900 (465), 1000-1010
    # (10: under the 20 us floor), 1200-1300 (100, to the window's end).
    start = np.array([0, 150, 430, 900, 1010], np.float64) * US
    dur = np.array([100, 250, 5, 100, 190], np.float64) * US
    window = (0.0, 1300 * US)
    reduced = {
        "window": window, "worst_chip": 0, "chips": [0],
        "trace": {
            "chips": {0: {"ops": (["op"] * 5, start, dur)}},
            "spans": [("traced", *window), ("round", 0.0, 650 * US), ("round", 650 * US, 1300 * US)],
        },
    }
    gaps = program_spans.device_gaps(reduced)
    assert (gaps / US).tolist() == [[100, 150], [400, 430], [435, 900], [1200, 1300]], gaps / US
    assert xplane.MIN_GAP_NS == 20 * US
    # The program (us): fit 50-600 holding fit/unstack 60-120, a launch
    # 410-420 and fit/restack 500-600; eval 700-1250 holding eval/read
    # 800-1250; another thread's annotation over everything.
    spans = [
        ("fit", 50 * US, 600 * US, "main"),
        ("fit/unstack", 60 * US, 120 * US, "main"),
        ("dispatch/fed.packed_step", 410 * US, 420 * US, "main"),
        ("fit/restack", 500 * US, 600 * US, "main"),
        ("eval", 700 * US, 1250 * US, "main"),
        ("eval/read", 800 * US, 1250 * US, "main"),
        ("fit", 0.0, 1300 * US, "another thread"),
    ]
    pieces = program_spans.innermost([s for s in spans if s[3] == "main"])
    assert [(a / US, b / US, n) for a, b, n in pieces] == [
        (50, 60, "fit"), (60, 120, "fit/unstack"), (120, 410, "fit"),
        (410, 420, "dispatch/fed.packed_step"), (420, 500, "fit"), (500, 600, "fit/restack"),
        (700, 800, "eval"), (800, 1250, "eval/read"),
    ], pieces
    assert program_spans.main_line(spans) == "main"
    by = program_spans.idle_by_annotation(reduced, [s for s in spans if s[3] == "main"])
    want = {
        "fit/unstack": 20.0,  # 100-120
        "fit": 30.0 + 20.0 + 65.0,  # 120-150, 400-410 and 420-430, 435-500
        "dispatch/fed.packed_step": 10.0,  # 410-420
        "fit/restack": 100.0,  # 500-600
        "eval": 100.0,  # 700-800
        "eval/read": 100.0 + 50.0,  # 800-900, 1200-1250
        program_spans.NO_ANNOTATION: 100.0 + 50.0,  # 600-700, 1250-1300
    }
    assert {k: round(v / US, 6) for k, v in by.items()} == want, by
    assert np.isclose(sum(by.values()), (gaps[:, 1] - gaps[:, 0]).sum())

    said: list[str] = []
    ctx = type("Ctx", (), {})()
    ctx.rec = type("Rec", (), {"data": {"xplane": reduced, program_spans.KEY: {"spans": spans, "window": window}}})()
    ctx.trace_path, ctx.rehearsal, ctx.say = "unused", False, said.append
    # Two rounds in the window: per round, in ms.
    assert np.isclose(program_span_idle.read(ctx, name="fit", inside=True), 0.245 / 2)
    assert np.isclose(program_span_idle.read(ctx, name="fit", inside=False), 0.400 / 2)
    assert len(said) == 2 and "by phase (2 round(s), ms)" in said[0] and "by innermost" in said[1], said
    assert "eval 0.250" in said[0] and "no annotation 0.150" in said[0], said[0]
    assert "fit/restack 0.100" in said[1], said[1]
    # A program without annotations: nothing to read, nothing raised.
    ctx.rec.data[program_spans.KEY] = {"spans": [], "window": window}
    assert program_span_idle.read(ctx, name="fit", inside=True) is None
    from benchmark.readers import program_span

    assert program_span.read(ctx, pattern="fit/next_batch") is None
    ctx.trace_path, ctx.rec.data = None, {}
    assert program_spans.of(ctx) is None and program_span.read(ctx, pattern="fit") is None
    return f"{len(gaps)} gaps over {len(pieces)} pieces; fit 0.245 ms, elsewhere 0.400 ms"


def check_v5e_trace() -> str:
    """The trace recorded on the v5e at PR 22 (``tiny_fed.xplane.pb.xz``, a
    program from before the annotations): the idle intervals agree with
    reduce/xplane.py's own sums, and the table of annotations is empty."""
    import lzma

    from benchmark.reduce import program_spans, xplane

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tiny_fed.xplane.pb")
        with lzma.open(os.path.join(HERE, "tiny_fed.xplane.pb.xz")) as src, open(path, "wb") as dst:
            dst.write(src.read())
        reduced = xplane.reduce(path, chips=1)
        assert program_spans.load(path) == []
    gaps = program_spans.device_gaps(reduced)
    idle_s = float((gaps[:, 1] - gaps[:, 0]).sum()) / 1e9
    # The reduction keeps its ten largest labels; all gaps lie between
    # their sum and the window's whole idle time (which counts the gaps
    # under 20 us too).
    top10_s = sum(s for _, s in reduced["breakdown"]["idle_gaps"])
    assert top10_s <= idle_s + 1e-12 <= reduced["window_s"] - reduced["busy_s"] + 1e-9, (top10_s, idle_s)
    assert np.isclose(idle_s, 4.949906098), idle_s
    assert (gaps[:, 1] - gaps[:, 0] >= xplane.MIN_GAP_NS).all() and (gaps[1:, 0] >= gaps[:-1, 1]).all()
    assert program_spans.rounds_in(reduced) == 2
    by = program_spans.idle_by_annotation(reduced, [])
    assert list(by) == [program_spans.NO_ANNOTATION] and np.isclose(by[program_spans.NO_ANNOTATION] / 1e9, idle_s)
    return f"{len(gaps)} gaps, {idle_s:.6f} s idle of {reduced['window_s']:.6f} s"


def check_patterns() -> str:
    """The name patterns of the data files select the programs and
    annotations they are for, and not their neighbours."""
    steps = re.compile(spec_args("train_step_ms")["pattern"])
    evals = re.compile(spec_args("eval_step_ms")["pattern"])
    agg = re.compile(spec_args("agg_device_ms")["pattern"])
    dispatch = re.compile(spec_args("dispatch_us")["pattern"])
    for site in spec_args("step_compiles")["sites"]:
        program = f"jit_{site.replace('.', '_')}(123456789)"
        assert steps.search(program) and not evals.search(program) and not agg.search(program), program
        assert dispatch.fullmatch(f"dispatch/{site}")
    for program in ("jit_fed_eval_step(1)", "jit_engine_eval_step(1)"):
        assert evals.search(program) and not steps.search(program)
    assert agg.search("jit_fedavg_step(7)") and not agg.search("jit_dp_fedavg_step(7)")
    for other in ("jit__lambda(1)", "jit_step(1)", "jit_train_step(1)", "jit_eval_step(1)", "jit__probs(1)",
                  "jit_fedseq_train_step(1)", "jit_engine_train_step_extra(1)"):
        assert not (steps.search(other) or evals.search(other) or agg.search(other)), other
    assert not dispatch.fullmatch("dispatch/fed.eval_step") and not dispatch.fullmatch("dispatch/serving.probs")
    return "4 patterns"


def main() -> int:
    failed = 0
    with tempfile.TemporaryDirectory(prefix="fedtpu_selftest_") as workdir:
        checks = (check_patterns, check_idle_by_hand, check_v5e_trace, lambda: check_recorded(context(workdir)))
        for name, check in zip(("patterns", "idle_by_hand", "v5e_trace", "recorded"), checks):
            try:
                print(f"[selftest] {name}: ok ({check()})", flush=True)
            except Exception:
                failed += 1
                traceback.print_exc()
                print(f"[selftest] {name}: FAILED", flush=True)
    print(f"[selftest] {len(checks) - failed} of {len(checks)} checks passed", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
