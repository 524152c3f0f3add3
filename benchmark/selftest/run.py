#!/usr/bin/env python3
"""The benchmark's own checks, on the CPU, by one command:

    JAX_PLATFORMS=cpu python3 benchmark/selftest/run.py

They check the yardstick, not the program: the trace reduction against a
small trace recorded on the v5e and kept beside this file
(``tiny_fed.xplane.pb.xz``: two tiny-preset federated rounds under the
benchmark's spans, PR 22), the load generator's due-time clock and lateness
against a stub client, the benchmark's round loop against
``FederatedTrainer.run``, each family's FLOPs against the program's
arithmetic, the flow template against the program's, and the manifest against
its data files. Not part of tier-1 (``tests/`` is outside what a benchmark PR
may touch); ``selftest/test_families.py`` runs the quick ones under pytest.
Prints one line a check and exits non-zero if any failed. Nothing printed here
is a speed.
"""

from __future__ import annotations

import concurrent.futures
import glob
import json
import lzma
import os
import re
import sys
import tempfile
import threading
import time
import traceback

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


# ------------------------------------------------------------------ xplane
def check_xplane() -> str:
    """Busy and idle time, the op table and the gap labels of the recorded
    trace, against values read off it by hand when it was recorded."""
    from benchmark.reduce import xplane

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tiny_fed.xplane.pb")
        with lzma.open(os.path.join(HERE, "tiny_fed.xplane.pb.xz")) as src, open(path, "wb") as dst:
            dst.write(src.read())
        r = xplane.reduce(path, chips=1)
    assert r["chips"] == [0] and r["worst_chip"] == 0
    # No bench:traced span in this recording: the window is the extent of
    # the benchmark's spans (two rounds).
    assert close(r["window_s"], 4.951144438), r["window_s"]
    assert close(r["busy_s"], 0.001200285), r["busy_s"]
    assert [n for n, _, _ in r["trace"]["spans"]] == ["round", "fit", "eval", "agg"] * 2
    top_op, top_s = r["breakdown"]["device_ops"][0]
    # 201 distinct copy-done operations of the step program share a label.
    assert top_op == "jit__lambda:copy-done f32[32] x201", top_op
    assert close(top_s, 0.000140571), top_s
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "fit: before jit__lambda" and close(gaps[0][1], 4.793230072), gaps[0]
    assert any(n.startswith("between spans: ") for n, _ in gaps), gaps
    assert sum(s for _, s in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    assert len(r["breakdown"]["device_ops"]) <= 10 and len(gaps) <= 10
    assert close(xplane.busy_inside(r, "fit"), 0.001022345)
    assert close(xplane.busy_inside(r, "eval"), 0.000115777)
    evals = xplane.select(r, "modules", "^jit_eval_step")
    assert len(evals) == 4 and close(float(evals.sum()), 108456.0)
    assert len(xplane.select(r, "ops", "^all-reduce", by="opcode")) == 0  # one chip
    # The helpers, on made-up intervals.
    start, dur = np.array([0.0, 5.0, 8.0, 20.0]), np.array([10.0, 2.0, 4.0, 5.0])
    assert xplane.union_ns(start, dur, 0.0, 30.0) == 17.0
    assert xplane.union_ns(start, dur, 9.0, 22.0) == 5.0
    assert xplane.opcode("%all-reduce-start.3 = (f32[8]{0:T(1024)S(1)}) all-reduce-start(f32[8] %x)") == "all-reduce-start"
    assert xplane.short_module("jit_train_step(123)") == "jit_train_step"
    hlo = "%fusion.351 = (f32[30522,768]{1,0:T(8,128)S(1)}, f32[30522,768]{1,0:T(8,128)}) fusion(f32[8]{0} %p), kind=kLoop, calls=%fc"
    assert xplane.short_op(hlo) == "fusion/Loop f32[30522,768]" and xplane.opcode(hlo) == "fusion"
    return f"window {r['window_s']:.3f} s, {len(r['trace']['chips'][0]['ops'][0])} device ops"


# ----------------------------------------------------------------- loadgen
class StubClient:
    """A client whose ``submit`` stalls once (a starved generator) and whose
    replies come ``service_s`` after the send."""

    def __init__(self, stall_at: int, stall_s: float, service_s: float):
        self.calls, self.stall_at, self.stall_s, self.service_s = 0, stall_at, stall_s, service_s

    def submit(self, *, text):
        self.calls += 1
        if self.calls == self.stall_at:
            time.sleep(self.stall_s)
        fut: concurrent.futures.Future = concurrent.futures.Future()
        body = {"prob": 0.5, "round": 1, "batch_size": 1, "bucket": 1, "queue_ms": 0.0}
        threading.Timer(self.service_s, fut.set_result, args=(body,)).start()
        return fut


def check_loadgen() -> str:
    """Due times come from the seed alone; reply time runs from the due
    time, so a stalled sender shows as lateness inside the latency and not
    as a fast server."""
    from benchmark import loadgen

    spec = {"rate": 200.0, "arrivals": "arrival-even.txt", "loop": "open", "drain_s": 2.0, "root": ROOT}
    a = loadgen.schedule({**spec, "arrivals": "poisson"}, 2.0, seed=3)
    b = loadgen.schedule({**spec, "arrivals": "poisson"}, 2.0, seed=3)
    c = loadgen.schedule({**spec, "arrivals": "poisson"}, 2.0, seed=4)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert len(a) == len(c) == 400 and (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] < 2.0
    # A recorded gap trace keeps its shape and takes the mean rate asked for.
    rec = loadgen.schedule({**spec, "arrivals": "arrival-bursty.txt", "rate": 500.0}, 4.0, seed=0)
    assert abs(len(rec) / 4.0 - 500.0) < 25, len(rec)
    gaps = np.diff(rec)
    assert gaps.std() > 1.5 * gaps.mean(), "the bursty recording came out smooth"
    stall, service = 0.06, 0.004
    run = loadgen.Run([StubClient(40, stall, service)], ["x"] * 8, spec, 0.5, seed=0)
    offsets = run.due.copy()
    assert np.allclose(np.diff(offsets), 1 / 200.0)
    t0 = time.perf_counter() + 0.02
    run.offer(t0)
    t = run.table()
    assert np.array_equal(t["due"], offsets + t0), "due times moved with the replies"
    assert (t["code"] == 0).all() and len(t["due"]) == 100
    late = t["sent"] - t["due"]
    from_due = t["done"] - t["due"]
    from_send = t["done"] - t["sent"]
    assert (late > -1e-4).all() and late[:38].max() < 0.02, late[:38].max()
    # Request 40 (index 39) held the sender for 60 ms: the ~11 requests due
    # meanwhile were sent late, and their reply time from due holds that.
    assert late[40] > stall - 0.01 and late[45] > stall - 0.04, (late[40], late[45])
    assert (from_due >= late + service - 2e-3).all()
    assert from_send[40] < service + 0.02 < from_due[40]
    assert late[-1] < 0.02, "the generator did not catch up after the stall"
    # Closed loop: never more than in_flight outstanding; due = sent.
    closed = loadgen.Run(
        [StubClient(-1, 0.0, 0.01)], ["x"], {**spec, "loop": "closed", "in_flight": 4}, 0.2, seed=0
    )
    closed.offer(time.perf_counter() + 0.01)
    tc = closed.table()
    assert 40 <= len(tc["due"]) <= 100, len(tc["due"])  # 4 in flight / 10 ms over 0.2 s
    assert np.allclose(tc["due"], tc["sent"], atol=1e-3)
    return f"open loop {len(t['due'])} requests, max late {late.max() * 1e3:.0f} ms under a {stall * 1e3:.0f} ms stall; closed loop {len(tc['due'])}"


# -------------------------------------------------------------- round loop
def check_round_loop() -> str:
    """The benchmark's round loop gives the same final parameters as
    ``FederatedTrainer.run`` for the same seed and rounds (tiny preset): it
    calls the same four public methods in the same order."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import harness
    from benchmark.drivers import fed_round

    def context():
        rec = harness.Recorder()
        entry = "distilbert-fed-round-c8"
        return harness.Context(
            workload=entry, seed=5, seconds=0.0, trace=False, rehearsal=True, chips=1,
            config=harness.load_json("configs", "distilbert-base-l128.json"),
            traffic=harness.load_json("traffic", "fed-round-c8.json"),
            cell=harness.load_json("cells", f"{entry}.json"), t_start=time.perf_counter(),
            workdir=tempfile.gettempdir(), rec=rec, meter=None, devices=jax.devices(),
        )

    rounds = 2
    ctx = context()
    ctx.say = lambda msg: None
    b = fed_round.build(ctx)
    for r in range(rounds):
        fed_round.one_round(ctx, b, r, check_mean=True)
    assert not ctx.problems, ctx.problems
    assert fed_round.replicas_identical(b["state"])
    mine = fed_round.replica0_crc(b["state"])
    ctx2 = context()
    ctx2.say = lambda msg: None
    b2 = fed_round.build(ctx2)
    state, history = b2["trainer"].run(
        b2["state"], b2["stacked"], b2["evals"], rounds=rounds, weights=b2["weights"]
    )
    b2["state"] = state
    theirs = fed_round.replica0_crc(b2["state"])
    assert len(history) == rounds
    assert mine == theirs, f"benchmark loop {mine:#010x} != FederatedTrainer.run {theirs:#010x}"
    # The Dirichlet partition covers every row once and is seeded.
    labels = np.array([0, 1] * 100)
    parts = fed_round.partition(labels, 4, "dirichlet:0.5", np.random.default_rng(1))
    again = fed_round.partition(labels, 4, "dirichlet:0.5", np.random.default_rng(1))
    assert sorted(np.concatenate(parts).tolist()) == list(range(200))
    assert all(np.array_equal(x, y) for x, y in zip(parts, again))
    return f"{rounds} rounds, replica 0 crc32 {mine:#010x} both ways"


# ------------------------------------------------------------------- flops
def check_flops() -> str:
    """Every configuration's family counts the FLOPs ``utils/profiling.py``
    counts today, and its parameter count equals what the program builds."""
    import jax

    from benchmark import families, harness

    prof = harness.pkg("utils.profiling")
    seen = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))):
        with open(path) as f:
            conf = json.load(f)
        family, model = families.load(conf), conf["model"]
        cfg = family.model_config(model)
        for rows in (1, 64):
            assert family.forward_flops(model, rows) == prof.forward_flops(cfg, rows)
            assert family.train_step_flops(model, rows) == prof.train_step_flops(cfg, rows)
        built = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(built))
        assert n == family.param_count(model) == conf["parameters"], (n, family.param_count(model))
        assert family.train_step_bytes(model) == 32.0 * n
        seen.append(f"{conf['name']} {n:,}")
    return "; ".join(seen)


def check_peaks() -> str:
    """The table of peaks holds the v5e's published row, an unknown device
    is an error, and the roofline's floor names the roof that sets it."""
    from benchmark import flops

    peaks = flops.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    try:
        flops.load_peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device got a peak")
    assert flops.roofline_floor_s(197e12, 1.0, peaks)[1] == "compute"
    assert flops.roofline_floor_s(1.0, 819e9, peaks) == (1.0, "memory")
    return "v5e 197 TFLOP/s bf16, 819 GB/s"


def check_flows() -> str:
    """The benchmark's flow sentences are the program's template, byte for
    byte, and the same seed gives the same flows."""
    import pandas as pd

    from benchmark import flows, harness

    texts, labels = flows.make_flows(64, seed=9)
    again, _ = flows.make_flows(64, seed=9)
    assert texts == again and len(set(texts)) == 64 and 0 < labels.sum() < 64
    row = {col: v for (_, col, _), v in zip(flows.TEMPLATE, [80, 12, 3, 0, 500, 0, 1200, 600, 1234.5, 99.25])}
    ours = "".join(f"{p}{row[c]}{s}" for p, c, s in flows.TEMPLATE)
    theirs = harness.pkg("data").get_dataset("cicids2017").render_texts(pd.DataFrame([row]))[0]
    assert ours == theirs, (ours, theirs)
    return f"{len(texts)} flows, template equal"


# --------------------------------------------------------------- reference
def check_compare() -> str:
    """``harness.compare_hidden`` on made-up hidden states: the error is
    relative L2 over a sequence's real tokens, padding is left out, and a
    model that answers every input with one sequence's states fails the
    binding."""
    from benchmark import harness

    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 6, 8))
    mask = np.ones((4, 6), np.int32)
    mask[:, 4:] = 0
    noise = rng.normal(size=want.shape)
    noise *= 0.01 * np.sqrt((want[:, :4] ** 2).sum((1, 2)) / (noise[:, :4] ** 2).sum((1, 2)))[:, None, None]
    got = want + noise
    got[:, 4:] += 100.0  # padding: not compared
    r = harness.compare_hidden(got, want, mask)
    assert close(r["hidden_rel_err"], 0.01, 1e-6), r
    assert r["nearest_other"] > 1.0 and r["binding"] > 100, r
    same = harness.compare_hidden(np.repeat(want[:1], 4, 0), want, mask)
    assert same["binding"] < 1.0 + 1e-9 and same["hidden_rel_err"] > 1.0, same
    return f"error {r['hidden_rel_err']:.4f}, binding {r['binding']:.0f}; one answer for all: binding {same['binding']:.2f}"


# ---------------------------------------------------------------- manifest
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_manifest() -> str:
    """Every name in BENCHMARK.json has its data file, every reader exists,
    and the metrics' cells and ``moves`` are consistent."""
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    applies = lambda metric, cell: "workloads" not in metric or cell in metric["workloads"]  # noqa: E731
    for c in m["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"])), c
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in cells.values():
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        for kind, key in (("configs", "config"), ("traffic", "traffic"), ("cells", "name")):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", kind, f"{w[key]}.json")), (kind, w[key])
        with open(os.path.join(ROOT, "benchmark", "traffic", f"{w['traffic']}.json")) as f:
            kind = json.load(f)["kind"]
        importlib.import_module(f"benchmark.drivers.{kind}")
        assert applies(e2e["setup_s"], w["name"])
        assert sum(applies(x, w["name"]) for x in e2e.values()) >= 2
        assert any(applies(x, w["name"]) for x in m["per_layer"])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)
    for x in m["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"{x['name']}.json")) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == x[key], (x["name"], key)
        importlib.import_module(f"benchmark.readers.{spec['reader']}")
        for cell in x.get("workloads", cells):
            assert applies(e2e[x["moves"]], cell), f"{x['name']} moves {x['moves']}, which {cell} does not report"
    for x in list(e2e.values()) + m["per_layer"]:
        assert NAME.match(x["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", x["unit"]), x
    assert all(0.01 <= x["bound"] <= 0.1 for x in e2e.values())
    return f"{len(cells)} cells, {len(e2e)} end-to-end and {len(m['per_layer'])} per-layer metrics"


#: Checks that touch no device and end in seconds: selftest/test_families.py
#: runs each as a case of one parametrised test.
QUICK = (check_manifest, check_flows, check_flops, check_peaks, check_compare)
CHECKS = (*QUICK, check_xplane, check_loadgen, check_round_loop)


def main() -> int:
    failed = 0
    for check in CHECKS:
        name = check.__name__[len("check_"):]
        try:
            print(f"[selftest] {name}: ok ({check()})", flush=True)
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"[selftest] {name}: FAILED", flush=True)
    print(f"[selftest] {len(CHECKS) - failed} of {len(CHECKS)} checks passed", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
