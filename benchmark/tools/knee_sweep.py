#!/usr/bin/env python3
"""Find the knee of a scoring cell: the highest offered rate the server
sustains. One process, one server, one stretch of open-loop load at each rate
(benchmark/loadgen.py, the cell's own mix with ``rate`` replaced), lowest rate
first. Made once, when the cell is defined; the cell then runs at a fixed 0.8
of the knee (the traffic file's ``rate``), and a later benchmark PR finds the
knee again with this script once an optimisation has moved it.

    python3 benchmark/tools/knee_sweep.py --workload distilbert-score-steady \
        --rates 500,1000,1500,2000,2500,3000 --seconds 10

A rate is *sustained* when nothing was rejected or left unanswered, the second
half's median reply time is within a quarter of the first half's (no growing
queue) and the generator itself kept up (late p99 under a tenth of the reply
p99). (Flows answered inside a 10 s stretch fall 1-2% short of those offered
at any rate, by the replies still in flight at its end: no criterion.) Prints one line a rate and writes ``chiprun_out/knee_sweep.json``.
Needs a TPU, as every run that reports a speed does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import run as bench
    from benchmark.drivers import score

    try:
        _, ctx = bench.make_context(
            args.workload, seed=args.seed, seconds=args.seconds, trace=False, rehearse=False
        )
    except bench.Refused as e:
        sys.stderr.write(f"knee_sweep: {e}\n")
        return e.code
    rows = []
    try:
        s = score.start_server(ctx)
        try:
            for rate in sorted(float(r) for r in args.rates.split(",")):
                mix = {**ctx.traffic, "rate": rate, "loop": "open"}
                got = score.offer_load(ctx, s, mix, args.seconds, tag=f"rate{rate:g}", window=False)
                t0, table = got["t0"], got["table"]
                r = score.summarise(table, t0, args.seconds)
                ok = r["ok"]
                first = ok & (table["due"] < t0 + args.seconds / 2)
                med = lambda m: float(np.median((table["done"][m] - table["due"][m]) * 1e3)) if m.any() else float("nan")  # noqa: E731
                late_p99 = float(np.percentile(r["late_ms"], 99))
                row = {
                    "rate": rate, "offered": r["attempted"], "answered": r["answered"],
                    "rejected": r["rejected"], "unanswered": r["unanswered"],
                    "flows_per_s": r["flows_per_s"], "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
                    "p50_first_half_ms": med(first), "p50_second_half_ms": med(ok & ~first),
                    "gen_late_p99_ms": late_p99,
                    "mean_batch": float(np.mean(table["batch_size"][ok])) if ok.any() else 0.0,
                }
                row["sustained"] = bool(
                    row["rejected"] == 0 and row["unanswered"] == 0
                    and row["p50_second_half_ms"] <= 1.25 * row["p50_first_half_ms"]
                    and late_p99 <= 0.1 * row["p99_ms"]
                )
                rows.append(row)
                print("[knee] " + json.dumps(row), flush=True)
        finally:
            s["server"].close()
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    sustained = [r["rate"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "knee_sweep.json"), "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds, "rows": rows, "knee": knee}, f, indent=1)
    print(f"[knee] highest sustained rate {knee}; the cell runs at 0.8 of it", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
