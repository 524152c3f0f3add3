#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled sub-window.
Everything that belongs to one cell, configuration, traffic mix or per-layer
metric is a data file found by its name (``cells/``, ``configs/``,
``traffic/``, ``layer_metrics/``); the code the data selects is in
``drivers/`` (one per traffic kind) and ``readers/`` (one per kind of
per-layer reading).

Exit codes: 0 with a result line; 1 when the run broke (no result line);
2 outside a checkout or with a wrong argument; 3 when JAX finds no TPU or
fewer chips than the cell asks for. No run falls back to the CPU.

    python3 benchmark/run.py --workload <cell> --rehearse-cpu

rehearses the cell's control flow at the tiny preset on 4 virtual CPU
devices: it prints no timing and no device metric, and its last line says
``"rehearsal": true``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny preset on 4 virtual CPU devices; control flow only",
    )
    return ap.parse_args(argv)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Refused(Exception):
    """The run cannot start here; ``code`` is the exit code."""

    def __init__(self, code: int, msg: str):
        super().__init__(msg)
        self.code = code


def make_context(workload: str, *, seed: int, seconds: float | None, trace: bool, rehearse: bool):
    """The manifest and one run's context: data files found by the cell's
    name, the compile cache placed, the device checked. Raises Refused."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    if not os.path.isdir(os.path.join(ROOT, harness.PKG)):
        raise Refused(2, f"{ROOT} holds no {harness.PKG}/: not inside a checkout")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        # A parked cell (PERF.md section 7): its files are here, and its
        # cell file holds the manifest entries it would add.
        try:
            entry = harness.load_json("cells", f"{workload}.json")
        except FileNotFoundError:
            raise Refused(2, f"no workload {workload!r} in BENCHMARK.json") from None
        for key in ("end_to_end", "per_layer"):
            manifest[key] = manifest[key] + entry["parked"][key]

    config = harness.load_json("configs", f"{entry['config']}.json")
    traffic = harness.load_json("traffic", f"{entry['traffic']}.json")
    cell = harness.load_json("cells", f"{entry['name']}.json")
    seconds = float(manifest["run_seconds"] if seconds is None else seconds)
    if rehearse:
        seconds = 0.0  # the loops make their minimum of rounds or requests

    # The cache directory is settled before jax is imported; the program's
    # own helper puts it at <checkout>/.jax_cache unless the machine names
    # another (JAX_COMPILATION_CACHE_DIR).
    cache_dir = harness.pkg("utils.compile_cache").place_compile_cache()
    import jax

    # Cache every program, also those that compile in under the default
    # second: each run is a new process and would compile them again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)
        # A rehearsal leaves no CPU entry in the cache the chip runs use.
        jax.config.update("jax_enable_compilation_cache", False)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(3, f"JAX found no device: {e}") from None
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise Refused(
            3,
            f"JAX found platform {dev.platform!r}, not a TPU: nothing was run "
            "(--rehearse-cpu rehearses the control flow)",
        )
    if len(devices) < entry["chips"]:
        raise Refused(
            3, f"cell {entry['name']} needs {entry['chips']} chip(s), JAX found {len(devices)}"
        )
    rec = harness.Recorder()
    # Interpreter start, imports and the backend's first touch of the chip.
    rec.spans.append({"name": "startup", "phase": "setup", "t0": T_START, "t1": time.perf_counter()})
    ctx = harness.Context(
        workload=entry["name"], seed=seed, seconds=seconds, trace=trace,
        rehearsal=rehearse, chips=entry["chips"], config=config, traffic=traffic,
        cell=cell, t_start=T_START, workdir=tempfile.mkdtemp(prefix="fedtpu_bench_"),
        rec=rec, meter=harness.CompileMeter(rec), devices=devices,
        gc_watch=harness.GcWatch(rec),
    )
    ctx.say(
        f"cell {ctx.workload}: config {entry['config']}, traffic {entry['traffic']} "
        f"(kind {traffic['kind']}), seed {ctx.seed}, {seconds:g} s, trace {int(ctx.trace)}; "
        f"device {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}"
    )
    if ctx.rehearsal:
        ctx.say(
            "REHEARSAL on the CPU at the tiny preset: control flow only; nothing "
            "below is a device number and no timing is printed"
        )
    return manifest, ctx


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        manifest, ctx = make_context(
            args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            rehearse=args.rehearse_cpu,
        )
    except Refused as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return e.code
    driver = importlib.import_module(f"benchmark.drivers.{ctx.traffic['kind']}")
    try:
        out = driver.run(ctx)
        window_compiles = int(ctx.meter.get("window", "compiles"))
        if not ctx.compare("window_compiles", window_compiles, 0):
            ctx.fail(f"{window_compiles} compilation(s) inside the window")
        result = assemble(ctx, manifest, out)
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    for problem in ctx.problems:
        ctx.say(f"NOT CORRECT: {problem}")
    # Every number that decided ``correct`` beside its limit: the last
    # lines of standard error, and the last key of the result line.
    for name, (value, limit) in ctx.compared.items():
        sys.stderr.write(f"compared {name}: {value!r} (limit {limit!r})\n")
    sys.stderr.flush()
    result["compared"] = ctx.compared
    print(json.dumps(result), flush=True)
    return 0


def assemble(ctx, manifest: dict, out: dict) -> dict:
    """The result line: the cell's metrics by name and unit, as measured,
    with all their digits."""
    from benchmark import harness

    peak, per_chip = ctx.memory
    dev = ctx.devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(ctx.devices),
        "memory_peak_bytes": peak,
    }
    ctx.say(
        f"set-up {ctx.num(ctx.setup_s, ' s')} of which backend compile "
        f"{ctx.num(ctx.meter.get('setup', 'backend_compile_s'), ' s')} in "
        f"{int(ctx.meter.get('setup', 'compiles'))} compilation(s), persistent cache "
        f"{int(ctx.meter.get('setup', 'cache_hits'))} hit(s) "
        f"{int(ctx.meter.get('setup', 'cache_misses'))} miss(es); window "
        f"{ctx.num(ctx.window_s, ' s')}; memory per chip {per_chip}"
    )
    if not ctx.rehearsal:
        ctx.say(ctx.gc_watch.report("window"))
        ctx.say(
            "set-up spans: "
            + "; ".join(
                f"{x['name']} {x['t1'] - x['t0']:.2f} s (from {x['t0'] - ctx.t_start:.1f})"
                for x in ctx.rec.spans
                if x["phase"] == "setup" and x["t1"] - x["t0"] >= 0.3
            )
        )
    values: dict[str, float] = {}
    breakdown = None
    if not ctx.trace:
        values = {"setup_s": ctx.setup_s, **out["end_to_end"]}
        wanted = [m for m in manifest["end_to_end"] if applies(m, ctx.workload)]
    else:
        wanted = [m for m in manifest["per_layer"] if applies(m, ctx.workload)]
        if ctx.trace_path and not ctx.rehearsal:  # a CPU trace has no device plane
            from benchmark.reduce import xplane

            reduced = xplane.reduce(
                ctx.trace_path, chips=ctx.rec.data.get("chips", ctx.chips),
                gap_label=ctx.cell.get("trace", {}).get("idle_label", "between spans"),
            )
            ctx.rec.data["xplane"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
            for line in reduced["report"]:
                ctx.say(line)
        for m in wanted:
            spec = harness.load_json("layer_metrics", f"{m['name']}.json")
            reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                values[m["name"]] = value
    metrics = {}
    for m in wanted:
        if m["name"] not in values:
            continue  # a reader that found nothing to read reports nothing
        value = float(values[m["name"]])
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": not ctx.problems,
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if ctx.rehearsal:
        # A CPU rehearsal proves control flow; it carries no number.
        result["rehearsal"] = True
        result["metrics"] = {k: {"unit": v["unit"]} for k, v in metrics.items()}
        result["device"] = {k: device[k] for k in ("platform", "kind", "count")}
        result.pop("breakdown", None)
    return result


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        # No result line: a run that broke must not look like a measurement.
        traceback.print_exc()
        code = 1
    sys.exit(code)
