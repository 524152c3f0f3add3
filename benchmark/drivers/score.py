"""Traffic kind ``score``: live flow scoring through the served path.

Set-up: weights random from the seed on the device, one checkpoint written
with the program's ``Checkpointer``, the scoring service built on it by
``cli.serving.build_infer_server`` with its defaults and started (which warms
its bucket programs), the load generator's child process started and warmed.
The device holds only what the service holds: the state the checkpoint is
written from lives on the host, and the reference forward runs after the
window, after the memory peak is read, on the served parameters themselves.
The window: the child (benchmark/loadgen.py) offers the mix's load for
``--seconds`` over loopback TCP, one flow sentence a request as text, and
times every reply from when the request was due. This process owns the chip
and only serves.

Parameters of a mix: ``loop`` (``open`` | ``closed``), ``rate``, ``arrivals``
(``poisson`` | a gap-trace file), ``connections``, ``in_flight``, ``flows``
(distinct sentences), ``warmup_s``, ``drain_s``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from .. import harness
from ..harness import Context, pkg

SAMPLE_REPLIES = 32


def start_server(ctx: Context) -> dict:
    """Everything up to a listening, warm server; returns it with the
    seeded flows and, on the host, the parameters the checkpoint holds."""
    import jax

    config = pkg("config")
    model_cfg = ctx.model_config()
    cfg = config.ExperimentConfig(
        model=model_cfg,
        data=config.DataConfig(max_len=model_cfg.max_len),
        train=config.TrainConfig(seed=ctx.seed, log_every=0),
    )
    tok = pkg("data").default_tokenizer()
    texts, split = harness.tokenised_flows(ctx, ctx.scaled("flows", 64), ctx.seed, tok)
    cfg_path = os.path.join(ctx.workdir, "config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg.to_dict(), f)
    ckpt_dir = os.path.join(ctx.workdir, "ckpt")
    with ctx.rec.span("checkpoint"):
        params = jax.device_get(
            harness.init_params_on_device(ctx.family, model_cfg, ctx.seed, cfg.train.prng_impl)
        )
        trainer = pkg("train.engine").Trainer(model_cfg, cfg.train, pad_id=tok.pad_id)
        # Checkpointer saves a whole TrainState. A fresh one's Adam moments
        # are zeros twice the size of the parameters, which no scorer holds:
        # they are made on the host, from the state's abstract shape.
        fresh = jax.eval_shape(lambda: trainer.init_state(seed=ctx.seed))
        state = fresh._replace(
            params=params,
            opt_state=jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), fresh.opt_state),
            step=np.zeros((), np.int32),
            rng=jax.random.key(ctx.seed, impl=cfg.train.prng_impl),
        )
        with pkg("train.checkpoint").Checkpointer(ckpt_dir) as ckpt:
            ckpt.save(1, state, meta={"kind": "local", "round": 1, "config": cfg.to_dict()})
            ckpt.wait()
        del state, trainer
    argv = [
        "infer-serve", "--config", cfg_path, "--checkpoint-dir", ckpt_dir,
        "--host", "127.0.0.1", "--port", "0",
    ]
    ctx.say(f"fedtpu {' '.join(argv)}")
    with ctx.rec.span("serve_start"):
        server, banner = pkg("cli.serving").build_infer_server(
            pkg("cli").build_parser().parse_args(argv)
        )
        server.start()
    ctx.say(banner)
    texts_path = os.path.join(ctx.workdir, "texts.json")
    with open(texts_path, "w") as f:
        json.dump(texts, f)
    return {
        "server": server, "texts": texts, "texts_path": texts_path, "split": split,
        "params": params,
    }


def offer_load(
    ctx: Context, s: dict, mix: dict, seconds: float, *, on_go=None, tag="load", window=True
) -> dict:
    """One child process offering ``mix`` for ``seconds``; returns the
    table it recorded and ``t0``, the start of the load on this clock.
    ``on_go(t0)`` runs in this process while the load is offered;
    ``window``: this load is the run's measured window (set-up ends at
    ``t0``)."""
    out = os.path.join(ctx.workdir, f"{tag}.npz")
    spec = {
        **{k: v for k, v in mix.items() if k not in ("doc", "kind", "knee")},
        "host": "127.0.0.1", "port": s["server"].port, "root": harness.ROOT,
        "texts": s["texts_path"], "seconds": seconds, "seed": ctx.seed, "out": out,
    }
    spec_path = os.path.join(ctx.workdir, f"{tag}.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_COMPILATION_CACHE": "0"}
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, "loadgen.py"), spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
    )
    # A child that hangs is killed, so that no readline below waits for ever.
    budget = float(mix.get("warmup_s", 0.0)) + seconds + float(mix.get("drain_s", 10.0)) + 90.0
    watchdog = threading.Timer(budget, child.kill)
    watchdog.start()
    try:
        line = child.stdout.readline().strip()
        if line != "READY":
            raise RuntimeError(f"load generator said {line!r}, not READY")
        t0 = time.perf_counter() + 0.1
        if window:
            ctx.begin_window(at=t0)
        child.stdin.write(f"GO {t0!r}\n")
        child.stdin.flush()
        if on_go is not None:
            on_go(t0)
        line = child.stdout.readline().strip()
        child.wait(timeout=30.0)
        if line != "DONE" or child.returncode != 0:
            raise RuntimeError(f"load generator ended with {line!r}, rc {child.returncode}")
    finally:
        watchdog.cancel()
        if child.poll() is None:
            child.kill()
        child.wait()
    with np.load(out) as z:
        table = {k: z[k] for k in z.files}
    return {"t0": t0, "table": table}


def summarise(table: dict, t0: float, seconds: float, skip_until: float | None = None) -> dict:
    """Client-side numbers of one stretch of load. ``skip_until``: leave out
    the requests due before that time (the traced part of a traced run)."""
    due = table["due"]
    keep = np.ones(len(due), bool) if skip_until is None else due >= skip_until
    ok = (table["code"] == 0) & keep
    latency = (table["done"][ok] - due[ok]) * 1e3
    late = (table["sent"][keep] - due[keep]) * 1e3
    span = seconds if skip_until is None else max(t0 + seconds - skip_until, 1e-9)
    in_window = ok & (table["done"] <= t0 + seconds)
    return {
        "attempted": int(keep.sum()),
        "answered": int(ok.sum()),
        "rejected": int(((table["code"] > 0) & keep).sum()),
        "unanswered": int(((table["code"] < 0) & keep).sum()),
        "latency_ms": latency,
        "late_ms": late,
        "flows_per_s": float(in_window.sum()) / span,
        **{
            f"p{q}_ms": float(np.percentile(latency, q)) if len(latency) else float("nan")
            for q in (50, 90, 95, 99)
        },
        "ok": ok,
    }


def check_served(ctx: Context, s: dict, table: dict, ok: np.ndarray) -> None:
    """After the window and the memory reading: the served parameters are
    the checkpoint's to the bit and the program's model on them agrees with
    the plain float32 reference (harness.check_model); a seeded sample of
    answered requests lies within the family's ``reply_abs`` of the
    reference forward of the same sentences; every reply names round 1."""
    import jax

    served = s["server"].engine.snapshot()[0]
    same = all(
        np.array_equal(np.asarray(x), y)
        for x, y in zip(jax.tree.leaves(served), jax.tree.leaves(s["params"]))
    )
    if not same:
        ctx.fail("the served parameters are not the checkpoint's")
    ctx.rec.data["reference"] = harness.check_model(
        ctx, served, s["split"], what="served model", key="served", bind=True
    )
    rounds = table["round"][ok]
    if len(rounds) and not (rounds == 1).all():
        ctx.fail(f"{int((rounds != 1).sum())} replies do not name the served round 1")
    answered = np.flatnonzero(ok)
    if len(answered) == 0:
        ctx.fail("no request was answered")
        return
    rng = np.random.default_rng(ctx.seed + 2003)
    pick = rng.choice(answered, size=min(SAMPLE_REPLIES, len(answered)), replace=False)
    flows = table["flow"][pick].astype(int)
    split = s["split"]
    _, want = ctx.family.reference(
        served, split.input_ids[flows], split.attention_mask[flows], ctx.model
    )
    want = np.asarray(want, np.float64)
    e = np.exp(want - want.max(-1, keepdims=True))
    p_ref = (e / e.sum(-1, keepdims=True))[:, 1]
    p_got = table["prob"][pick]
    err = float(np.abs(p_got - p_ref).max())
    limit = ctx.family.TOLERANCES["reply_abs"]
    ctx.say(
        f"correct/replies: {len(pick)} served probabilities vs the float32 reference: "
        f"max |dp| {err:.6f} (limit {limit}); the reference's answers span "
        f"{p_ref.max() - p_ref.min():.6f}"
    )
    if not ctx.compare("replies.max_abs_dp", err, limit):
        ctx.fail(f"served probabilities differ from the reference by {err} (limit {limit})")
    ctx.rec.data["reference"]["max_abs_dp"] = err


def run(ctx: Context) -> dict:
    mix = dict(ctx.traffic)
    seconds = ctx.seconds
    if ctx.rehearsal:
        mix.update(rate=40.0, warmup_s=0.3, in_flight=min(int(mix.get("in_flight") or 1), 4))
        seconds = 1.0
    s = start_server(ctx)
    server = s["server"]
    traced: dict = {}

    def trace_part(t0: float) -> None:
        # The traced sub-window opens the window; its requests are left out
        # of the reply metrics (starting and stopping the profiler stalls
        # the process it traces).
        time.sleep(max(0.0, t0 - time.perf_counter()))
        with ctx.profiler():
            with ctx.rec.span("traced"):
                time.sleep(float(ctx.cell.get("trace", {}).get("seconds", 3.0)) if not ctx.rehearsal else 0.3)
        traced["until"] = time.perf_counter() + 0.5

    try:
        got = offer_load(ctx, s, mix, seconds, on_go=trace_part if ctx.trace else None)
    finally:
        stats = server.stats()
        recompiles = list(server.engine.ledger.recompiles())
        server.close()
    ctx.end_window(got["t0"])
    t0, table = got["t0"], got["table"]
    whole = summarise(table, t0, seconds)
    part = summarise(table, t0, seconds, traced.get("until")) if ctx.trace else whole
    if recompiles:
        ctx.fail(f"the engine recompiled inside the window: {recompiles}")
    check_served(ctx, s, table, whole["ok"])
    failed = whole["rejected"] + whole["unanswered"]
    if failed:
        ctx.say(f"{whole['rejected']} rejected, {whole['unanswered']} unanswered of {whole['attempted']}")
    late_p99 = float(np.percentile(part["late_ms"], 99)) if len(part["late_ms"]) else float("nan")
    ctx.say(
        f"window: offered {whole['attempted']} requests in {seconds:g} s "
        f"({mix.get('loop', 'open')} loop, rate {mix.get('rate')}), answered {whole['answered']}; "
        f"reply time from due p50 {ctx.num(part['p50_ms'], ' ms')} p90 {ctx.num(part['p90_ms'], ' ms')} "
        f"p95 {ctx.num(part['p95_ms'], ' ms')} p99 {ctx.num(part['p99_ms'], ' ms')} "
        f"over {part['answered']} replies; generator late p99 {ctx.num(late_p99, ' ms')}; server: "
        f"{stats['batches']} dispatches, mean batch {stats['mean_batch']:.1f}, rejects {stats['rejects_total']}, "
        f"its own p99 (enqueue to done) {ctx.num(stats['p99_ms'], ' ms')}"
    )
    if not ctx.rehearsal and late_p99 > 0.1 * part["p95_ms"]:
        ctx.say(
            "WARNING: the generator ran late by more than a tenth of the p95: it, "
            "not the server, was starved, and this run's tail is not to be trusted"
        )
    ok = part["ok"]
    ctx.rec.data["replies"] = {
        "latency_ms": part["latency_ms"], "late_ms": part["late_ms"],
        **{k: table[k][ok] for k in ("queue_ms", "batch_size", "bucket")},
    }
    ctx.rec.data.update(chips=1, server_stats={k: v for k, v in stats.items() if k != "score_hist"})
    return {
        "attempted": whole["attempted"],
        "failed": failed,
        "end_to_end": {"score_p95_ms": whole["p95_ms"], "flows_per_s": whole["flows_per_s"]},
    }
