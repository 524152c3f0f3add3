#!/usr/bin/env python3
"""The load generator: one child process that offers scoring requests to a
server on a fixed schedule and records what comes back. It never touches the
device (``JAX_PLATFORMS=cpu`` in its environment; it only drives the
program's client SDK, ``serving.client.PipelinedScoringClient``).

Copied from the program's ``serving/client.py::run_load`` and corrected: there
latency runs from the actual send, so a stalled generator hides the wait it
imposed; here every request has a *due* time fixed before the window from
the seed, latency runs from that due time, and how late each send was is
recorded beside it.

    python3 benchmark/loadgen.py <spec.json>

The spec (written by drivers/score.py): ``host``, ``port``, ``root`` (the
checkout), ``texts`` (a JSON list of flow sentences), ``loop``
(``open`` | ``closed``), ``rate`` (requests/s, open loop), ``arrivals``
(``poisson`` | a gap-trace file under benchmark/traffic/, one gap in seconds a
line, scaled to the mean ``rate``), ``connections``, ``in_flight`` (per
connection, closed loop), ``seconds``, ``warmup_s``, ``drain_s``, ``seed``,
``out`` (an ``.npz`` path).

Protocol with the parent, over the child's stdio: the child connects, sends
its warm-up requests, prints ``READY``; the parent writes ``GO <t0>`` with
``t0`` on the system-wide monotonic clock (``time.perf_counter``); the child
offers load from ``t0`` for ``seconds``, waits at most ``drain_s`` for
replies, writes ``out`` and prints ``DONE``.

Per request the output holds: ``due``, ``sent`` (``submit`` called),
``handed`` (``submit`` returned), ``done`` (monotonic seconds; ``done`` NaN
when unanswered), ``flow`` (index into ``texts``), ``code`` (0
answered, the reject code when rejected, -1 unanswered or failed), and the
reply's ``prob``, ``round``, ``batch_size``, ``bucket``, ``queue_ms``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

import numpy as np

PKG = (
    "detecting_cyber_attacks_with_distilled_large_language_models"
    "_in_distributed_networks_tpu"
)
REPLY_FIELDS = ("prob", "round", "batch_size", "bucket", "queue_ms")
#: Rows of the table a closed loop fills at the most (it has no schedule to
#: count them from): 51 s at 39,000 requests/s.
CLOSED_LOOP_ROWS = 2_000_000


def schedule(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) as offsets from the window's start, fixed
    from the seed before any request is sent. ``poisson`` is a Poisson
    process conditioned on its count: exactly rate x seconds arrivals at
    independent uniform times, so that every run offers the same amount of
    work (an unconditioned count would move flows/s by 0.6% a seed)."""
    rng = np.random.default_rng(seed)
    rate = float(spec["rate"])
    arrivals = spec.get("arrivals", "poisson")
    if arrivals == "poisson":
        return np.sort(rng.uniform(0.0, seconds, size=int(round(rate * seconds))))
    path = os.path.join(spec["root"], "benchmark", "traffic", arrivals)
    with open(path) as f:
        rec = [float(x) for x in (ln.strip() for ln in f) if x and not x.startswith("#")]
    rec = np.asarray(rec, np.float64)
    if len(rec) == 0 or (rec < 0).any() or rec.sum() <= 0:
        raise ValueError(f"arrival trace {arrivals!r}: want non-negative gaps, not all zero")
    # The recording keeps its shape; its mean gap becomes 1 / rate.
    rec = rec * (1.0 / rate) / rec.mean()
    n = int(rate * seconds * 1.5) + 64
    gaps = np.tile(rec, n // len(rec) + 1)[:n]
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


class Run:
    """One stretch of offered load and the table it fills."""

    def __init__(self, clients, texts, spec: dict, seconds: float, seed: int):
        self.clients, self.texts, self.spec, self.seconds = clients, texts, spec, seconds
        self.closed = spec.get("loop", "open") == "closed"
        rng = np.random.default_rng(seed + 1)
        if self.closed:
            self.due = np.full(CLOSED_LOOP_ROWS, np.nan)
        else:
            self.due = schedule(spec, seconds, seed)
        n = len(self.due)
        self.flow = rng.integers(0, len(texts), size=n)
        self.sent = np.full(n, np.nan)
        self.handed = np.full(n, np.nan)  # when submit() returned
        self.done = np.full(n, np.nan)
        self.code = np.full(n, -1, np.int64)
        self.reply = {k: np.full(n, np.nan) for k in REPLY_FIELDS}
        self.count = 0
        self.lock = threading.Lock()

    def _on_done(self, i: int, release, fut) -> None:
        # Runs on the client's reader thread at resolution.
        self.done[i] = time.perf_counter()
        err = fut.exception()
        if err is None:
            body = fut.result()
            self.code[i] = 0
            for k in REPLY_FIELDS:
                if k in body:
                    self.reply[k][i] = body[k]
        else:
            self.code[i] = int(getattr(err, "code", -1)) or -1
            if not hasattr(err, "code"):
                self.done[i] = np.nan  # a lost connection is no answer
        if release is not None:
            release()

    def _send(self, i: int, conn: int, release=None) -> None:
        self.sent[i] = time.perf_counter()
        fut = self.clients[conn].submit(text=self.texts[int(self.flow[i])])
        self.handed[i] = time.perf_counter()
        fut.add_done_callback(lambda f, i=i: self._on_done(i, release, f))

    def open_loop(self, t0: float) -> None:
        """Every request at its due time, whether or not earlier ones have
        come back. Sleeps, never spins: a spinning sender would hold the
        interpreter lock against the reader threads that take the time."""
        self.due = self.due + t0
        n_conn = len(self.clients)
        for i in range(len(self.due)):
            delay = self.due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self._send(i, i % n_conn)
        self.count = len(self.due)

    def closed_loop(self, t0: float) -> None:
        """``in_flight`` requests outstanding on each connection; the next is
        sent when one comes back. A request is due when it is sent."""
        depth = int(self.spec["in_flight"])
        t_end = t0 + self.seconds

        def worker(conn: int) -> None:
            gate = threading.Semaphore(depth)
            while True:
                gate.acquire()
                if time.perf_counter() >= t_end:
                    return
                with self.lock:
                    i = self.count
                    if i >= len(self.due):
                        return
                    self.count += 1
                self.due[i] = time.perf_counter()
                self._send(i, conn, gate.release)

        time.sleep(max(0.0, t0 - time.perf_counter()))
        threads = [threading.Thread(target=worker, args=(c,)) for c in range(len(self.clients))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def offer(self, t0: float) -> None:
        (self.closed_loop if self.closed else self.open_loop)(t0)
        stop = time.perf_counter() + float(self.spec.get("drain_s", 10.0))
        n = self.count
        while time.perf_counter() < stop:
            if not (np.isnan(self.done[:n]) & (self.code[:n] == -1)).any():
                break
            time.sleep(0.01)

    def table(self) -> dict:
        n = self.count
        out = {k: getattr(self, k)[:n] for k in ("due", "sent", "handed", "done", "flow", "code")}
        out.update({k: v[:n] for k, v in self.reply.items()})
        return out


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    sys.path.insert(0, spec["root"])
    # Reader threads take the reply times: hand the interpreter over fast.
    sys.setswitchinterval(0.0005)
    import importlib

    client_mod = importlib.import_module(f"{PKG}.serving.client")
    with open(spec["texts"]) as f:
        texts = json.load(f)
    clients = [
        client_mod.PipelinedScoringClient(spec["host"], int(spec["port"]), timeout=60.0)
        for _ in range(int(spec["connections"]))
    ]
    try:
        warm_s = float(spec.get("warmup_s", 0.0))
        if warm_s > 0:
            # The same traffic for a short stretch, recorded nowhere: sockets,
            # threads and the server's batcher are warm when the window opens.
            Run(clients, texts, {**spec, "drain_s": 5.0}, warm_s, int(spec["seed"]) + 17).offer(
                time.perf_counter() + 0.05
            )
        # The SDK's import pulls in the whole model stack (jax, flax, orbax):
        # a full garbage collection over that heap stops every thread of this
        # process for 60-80 ms (measured, PR 22) and showed as the generator
        # running late. Everything alive now stays: later collections walk
        # only what the window allocates.
        gc.collect()
        gc.freeze()
        print("READY", flush=True)
        line = sys.stdin.readline().split()
        if len(line) != 2 or line[0] != "GO":
            return 2
        run = Run(clients, texts, spec, float(spec["seconds"]), int(spec["seed"]))
        run.offer(float(line[1]))
        np.savez(spec["out"], **run.table())
        print("DONE", flush=True)
        return 0
    finally:
        for c in clients:
            c.close()


if __name__ == "__main__":
    sys.exit(main(sys.argv))
