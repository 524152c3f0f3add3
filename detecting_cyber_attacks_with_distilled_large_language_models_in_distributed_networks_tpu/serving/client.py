"""Scoring-service SDK + load generator (tests drive services with it).

Three client shapes over the same wire:

* :class:`ScoringClient` — one TCP connection, synchronous
  request/reply (``score()``); concurrency comes from many clients —
  which is what makes the server's micro-batcher earn its keep: N
  concurrent connections coalesce into one padded bucket dispatch.
* :class:`PipelinedScoringClient` — multi-request pipelining on ONE
  connection: ``submit()`` returns a future immediately and a reader
  thread matches replies to pending requests by the protocol's id echo.
  Replies may arrive out of order (a deadline reject overtakes scoring;
  a router fans one connection across replicas), which is exactly why
  the wire carries ids instead of relying on ordering.
* :class:`AsyncScoringClient` — the asyncio variant of the pipelined
  shape: ``await score(...)`` from any number of concurrent tasks on
  one connection, no threads.

:func:`run_load` drives a service with any of them (closed-loop threads,
optional pipelining depth, optional open-loop pacing at a target QPS)
and reports client-observed throughput and latency percentiles (the
benchmark's own generator is ``benchmark/loadgen.py``).
"""

from __future__ import annotations

import socket
import threading
import time
from concurrent.futures import Future
from typing import Any, Mapping, Sequence

import numpy as np

from ..comm import framing
from ..comm.wire import NONCE_LEN, NONCE_MAGIC, WireError
from . import protocol


class ScoreRejected(Exception):
    """Explicit server-side refusal (admission control / deadline)."""

    def __init__(self, code: int, reason: str, req_id: int):
        super().__init__(f"request {req_id} rejected ({code}): {reason}")
        self.code = int(code)
        self.reason = reason
        self.req_id = int(req_id)


def _set_nodelay(sock: socket.socket) -> None:
    """Disable Nagle on a scoring socket: the frames are small and the
    transport writes header + payload separately (write-write-read), a
    pattern Nagle + delayed ACK turns into per-frame stalls — visibly so
    once a router hop doubles the TCP legs per request."""
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError:
        pass


def answer_auth_challenge(sock: socket.socket, auth_key: bytes) -> None:
    """Client side of the scoring port's HMAC handshake: read the
    server's NONCE challenge, answer with the keyed proof. Shared by
    every client shape here AND the router's backend dials — the
    handshake must not exist four times and drift."""
    try:
        chal = bytes(framing.recv_frame(sock, send_ack=False))
    except (OSError, ConnectionError) as e:
        raise WireError(
            "server sent no auth challenge — is it running with "
            f"--auth? ({e})"
        ) from None
    if len(chal) != len(NONCE_MAGIC) + NONCE_LEN or not chal.startswith(
        NONCE_MAGIC
    ):
        raise WireError(
            f"bad auth challenge from server (magic {chal[:4]!r})"
        )
    framing.send_frame(
        sock,
        protocol.build_auth_response(auth_key, chal[len(NONCE_MAGIC) :]),
        await_ack=False,
    )


class ScoringClient:
    """Blocking scoring connection. Not thread-safe; one per thread.

    ``auth_key``: the scoring port's shared secret (server ``--auth``):
    the constructor answers the server's per-connection nonce challenge
    before the first request. Against a server that requires auth, a
    keyless client fails with a clear WireError on its first score()
    (the challenge frame arrives where the reply was expected)."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        timeout: float = 30.0,
        auth_key: bytes | None = None,
    ):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.settimeout(timeout)
        _set_nodelay(self.sock)
        self._next_id = 0
        if auth_key is not None:
            try:
                answer_auth_challenge(self.sock, auth_key)
            except WireError:
                self.close()
                raise

    def score(
        self,
        *,
        text: str | None = None,
        features: Mapping[str, Any] | None = None,
        deadline_ms: float | None = None,
        trace: str | None = None,
    ) -> dict:
        """Score one flow; returns the reply dict (prob, prediction,
        round, batch_size, bucket, queue_ms — plus ``trace`` echoed when
        the request carried one). Raises :class:`ScoreRejected` on an
        explicit reject frame."""
        self._next_id += 1
        req_id = self._next_id
        framing.send_frame(
            self.sock,
            protocol.build_request(
                req_id,
                text=text,
                features=features,
                deadline_ms=deadline_ms,
                trace=trace,
            ),
            await_ack=False,
        )
        reply = bytes(framing.recv_frame(self.sock, send_ack=False))
        if reply[:4] == NONCE_MAGIC:
            # The server's auth challenge landed where the reply was
            # expected: this client connected without a key to an
            # --auth server. Name the fix instead of a generic magic error.
            raise WireError(
                "server requires authentication — construct the client "
                "with auth_key (server runs with --auth)"
            )
        if protocol.is_reject(reply):
            body = protocol.parse_reject(reply)
            raise ScoreRejected(body["code"], body["reason"], body["id"])
        body = protocol.parse_reply(reply)
        if body["id"] != req_id:
            raise WireError(
                f"reply for request {body['id']} arrived while awaiting "
                f"{req_id} (synchronous client; server must answer in order)"
            )
        return body

    def stats(self) -> dict:
        """Fetch the server's ``stats()`` snapshot over this connection
        (the in-band probe the router's health checks ride)."""
        self._next_id += 1
        req_id = self._next_id
        framing.send_frame(
            self.sock, protocol.build_stats_request(req_id), await_ack=False
        )
        body = protocol.parse_stats_reply(
            bytes(framing.recv_frame(self.sock, send_ack=False))
        )
        if body["id"] != req_id:
            raise WireError(
                f"stats reply for request {body['id']} arrived while "
                f"awaiting {req_id}"
            )
        return body["stats"]

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def __enter__(self) -> "ScoringClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class PipelinedScoringClient:
    """Multi-request pipelining on one connection.

    ``submit()`` sends immediately and returns a
    :class:`concurrent.futures.Future`; a reader thread matches replies
    to pending requests by the protocol's id echo, so any number of
    requests ride the wire concurrently and out-of-order replies (a
    deadline reject overtaking scoring, a router fanning one connection
    across replicas) resolve correctly. Thread-safe: any thread may
    submit. A rejected request resolves its future with
    :class:`ScoreRejected`; a dead connection fails every pending future
    with the underlying error."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        timeout: float = 30.0,
        auth_key: bytes | None = None,
    ):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.settimeout(timeout)
        _set_nodelay(self.sock)
        if auth_key is not None:
            try:
                answer_auth_challenge(self.sock, auth_key)
            except WireError:
                self.close()
                raise
        self._lock = threading.Lock()  # pending map + id counter + _err
        self._wlock = threading.Lock()  # serializes frame writes
        self._pending: dict[int, Future] = {}
        self._next_id = 0
        self._err: Exception | None = None
        self._closed = False
        self._reader = threading.Thread(target=self._read_loop, daemon=True)
        self._reader.start()

    # ------------------------------------------------------------ submit
    def submit(
        self,
        *,
        text: str | None = None,
        features: Mapping[str, Any] | None = None,
        deadline_ms: float | None = None,
        trace: str | None = None,
    ) -> Future:
        with self._lock:
            if self._err is not None:
                raise self._err
            self._next_id += 1
            req_id = self._next_id
            fut: Future = Future()
            self._pending[req_id] = fut
        frame = protocol.build_request(
            req_id,
            text=text,
            features=features,
            deadline_ms=deadline_ms,
            trace=trace,
        )
        try:
            with self._wlock:
                framing.send_frame(self.sock, frame, await_ack=False)
        except (OSError, ConnectionError) as e:
            with self._lock:
                self._pending.pop(req_id, None)
            # The reader may have raced us to the dead socket and failed
            # this future via _fail_all already — never double-resolve.
            if not fut.done():
                fut.set_exception(WireError(f"send failed: {e}"))
        return fut

    def score(self, *, timeout: float | None = None, **kw) -> dict:
        """Synchronous convenience over :meth:`submit` (one in flight)."""
        return self.submit(**kw).result(timeout=timeout)

    # ------------------------------------------------------------- reader
    def _read_loop(self) -> None:
        while True:
            try:
                frame = bytes(
                    framing.recv_frame(self.sock, send_ack=False)
                )
            except (OSError, ConnectionError, WireError) as e:
                self._fail_all(
                    e
                    if isinstance(e, WireError)
                    else WireError(f"connection lost: {e}")
                )
                return
            if frame[:4] == NONCE_MAGIC:
                self._fail_all(
                    WireError(
                        "server requires authentication — construct the "
                        "client with auth_key (server runs with --auth)"
                    )
                )
                return
            try:
                req_id = protocol.frame_id(frame)
            except WireError as e:
                self._fail_all(e)
                return
            with self._lock:
                fut = self._pending.pop(req_id, None)
            if fut is None:
                continue  # reply for a send that already failed locally
            try:
                if protocol.is_reject(frame):
                    body = protocol.parse_reject(frame)
                    fut.set_exception(
                        ScoreRejected(body["code"], body["reason"], body["id"])
                    )
                elif protocol.is_stats_reply(frame):
                    fut.set_result(protocol.parse_stats_reply(frame)["stats"])
                else:
                    fut.set_result(protocol.parse_reply(frame))
            except WireError as e:
                fut.set_exception(e)

    def _fail_all(self, err: Exception) -> None:
        with self._lock:
            if self._closed:
                err = WireError("client closed")
            self._err = err
            pending = list(self._pending.values())
            self._pending.clear()
        for fut in pending:
            if not fut.done():
                fut.set_exception(err)

    # ---------------------------------------------------------------- misc
    def stats(self, *, timeout: float | None = None) -> dict:
        """The server's ``stats()`` snapshot, pipelined like any request."""
        with self._lock:
            if self._err is not None:
                raise self._err
            self._next_id += 1
            req_id = self._next_id
            fut: Future = Future()
            self._pending[req_id] = fut
        try:
            with self._wlock:
                framing.send_frame(
                    self.sock,
                    protocol.build_stats_request(req_id),
                    await_ack=False,
                )
        except (OSError, ConnectionError) as e:
            with self._lock:
                self._pending.pop(req_id, None)
            raise WireError(f"send failed: {e}") from None
        return fut.result(timeout=timeout)

    def close(self) -> None:
        with self._lock:
            self._closed = True
        try:
            # shutdown() BEFORE close(): a plain close while the reader
            # blocks in recv is deferred by CPython until the recv
            # returns (the faults/proxy.py lesson) — the reader would
            # sit its full socket timeout out and stall this join.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        self._reader.join(timeout=5.0)

    def __enter__(self) -> "PipelinedScoringClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AsyncScoringClient:
    """asyncio scoring client: ``await score(...)`` from any number of
    concurrent tasks over one connection.

    The async twin of :class:`PipelinedScoringClient` — same id-matched
    pipelining, no threads: a reader task resolves per-request futures
    as frames arrive. Framing is re-implemented on asyncio streams in
    fire-and-forget mode (``await_ack=False`` both directions, exactly
    the sync protocol), including the CRC check — the transport contract
    must not weaken because the caller went async.

    Construct with ``await AsyncScoringClient.connect(host, port)``.
    """

    def __init__(self, reader, writer):
        self._reader = reader
        self._writer = writer
        self._pending: dict[int, Any] = {}  # id -> asyncio.Future
        self._next_id = 0
        self._err: Exception | None = None
        self._reader_task = None

    # -------------------------------------------------------------- framing
    async def _recv_frame(self) -> bytes:
        import struct

        from ..comm import native

        header = await self._reader.readexactly(len(framing.FRAME_MAGIC) + 12)
        if header[:4] != framing.FRAME_MAGIC:
            raise WireError(f"bad frame magic {bytes(header[:4])!r}")
        length, crc = struct.unpack("<QI", header[4:])
        if length > framing.MAX_FRAME:
            raise WireError(f"frame length {length} exceeds {framing.MAX_FRAME}")
        payload = await self._reader.readexactly(length)
        if native.crc32(payload) != crc:
            raise WireError("frame CRC mismatch")
        return bytes(payload)

    async def _send_frame(self, payload: bytes) -> None:
        import struct

        from ..comm import native

        self._writer.write(
            framing.FRAME_MAGIC
            + struct.pack("<QI", len(payload), native.crc32(payload))
            + payload
        )
        await self._writer.drain()

    # ------------------------------------------------------------- lifecycle
    @classmethod
    async def connect(
        cls,
        host: str,
        port: int,
        *,
        auth_key: bytes | None = None,
    ) -> "AsyncScoringClient":
        import asyncio

        reader, writer = await asyncio.open_connection(host, port)
        self = cls(reader, writer)
        if auth_key is not None:
            chal = await self._recv_frame()
            if len(chal) != len(NONCE_MAGIC) + NONCE_LEN or not chal.startswith(
                NONCE_MAGIC
            ):
                writer.close()
                raise WireError(
                    f"bad auth challenge from server (magic {chal[:4]!r})"
                )
            await self._send_frame(
                protocol.build_auth_response(
                    auth_key, chal[len(NONCE_MAGIC) :]
                )
            )
        self._reader_task = asyncio.ensure_future(self._read_loop())
        return self

    async def _read_loop(self) -> None:
        import asyncio

        try:
            while True:
                frame = await self._recv_frame()
                if frame[:4] == NONCE_MAGIC:
                    raise WireError(
                        "server requires authentication — connect with "
                        "auth_key (server runs with --auth)"
                    )
                req_id = protocol.frame_id(frame)
                fut = self._pending.pop(req_id, None)
                if fut is None or fut.done():
                    continue
                if protocol.is_reject(frame):
                    body = protocol.parse_reject(frame)
                    fut.set_exception(
                        ScoreRejected(body["code"], body["reason"], body["id"])
                    )
                elif protocol.is_stats_reply(frame):
                    fut.set_result(protocol.parse_stats_reply(frame)["stats"])
                else:
                    fut.set_result(protocol.parse_reply(frame))
        except asyncio.CancelledError:
            # close() cancelled us: awaiters blocked in score()/stats()
            # must not hang forever on futures nobody will resolve.
            self._fail_pending(WireError("client closed"))
            raise
        except (OSError, ConnectionError, WireError, EOFError) as e:
            self._fail_pending(
                e
                if isinstance(e, WireError)
                else WireError(f"connection lost: {e}")
            )

    def _fail_pending(self, err: Exception) -> None:
        self._err = err
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(err)
        self._pending.clear()

    async def score(
        self,
        *,
        text: str | None = None,
        features: Mapping[str, Any] | None = None,
        deadline_ms: float | None = None,
        trace: str | None = None,
    ) -> dict:
        """Score one flow; safe to call from many tasks concurrently —
        requests pipeline on the single connection and replies match by
        id. Raises :class:`ScoreRejected` on an explicit reject."""
        import asyncio

        if self._err is not None:
            raise self._err
        self._next_id += 1
        req_id = self._next_id
        fut = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        try:
            await self._send_frame(
                protocol.build_request(
                    req_id,
                    text=text,
                    features=features,
                    deadline_ms=deadline_ms,
                    trace=trace,
                )
            )
        except BaseException:
            self._pending.pop(req_id, None)  # never leak the entry
            raise
        return await fut

    async def stats(self) -> dict:
        import asyncio

        if self._err is not None:
            raise self._err
        self._next_id += 1
        req_id = self._next_id
        fut = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        try:
            await self._send_frame(protocol.build_stats_request(req_id))
        except BaseException:
            self._pending.pop(req_id, None)  # never leak the entry
            raise
        return await fut

    async def close(self) -> None:
        if self._reader_task is not None:
            self._reader_task.cancel()
            try:
                await self._reader_task
            except BaseException:
                pass
        self._fail_pending(WireError("client closed"))
        try:
            self._writer.close()
            await self._writer.wait_closed()
        except (OSError, ConnectionError):
            pass

    async def __aenter__(self) -> "AsyncScoringClient":
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close()


def fetch_stats(
    host: str,
    port: int,
    *,
    timeout: float = 10.0,
    auth_key: bytes | None = None,
) -> dict:
    """One-shot ``stats()`` fetch: dial, (auth,) probe, close. The ops
    convenience behind ``fedtpu route``'s status logging and tests."""
    with ScoringClient(
        host, port, timeout=timeout, auth_key=auth_key
    ) as cli:
        return cli.stats()


def probe_scores(
    host: str,
    port: int,
    texts: Sequence[str],
    *,
    timeout: float = 10.0,
    deadline_ms: float | None = None,
    trace: str | None = None,
    auth_key: bytes | None = None,
) -> list[tuple[dict, float]]:
    """One canary pass: dial ONE connection, score every text in order,
    close. Returns ``(reply, latency_s)`` per text, where the latency is
    the per-request send->reply wall — the sentinel's end-to-end canary
    measurement (obs/sentinel.py), deliberately the synchronous client
    so each probe measures a full round trip, not pipelined overlap. An
    explicit server reject still yields a measurement: the reply dict is
    the reject body plus ``"rejected": True`` (a canary that cannot be
    scored is a finding, not a crash); transport errors propagate to the
    caller, who counts the pass unreachable."""
    out: list[tuple[dict, float]] = []
    with ScoringClient(
        host, port, timeout=timeout, auth_key=auth_key
    ) as cli:
        for text in texts:
            t0 = time.monotonic()
            try:
                reply = cli.score(
                    text=text, deadline_ms=deadline_ms, trace=trace
                )
            except ScoreRejected as e:
                reply = {
                    "id": e.req_id,
                    "rejected": True,
                    "code": e.code,
                    "reason": e.reason,
                    "prob": float("nan"),
                    "prediction": 0,
                    "round": None,
                }
            out.append((reply, time.monotonic() - t0))
    return out


def load_arrival_trace(path: str) -> list[float]:
    """Read a recorded inter-arrival trace: one non-negative gap (in
    seconds) per line, blank lines and ``#`` comments skipped. The
    test fixtures ship a tiny bursty trace in this format."""
    gaps: list[float] = []
    with open(path) as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            gaps.append(float(line))
    if not gaps:
        raise ValueError(f"arrival trace {path!r} has no gaps")
    if any(g < 0.0 for g in gaps):
        raise ValueError(f"arrival trace {path!r} has negative gaps")
    return gaps


def run_load(
    host: str,
    port: int,
    texts: Sequence[str],
    *,
    concurrency: int = 4,
    requests: int | None = None,
    deadline_ms: float | None = None,
    timeout: float = 60.0,
    auth_key: bytes | None = None,
    pipeline: int = 1,
    target_qps: float | None = None,
    arrival_trace: Sequence[float] | None = None,
) -> dict:
    """Load generator: ``concurrency`` connections scoring the next text
    round-robin until ``requests`` total (default: one pass over
    ``texts``) have been answered. Returns client-observed stats:
    flows/s, p50/p95/p99 ms, reject count, per-reply batch sizes (the
    coalescing evidence tests assert on).

    ``pipeline`` > 1 keeps that many requests in flight PER CONNECTION
    (:class:`PipelinedScoringClient`) — the closed loop stops being
    bounded by one round-trip per connection. ``target_qps`` switches to
    open-loop pacing: requests are issued on a fixed fleet-wide schedule
    (request i not before ``t0 + i/target_qps``) regardless of how fast
    replies come back, which is how you measure a latency distribution
    AT a load point instead of the closed loop's self-throttled
    equilibrium; pacing implies pipelining (a paced sender must not
    block on the previous reply).

    ``arrival_trace`` replays a RECORDED inter-arrival pattern instead
    of a constant rate: gap ``j`` (seconds) separates request ``j`` from
    request ``j+1`` on the fleet-wide schedule, and the trace wraps
    whole-cycle when ``requests`` outruns it — a bursty recording stays
    bursty for the whole run. Open-loop like ``target_qps`` (the two are
    mutually exclusive), so the tail the service shows under real burst
    shapes is measurable, not the closed loop's smoothed-out version."""
    total = len(texts) if requests is None else int(requests)
    pipeline = max(1, int(pipeline))
    if target_qps is not None:
        if target_qps <= 0:
            raise ValueError(f"target_qps={target_qps} must be > 0")
        pipeline = max(pipeline, 32)  # pacing must not block on replies
    arrival_base: np.ndarray | None = None
    arrival_cycle = 0.0
    if arrival_trace is not None:
        if target_qps is not None:
            raise ValueError(
                "arrival_trace and target_qps are mutually exclusive "
                "(both fix the fleet-wide send schedule)"
            )
        gaps = np.asarray(list(arrival_trace), np.float64)
        if gaps.size == 0:
            raise ValueError("arrival_trace is empty")
        if (gaps < 0.0).any():
            raise ValueError("arrival_trace gaps must be >= 0")
        # Request j fires at the cumulative offset of the gaps BEFORE
        # it; past the recorded horizon the whole cycle repeats.
        arrival_base = np.concatenate(([0.0], np.cumsum(gaps[:-1])))
        arrival_cycle = float(gaps.sum())
        pipeline = max(pipeline, 32)  # pacing must not block on replies
    idx = iter(range(total))
    idx_lock = threading.Lock()
    latencies: list[float] = []
    batch_sizes: list[int] = []
    rejects = [0]
    errors: list[Exception] = []
    out_lock = threading.Lock()
    t_sched = time.monotonic()

    def worker_sync() -> None:
        with ScoringClient(
            host, port, timeout=timeout, auth_key=auth_key
        ) as cli:
            while True:
                with idx_lock:
                    i = next(idx, None)
                if i is None:
                    return
                t0 = time.monotonic()
                try:
                    reply = cli.score(
                        text=texts[i % len(texts)], deadline_ms=deadline_ms
                    )
                except ScoreRejected:
                    with out_lock:
                        rejects[0] += 1
                    continue
                dt = time.monotonic() - t0
                with out_lock:
                    latencies.append(dt)
                    batch_sizes.append(int(reply["batch_size"]))

    def worker_pipelined() -> None:
        import collections

        def on_done(fut, t0) -> None:
            # Runs on the reader thread AT resolution — the latency is
            # send -> reply, not send -> whenever-the-sender-drained.
            dt = time.monotonic() - t0
            try:
                reply = fut.result()
            except ScoreRejected:
                with out_lock:
                    rejects[0] += 1
                return
            except Exception:
                return  # surfaced by the drain's result() below
            with out_lock:
                latencies.append(dt)
                batch_sizes.append(int(reply["batch_size"]))

        def drain(fut) -> None:
            # Backpressure + error surfacing only; recording happened in
            # the done-callback.
            try:
                fut.result(timeout=timeout)
            except ScoreRejected:
                pass

        with PipelinedScoringClient(
            host, port, timeout=timeout, auth_key=auth_key
        ) as cli:
            window: collections.deque = collections.deque()
            while True:
                with idx_lock:
                    i = next(idx, None)
                if i is None:
                    break
                if target_qps is not None:
                    # Fleet-wide schedule: request i fires at i/qps.
                    delay = (t_sched + i / target_qps) - time.monotonic()
                    if delay > 0:
                        time.sleep(delay)
                elif arrival_base is not None:
                    # Recorded schedule: request i fires at its trace
                    # offset (whole cycles past the recorded horizon).
                    n_base = len(arrival_base)
                    offset = (
                        (i // n_base) * arrival_cycle
                        + arrival_base[i % n_base]
                    )
                    delay = (t_sched + offset) - time.monotonic()
                    if delay > 0:
                        time.sleep(delay)
                t0 = time.monotonic()
                fut = cli.submit(
                    text=texts[i % len(texts)], deadline_ms=deadline_ms
                )
                fut.add_done_callback(lambda f, t0=t0: on_done(f, t0))
                window.append(fut)
                while len(window) >= pipeline:
                    drain(window.popleft())
            while window:
                drain(window.popleft())

    def worker() -> None:
        try:
            if pipeline > 1:
                worker_pipelined()
            else:
                worker_sync()
        except Exception as e:  # surface worker crashes to the caller
            with out_lock:
                errors.append(e)

    threads = [
        threading.Thread(target=worker, daemon=True)
        for _ in range(max(1, concurrency))
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout + 30.0)
    wall = max(time.monotonic() - t0, 1e-9)
    if errors:
        raise errors[0]
    lat = np.asarray(latencies, np.float64) * 1e3
    pct = (
        {f"p{p}_ms": float(np.percentile(lat, p)) for p in (50, 95, 99)}
        if lat.size
        else {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
    )
    return {
        "scored": len(latencies),
        "rejected": rejects[0],
        "wall_s": wall,
        "flows_per_sec": len(latencies) / wall,
        "target_qps": target_qps,
        "arrival_trace_len": (
            len(arrival_base) if arrival_base is not None else None
        ),
        "arrival_cycle_s": (
            arrival_cycle if arrival_base is not None else None
        ),
        "pipeline": pipeline,
        "mean_batch": float(np.mean(batch_sizes)) if batch_sizes else 0.0,
        "max_batch": max(batch_sizes, default=0),
        "batch_sizes": batch_sizes,
        **pct,
    }
