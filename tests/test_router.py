"""Serving replica fleet (ISSUE 9): the router tier (router/), the
pipelined/async SDK, the stats wire frame, and rolling hot-reload.

Contracts pinned here:

* Probabilities through the router are BIT-IDENTICAL to the replica's
  own replies (the id rewrite touches only the id bytes).
* Least-in-flight routing spreads live traffic across healthy replicas;
  drained or ejected replicas leave the pick set and readmit cleanly.
* A registry promotion against a running fleet rolling-reloads every
  replica under load with ZERO dropped requests (the zero-downtime
  deploy contract, test-scale), emits
  ``replica-drain`` spans, and records per-replica reload events on the
  registry's audit trail.
* The pipelined and async clients match replies to requests by id —
  out-of-order replies resolve the right futures.
* ``run_load(target_qps=...)`` paces the request schedule open-loop.
"""

import json
import threading
import time

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.wire import (
    WireError,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
    default_tokenizer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router import (
    FleetReplica,
    ScoringRouter,
    ServingFleet,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
    AsyncScoringClient,
    PipelinedScoringClient,
    ScoringClient,
    fetch_stats,
    protocol,
    run_load,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
)

TEXTS = [
    f"Destination port is {p}. Flow duration is {d} microseconds. "
    f"Total forward packets are {n}."
    for p, d, n in [
        (80, 100, 3),
        (443, 2500, 9),
        (8080, 7, 1),
        (53, 120000, 44),
    ]
]


@pytest.fixture(scope="module")
def tiny_setup():
    tok = default_tokenizer()
    model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
    trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
    params = trainer.init_state(seed=0).params
    params2 = trainer.init_state(seed=1).params
    return tok, model_cfg, trainer, params, params2


def _replica(tiny_setup, replica_id=0, *, params=None, round_id=1, **kw):
    tok, model_cfg, _trainer, p1, _p2 = tiny_setup
    kw.setdefault("buckets", (1, 4))
    kw.setdefault("gather_window_s", 0.002)
    return FleetReplica(
        replica_id,
        model_cfg,
        params if params is not None else p1,
        tok,
        round_id=round_id,
        **kw,
    ).start()


@pytest.fixture(scope="module")
def shared_replica(tiny_setup):
    """One warm no-auth replica reused by every single-replica test —
    each engine spin-up pays the bucket jit, so tests share it."""
    rep = _replica(tiny_setup, replica_id=7)
    yield rep
    rep.close()


def _expected_probs(tiny_setup, texts):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import (
        TokenizedSplit,
    )

    tok, model_cfg, trainer, params, _ = tiny_setup
    enc = tok.batch_encode(texts, max_len=model_cfg.max_len)
    split = TokenizedSplit(
        enc["input_ids"],
        enc["attention_mask"],
        np.zeros(len(texts), np.int32),
    )
    return trainer.evaluate(params, split, batch_size=4)["probs"]


# ----------------------------------------------------------- stats frame
def test_stats_frame_roundtrip_and_replica_id(tiny_setup, shared_replica):
    """The in-band stats probe answers from the reader thread with the
    replica's identity stamped — the router's health/telemetry source."""
    with ScoringClient("127.0.0.1", shared_replica.port) as cli:
        cli.score(text=TEXTS[0])
        s = cli.stats()
    assert s["replica"] == 7
    assert s["scored"] >= 1
    assert s["round"] == 1


def test_frame_id_and_rewrite_unit():
    """The router's id remap: fast-path splice and JSON fallback both
    preserve every non-id byte's VALUE; non-scoring frames refuse."""
    rep = protocol.build_reply(
        3,
        prob=0.123456789012345,
        threshold=0.5,
        round_id=9,
        batch_size=2,
        bucket=4,
        queue_ms=1.25,
    )
    out = protocol.rewrite_id(rep, 77)
    body = protocol.parse_reply(out)
    assert body["id"] == 77
    assert body["prob"] == 0.123456789012345  # bit-exact double
    assert protocol.frame_id(out) == 77
    # Rejects and stats frames remap too (everything the router relays).
    rej = protocol.rewrite_id(
        protocol.build_reject(5, code=503, reason="x"), 6
    )
    assert protocol.parse_reject(rej)["id"] == 6
    st = protocol.rewrite_id(protocol.build_stats_request(1), 2)
    assert protocol.parse_stats_request(st)["id"] == 2
    # Non-canonical body (id not leading) takes the JSON fallback.
    weird = rep[:4] + json.dumps(
        {"prob": 0.5, "id": 3, "prediction": 1, "round": 0, "batch_size": 1}
    ).encode()
    assert protocol.frame_id(weird) == 3
    assert protocol.parse_reply(protocol.rewrite_id(weird, 8))["id"] == 8
    with pytest.raises(WireError):
        protocol.frame_id(b"XXXX{}")
    with pytest.raises(WireError):
        protocol.rewrite_id(b"XXXX{}", 1)


# ------------------------------------------------------------ the router
def test_router_routes_bit_exact_spreads_and_drains(tiny_setup):
    """Two replicas behind the router: replies through the router are
    bit-identical to the predict pipeline's probabilities, concurrent
    load reaches BOTH replicas (least-in-flight), and a drained replica
    leaves the pick set until readmitted."""
    reps = [_replica(tiny_setup, i) for i in range(2)]
    router = ScoringRouter(
        [("127.0.0.1", r.port) for r in reps], probe_interval_s=0.2
    )
    try:
        router.start()
        want = _expected_probs(tiny_setup, TEXTS)
        with ScoringClient("127.0.0.1", router.port) as cli:
            for text, p in zip(TEXTS, want):
                reply = cli.score(text=text)
                assert reply["prob"] == float(np.float32(p))
                assert reply["round"] == 1
        # Concurrent fan-out: both replicas score.
        stats = run_load(
            "127.0.0.1", router.port, TEXTS, concurrency=4,
            requests=32, pipeline=4,
        )
        assert stats["scored"] == 32 and stats["rejected"] == 0
        per_rep = [
            fetch_stats("127.0.0.1", r.port)["scored"] for r in reps
        ]
        assert all(n > 0 for n in per_rep), per_rep
        # Drain replica 0: new traffic avoids it; readmit restores it.
        router.drain(0)
        assert router.wait_drained(0, timeout=10.0)
        before = fetch_stats("127.0.0.1", reps[0].port)["scored"]
        run_load(
            "127.0.0.1", router.port, TEXTS, concurrency=2, requests=8
        )
        assert fetch_stats("127.0.0.1", reps[0].port)["scored"] == before
        router.undrain(0)
        with ScoringClient("127.0.0.1", router.port) as cli:
            s = cli.stats()
        assert s["kind"] == "router" and s["healthy"] == 2
        assert not s["backends"][0]["draining"]
        # Fast-lane eject anchor: kill replica 1 — the router ejects it
        # and the survivor keeps serving (the full eject/readmit-with-
        # replacement flow rides the slow lane).
        reps[1].close()
        deadline = time.monotonic() + 10.0
        while router.stats()["healthy"] > 1:
            assert time.monotonic() < deadline, "eject never happened"
            time.sleep(0.05)
        with ScoringClient("127.0.0.1", router.port) as cli:
            assert cli.score(text=TEXTS[0])["round"] == 1
        assert router.stats()["backends"][1]["ejects"] >= 1
    finally:
        router.close()
        for r in reps:
            r.close()


@pytest.mark.slow
def test_router_ejects_dead_replica_and_readmits(tiny_setup):
    """Killing a replica ejects it (traffic keeps flowing on the
    survivor); a replacement on the same port is readmitted by the
    prober and serves again."""
    reps = [_replica(tiny_setup, i) for i in range(2)]
    port0 = reps[0].port
    router = ScoringRouter(
        [("127.0.0.1", r.port) for r in reps],
        probe_interval_s=0.1,
        probe_timeout_s=0.5,
    )
    try:
        router.start()
        reps[0].close()  # replica 0 dies
        deadline = time.monotonic() + 10.0
        while router.stats()["healthy"] > 1:
            assert time.monotonic() < deadline, "eject never happened"
            time.sleep(0.05)
        assert router.stats()["backends"][0]["ejects"] >= 1
        # Survivor keeps serving through the router.
        with ScoringClient("127.0.0.1", router.port) as cli:
            assert cli.score(text=TEXTS[0])["round"] == 1
        # Replacement replica on the SAME port -> readmitted.
        tok, model_cfg, _t, params, _p2 = tiny_setup
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
            MicroBatcher,
            ScoreEngine,
            ScoringServer,
        )

        engine = ScoreEngine(
            model_cfg, params, pad_id=tok.pad_id, buckets=(1, 4),
            round_id=5,
        )
        replacement = ScoringServer(
            engine,
            tok,
            port=port0,
            batcher=MicroBatcher(max_batch=4, gather_window_s=0.002),
            replica_id=0,
            warmup=False,
        ).start()
        try:
            deadline = time.monotonic() + 10.0
            while router.stats()["healthy"] < 2:
                assert time.monotonic() < deadline, "readmit never happened"
                time.sleep(0.05)
            # The readmitted replica's round shows via the probe stats.
            deadline = time.monotonic() + 5.0
            while router.stats()["backends"][0]["round"] != 5:
                assert time.monotonic() < deadline
                time.sleep(0.05)
        finally:
            replacement.close()
    finally:
        router.close()
        for r in reps:
            r.close()


def test_router_auth_end_to_end(tiny_setup):
    """With a key, the chain is authenticated at every hop: keyed sync
    AND async clients -> router -> keyed replica works; a keyless client
    is refused at the router exactly as at a bare replica."""
    import asyncio

    key = b"router-secret"
    rep = _replica(tiny_setup, 0, auth_key=key)
    router = ScoringRouter(
        [("127.0.0.1", rep.port)], auth_key=key, probe_interval_s=0.2
    )
    try:
        router.start()
        with ScoringClient(
            "127.0.0.1", router.port, auth_key=key
        ) as cli:
            assert cli.score(text=TEXTS[0])["round"] == 1
        with pytest.raises(WireError, match="auth"):
            with ScoringClient("127.0.0.1", router.port) as bad:
                bad.score(text=TEXTS[0])

        async def go():
            acli = await AsyncScoringClient.connect(
                "127.0.0.1", router.port, auth_key=key
            )
            try:
                return await acli.score(text=TEXTS[1])
            finally:
                await acli.close()

        assert asyncio.run(go())["round"] == 1
    finally:
        router.close()
        rep.close()


def test_malformed_body_gets_400_not_connection_drop(shared_replica):
    """A well-framed request whose body fails validation is answered
    with an explicit 400 reject — on a router deployment many clients
    share the backend connection, so a drop would sever them all."""
    import socket as _socket

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        framing,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.wire import (
        SCORE_REQ_MAGIC,
    )

    sock = _socket.create_connection(("127.0.0.1", shared_replica.port))
    try:
        bad = SCORE_REQ_MAGIC + b'{"id":9,"text":5}'  # wrong-typed body
        framing.send_frame(sock, bad, await_ack=False)
        reply = bytes(framing.recv_frame(sock, send_ack=False))
        body = protocol.parse_reject(reply)
        assert body["id"] == 9 and body["code"] == 400
        # The connection SURVIVED: a good request still scores.
        framing.send_frame(
            sock,
            protocol.build_request(10, text=TEXTS[0]),
            await_ack=False,
        )
        good = protocol.parse_reply(
            bytes(framing.recv_frame(sock, send_ack=False))
        )
        assert good["id"] == 10
    finally:
        sock.close()


# ------------------------------------------------------- pipelined/async
def test_pipelined_client_matches_replies_by_id(tiny_setup, shared_replica):
    """Many requests in flight on one connection resolve to the RIGHT
    replies (id-matched), bit-equal to the predict pipeline."""
    want = _expected_probs(tiny_setup, TEXTS)
    with PipelinedScoringClient("127.0.0.1", shared_replica.port) as cli:
        futs = [
            cli.submit(text=TEXTS[i % len(TEXTS)]) for i in range(16)
        ]
        for i, fut in enumerate(futs):
            reply = fut.result(timeout=30)
            assert reply["prob"] == float(
                np.float32(want[i % len(TEXTS)])
            )
        # stats pipelines like any request.
        assert cli.stats(timeout=10)["scored"] >= 16


def test_async_client_concurrent_scores_bit_exact(tiny_setup, shared_replica):
    """The asyncio SDK: concurrent tasks on one connection, id-matched,
    bit-equal to the sync path; stats works."""
    import asyncio

    want = _expected_probs(tiny_setup, TEXTS)

    async def go():
        cli = await AsyncScoringClient.connect(
            "127.0.0.1", shared_replica.port
        )
        try:
            replies = await asyncio.gather(
                *(cli.score(text=t) for t in TEXTS)
            )
            stats = await cli.stats()
        finally:
            await cli.close()
        return replies, stats

    replies, stats = asyncio.run(go())
    for reply, p in zip(replies, want):
        assert reply["prob"] == float(np.float32(p))
    assert stats["scored"] >= len(TEXTS)


def test_run_load_target_qps_paces_open_loop(shared_replica):
    """target_qps issues requests on the fleet-wide schedule: the run's
    wall tracks requests/qps (not the closed loop's equilibrium) and
    every request completes."""
    qps = 40.0
    n = 80
    stats = run_load(
        "127.0.0.1", shared_replica.port, TEXTS, concurrency=4,
        requests=n, target_qps=qps,
    )
    assert stats["scored"] == n and stats["rejected"] == 0
    # Schedule spans n/qps = 2 s; allow generous slack for the box.
    assert stats["wall_s"] >= n / qps * 0.9
    assert stats["flows_per_sec"] <= qps * 1.2


# -------------------------------------------------------- rolling reload
@pytest.mark.slow
def test_rolling_reload_zero_drop_spans_and_audit(tiny_setup, tmp_path):
    """The acceptance-shaped promotion: a registry pointer move against
    a fleet under closed-loop load swaps every replica to the new round
    with ZERO rejects, emits replica-drain spans, and records one
    registry reload event per replica. An architecture-mismatched
    artifact promoted first is refused fleet-wide (pointer guard)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
        Tracer,
        load_spans,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
        ModelRegistry,
    )

    tok, model_cfg, _trainer, params, params2 = tiny_setup
    registry = ModelRegistry(str(tmp_path / "registry"))
    aid1 = registry.add(params, round_index=1, model_config=model_cfg)
    registry.promote(aid1, to="serving")
    tracer = Tracer(str(tmp_path / "fleet.jsonl"), proc="fleet")
    reps = [_replica(tiny_setup, i) for i in range(2)]
    fleet = ServingFleet(
        reps,
        registry=registry,
        probe_interval_s=0.2,
        reload_poll_s=0.1,
        tracer=tracer,
    ).start()
    try:
        # (1) Architecture guard: a mismatched artifact never swaps in.
        bad_cfg = model_cfg.replace(n_layers=model_cfg.n_layers + 1)
        bad_trainer = Trainer(bad_cfg, TrainConfig(), pad_id=tok.pad_id)
        bad_aid = registry.add(
            bad_trainer.init_state(seed=3).params,
            round_index=9,
            model_config=bad_cfg,
        )
        registry.promote(bad_aid, to="serving")
        time.sleep(0.5)
        assert fleet.stats()["reloads"] == 0
        assert [r.round_id for r in reps] == [1, 1]
        # (2) The real promotion, fired under load: zero drops.
        out = {}

        def loadgen():
            out["stats"] = run_load(
                "127.0.0.1", fleet.port, TEXTS, concurrency=4,
                requests=96, pipeline=4, timeout=60,
            )

        lt = threading.Thread(target=loadgen, daemon=True)
        lt.start()
        aid2 = registry.add(params2, round_index=2, model_config=model_cfg)
        registry.promote(aid2, to="serving")
        lt.join(timeout=90)
        assert not lt.is_alive()
        deadline = time.monotonic() + 15.0
        while fleet.stats()["reloads"] < 1:
            assert time.monotonic() < deadline, "rolling reload never ran"
            time.sleep(0.05)
        assert out["stats"]["rejected"] == 0
        assert out["stats"]["scored"] == 96
        assert [r.round_id for r in reps] == [2, 2]
        with ScoringClient("127.0.0.1", fleet.port) as cli:
            assert cli.score(text=TEXTS[0])["round"] == 2
        assert fleet.stats()["serving_artifact"] == aid2
    finally:
        fleet.close()
        for r in reps:
            r.close()
    # (3) Spans + audit trail.
    spans = load_spans([str(tmp_path / "fleet.jsonl")])
    drains = [s for s in spans if s["span"] == "replica-drain"]
    assert {s["replica"] for s in drains} == {0, 1}
    assert all(s["artifact"] == aid2 and s["round"] == 2 for s in drains)
    events = [
        json.loads(line)
        for line in (tmp_path / "registry" / "events.jsonl")
        .read_text()
        .splitlines()
    ]
    reloads = [e for e in events if e["event"] == "reload"]
    assert {e["consumer"] for e in reloads} == {"replica-0", "replica-1"}
    assert all(e["artifact"] == aid2 for e in reloads)


# ------------------------------------------------------------------- CLI
def test_router_cli_parser_wiring():
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.parser import (
        build_parser,
    )

    ap = build_parser()
    a = ap.parse_args(
        ["route", "--backend", "10.0.0.1:12380", "--backend",
         "10.0.0.2:12380", "--probe-interval", "0.5"]
    )
    assert a.fn.__name__ == "cmd_route"
    assert a.backend == ["10.0.0.1:12380", "10.0.0.2:12380"]
    assert a.probe_interval == 0.5
    a = ap.parse_args(
        ["fleet", "--registry-dir", "/tmp/reg", "--replicas", "4"]
    )
    assert a.fn.__name__ == "cmd_fleet" and a.replicas == 4
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.router import (
        _parse_backends,
    )

    assert _parse_backends(["host:1", ":2", "8.8.8.8:99"]) == [
        ("host", 1), ("127.0.0.1", 2), ("8.8.8.8", 99),
    ]
    with pytest.raises(SystemExit):
        _parse_backends(["nope"])
    with pytest.raises(SystemExit):
        _parse_backends([])
