"""Cross-tier observability (obs/): span-ID propagation over a live
loopback round, the Prometheus /metrics endpoint, per-round timeline
attribution, and the Chrome trace-event export.

All host-side (sockets + JSONL + stdlib HTTP) — no JAX programs — so the
whole module stays in the fast lane.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.client import (
    FederatedClient,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.server import (
    AggregationServer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
    MetricsRegistry,
    MetricsServer,
    Tracer,
    chrome_trace,
    default_registry,
    export_chrome_trace,
    group_rounds,
    load_spans,
    round_summaries,
    timeline_table,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.trace import (
    SCHEMA,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
    protocol,
)

N_CLIENTS = 2
LOCAL_SLEEP_S = 0.12  # simulated local training; dominates the round wall


@pytest.fixture(scope="module")
def live_round(tmp_path_factory):
    """One traced loopback round: a real AggregationServer + N real
    FederatedClients, every process writing its own span JSONL — the
    exact multi-file layout `fedtpu obs` merges."""
    trace_dir = tmp_path_factory.mktemp("obs-spans")
    server = AggregationServer(
        port=0,
        num_clients=N_CLIENTS,
        timeout=30,
        tracer=Tracer(str(trace_dir / "server.jsonl"), proc="server"),
    )
    result: dict = {}

    def run_server():
        result["agg"] = server.serve_round()

    def run_client(cid: int):
        fc = FederatedClient(
            "127.0.0.1",
            server.port,
            client_id=cid,
            timeout=30,
            tracer=Tracer(
                str(trace_dir / f"client{cid}.jsonl"), proc=f"client-{cid}"
            ),
        )
        t0 = time.time()
        time.sleep(LOCAL_SLEEP_S)  # stand-in for the local training phase
        fc.note_local_phase(t0, time.time() - t0, client=cid)
        fc.exchange({"w": np.full(64, cid + 1.0, np.float32)}, n_samples=10)
        result[f"trace{cid}"] = fc.last_trace

    st = threading.Thread(target=run_server)
    cts = [
        threading.Thread(target=run_client, args=(c,))
        for c in range(N_CLIENTS)
    ]
    st.start()
    for t in cts:
        t.start()
    for t in cts:
        t.join(timeout=60)
    st.join(timeout=60)
    server.close()
    spans = load_spans(trace_dir=str(trace_dir))
    return {
        "dir": str(trace_dir),
        "spans": spans,
        "server": server,
        **result,
    }


# ------------------------------------------------------- span propagation
def test_span_ids_propagate_across_the_wire(live_round):
    """The acceptance contract: server and every client agree on the
    round's (trace, round) identity — the id crossed the wire in the
    reply meta, not via any shared process state."""
    spans = live_round["spans"]
    assert spans, "no spans written"
    traced = [s for s in spans if s.get("trace")]
    trace_ids = {s["trace"] for s in traced}
    assert len(trace_ids) == 1  # one round -> exactly one trace id
    (tid,) = trace_ids
    # Both clients adopted the server's id (returned via last_trace too).
    for c in range(N_CLIENTS):
        assert live_round[f"trace{c}"] == (tid, 0)
    # Every tier's file contributed spans under that identity.
    procs = {s["proc"] for s in traced}
    assert procs == {"server", *(f"client-{c}" for c in range(N_CLIENTS))}
    by_proc = {p: {s["span"] for s in traced if s["proc"] == p} for p in procs}
    assert {"round", "agg", "wire-reply"} <= by_proc["server"]
    for c in range(N_CLIENTS):
        assert by_proc[f"client-{c}"] == {
            "client-local", "wire-upload", "wire-reply",
        }
    # All spans agree on the round index and carry the schema tag.
    assert {s.get("round") for s in traced} == {0}
    assert all(s["schema"] == SCHEMA for s in spans)
    assert all(s.get("run_id") for s in spans)


def test_untraced_client_still_interoperates():
    """A client with no tracer against a tracing server: the exchange is
    unchanged (the trace rides optional meta) and the client still
    LEARNS the round identity via last_trace."""
    server = AggregationServer(port=0, num_clients=1, timeout=30)
    out = {}
    st = threading.Thread(target=lambda: out.update(agg=server.serve_round()))
    st.start()
    fc = FederatedClient("127.0.0.1", server.port, client_id=0, timeout=30)
    agg = fc.exchange({"w": np.ones(8, np.float32)})
    st.join(timeout=60)
    server.close()
    np.testing.assert_allclose(agg["w"], np.ones(8))
    trace_id, rnd = fc.last_trace
    assert isinstance(trace_id, str) and len(trace_id) == 16
    assert rnd == 0


# ------------------------------------------------------------- timeline
def test_timeline_attributes_round_wall(live_round):
    """compute + upload + wait + agg + reply reconstructs each client's
    measured round wall within 10% (the acceptance bound), and the
    simulated local phase is attributed to compute."""
    summaries = round_summaries(live_round["spans"])
    assert len(summaries) == 1
    b = summaries[0]
    assert b["round"] == 0
    assert len(b["clients"]) == N_CLIENTS
    for proc, row in b["clients"].items():
        assert row["measured_s"] > 0
        err = abs(row["attributed_s"] - row["measured_s"]) / row["measured_s"]
        assert err < 0.10, (proc, row)
        # The 120 ms simulated local phase landed in compute, not wait.
        assert row["compute_s"] == pytest.approx(LOCAL_SLEEP_S, rel=0.5)
    assert b["slowest_span"] is not None
    table = timeline_table(live_round["spans"])
    assert "compute" in table and "wait" in table and "slowest span" in table
    for c in range(N_CLIENTS):
        assert f"client-{c}" in table


def test_server_phase_seconds_accounting(live_round):
    """The always-on comm/compute breakdown (the timeline's and
    /metrics' source): wait/agg/reply are all populated and wait dominates
    a round whose wall is the clients' local phases."""
    phases = live_round["server"].phase_seconds
    assert set(phases) == {"wait", "agg", "reply"}
    assert phases["wait"] >= LOCAL_SLEEP_S  # straggler wait >= local sim
    assert phases["agg"] > 0 and phases["reply"] > 0
    assert phases["wait"] > phases["agg"]


# ---------------------------------------------------------- chrome export
def test_chrome_trace_export_roundtrips(live_round, tmp_path):
    path = export_chrome_trace(
        live_round["spans"], str(tmp_path / "trace.json")
    )
    with open(path) as f:
        doc = json.load(f)  # the acceptance check: valid JSON round-trip
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert len(xs) == len(live_round["spans"])
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # Metadata names every process lane.
    names = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert names == {"server", *(f"client-{c}" for c in range(N_CLIENTS))}


def test_client_phase_spans_monotonic_non_overlapping(live_round):
    """Per client: client-local -> wire-upload -> wire-reply are strictly
    ordered and non-overlapping (the phases are sequential by
    construction; overlap would mean the clocks/durations are wrong)."""
    spans = live_round["spans"]
    for c in range(N_CLIENTS):
        mine = sorted(
            (s for s in spans if s.get("proc") == f"client-{c}"),
            key=lambda s: s["ts"],
        )
        assert [s["span"] for s in mine] == [
            "client-local", "wire-upload", "wire-reply",
        ]
        for prev, nxt in zip(mine, mine[1:]):
            # 2 ms slack: ts comes from time.time(), durations from the
            # monotonic clock; sub-ms skew between them is expected.
            assert nxt["ts"] >= prev["ts"] + prev["dur_s"] - 2e-3


# ------------------------------------------------------------- /metrics
def test_prometheus_endpoint_scrapes_and_parses():
    reg = MetricsRegistry()
    reg.counter("demo_rounds_total", help="rounds").inc(3)
    reg.gauge("demo_queue_depth").set(7)
    h = reg.histogram("demo_wait_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    h.observe(2.0)
    reg.counter(
        "demo_rejects_total", labels={"kind": "deadline"}
    ).inc()
    with MetricsServer(0, host="127.0.0.1", registry=reg) as srv:
        body = (
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
    assert "# TYPE demo_rounds_total counter" in body
    assert "demo_rounds_total 3" in body
    assert "demo_queue_depth 7" in body
    assert 'demo_rejects_total{kind="deadline"} 1' in body
    assert 'demo_wait_seconds_bucket{le="+Inf"} 2' in body
    assert "demo_wait_seconds_count 2" in body
    # Every sample line parses as `name[{labels}] value` with a float
    # value — the exposition-format contract a scraper depends on.
    for line in body.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)
        assert name_part.startswith("demo_")


def test_metrics_json_twin_endpoint_matches_text_rendering():
    """ISSUE 11 satellite: /metrics.json serves the SAME numbers as the
    Prometheus text format — machine-readable, schema-tagged, no
    exposition-format parser needed (the scrape hub's input)."""
    reg = MetricsRegistry()
    reg.counter("demo_rounds_total", help="rounds").inc(3)
    reg.gauge("demo_queue_depth").set(7)
    h = reg.histogram("demo_wait_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.05)
    h.observe(2.0)
    reg.counter("demo_rejects_total", labels={"kind": "deadline"}).inc()
    with MetricsServer(0, host="127.0.0.1", registry=reg) as srv:
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics.json", timeout=10
        )
        assert raw.headers["Content-Type"] == "application/json"
        doc = json.loads(raw.read())
    assert doc["schema"] == "fedtpu-metrics-v1"
    fams = doc["families"]
    assert fams["demo_rounds_total"]["type"] == "counter"
    assert fams["demo_rounds_total"]["samples"][0]["value"] == 3
    assert fams["demo_queue_depth"]["samples"][0]["value"] == 7
    # Labeled sample keeps its labels as a dict.
    (rej,) = fams["demo_rejects_total"]["samples"]
    assert rej["labels"] == {"kind": "deadline"} and rej["value"] == 1
    # Histogram buckets are CUMULATIVE [edge, count] pairs ending +Inf —
    # identical numbers to the text rendering's _bucket lines.
    (hs,) = fams["demo_wait_seconds"]["samples"]
    assert hs["buckets"] == [["0.01", 0], ["0.1", 1], ["1", 1], ["+Inf", 2]]
    assert hs["count"] == 2 and hs["sum"] == pytest.approx(2.05)
    # Twin consistency: every text sample value appears in the JSON.
    text = reg.render()
    assert 'demo_wait_seconds_bucket{le="+Inf"} 2' in text
    assert "demo_rounds_total 3" in text


def test_new_health_span_names_registered():
    """The PR-10 spans are IN the closed vocabulary (the obs-span-vocab
    static pass anchors on this tuple) and the timeline renders them as
    extra rows."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
        SPAN_NAMES,
    )

    assert {"slo-eval", "postmortem-dump", "drift-trigger"} <= set(
        SPAN_NAMES
    )
    # The REAL emission shapes: slo-eval and postmortem-dump carry NO
    # (trace, round) — they happen outside any round's identity — and
    # drift-trigger carries only the round index. The timeline must
    # render all three anyway (the unscoped trailing section / the
    # per-round extra rows), not silently drop them.
    t_spans = [
        {
            "schema": SCHEMA, "proc": "obs-hub", "span": "slo-eval",
            "ts": 1.0, "dur_s": 0.002, "firing": 1, "up": 1,
        },
        {
            "schema": SCHEMA, "proc": "server", "span": "postmortem-dump",
            "ts": 2.0, "dur_s": 0.01, "reason": "round-failure",
            "bundle": "b.json",
        },
        {
            "schema": SCHEMA, "proc": "controller", "span": "drift-trigger",
            "ts": 3.0, "dur_s": 0.0, "round": 1, "drift": 0.31,
        },
        # An anchoring round so the per-round half renders too.
        {
            "schema": SCHEMA, "proc": "server", "span": "round",
            "ts": 0.5, "dur_s": 1.0, "trace": "aa", "round": 1,
        },
    ]
    table = timeline_table(t_spans)
    assert "slo-eval" in table and "firing=1" in table
    assert "postmortem-dump" in table and "reason=round-failure" in table
    assert "drift-trigger" in table
    assert "unscoped health-plane spans" in table


def test_http_404_off_path():
    reg = MetricsRegistry()
    with MetricsServer(0, host="127.0.0.1", registry=reg) as srv:
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10
            )


def test_round_engine_feeds_default_registry(live_round):
    """The FL server's counters land on the process default registry —
    what `serve --metrics-port` exposes without extra wiring — and a
    live HTTP scrape of that registry sees the round that just ran."""
    with MetricsServer(0, host="127.0.0.1") as srv:
        body = (
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10
            )
            .read()
            .decode()
        )
    for needle in (
        "fedtpu_server_rounds_total",
        "fedtpu_server_uploads_total",
        "fedtpu_server_wire_bytes_received_total",
        'fedtpu_server_round_phase_seconds_total{phase="agg"}',
    ):
        assert needle in body

    def sample(name: str) -> float:
        for line in body.splitlines():
            if line.startswith(name + " "):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"{name} not rendered")

    assert sample("fedtpu_server_rounds_total") >= 1
    assert sample("fedtpu_server_uploads_total") >= N_CLIENTS


# ------------------------------------------------- scoring-protocol trace
def test_scoring_protocol_trace_echo():
    req = protocol.parse_request(
        protocol.build_request(7, text="flow", trace="abcd1234abcd1234")
    )
    assert req["trace"] == "abcd1234abcd1234"
    rep = protocol.parse_reply(
        protocol.build_reply(
            7,
            prob=0.25,
            threshold=0.5,
            round_id=3,
            batch_size=4,
            bucket=8,
            queue_ms=1.5,
            trace=req["trace"],
        )
    )
    assert rep["trace"] == "abcd1234abcd1234"
    # Omitted everywhere: old peers' frames carry no trace key at all.
    assert "trace" not in protocol.parse_request(
        protocol.build_request(8, text="flow")
    )
    with pytest.raises(Exception):
        protocol.parse_request(
            protocol.SCORE_REQ_MAGIC
            + json.dumps({"id": 9, "text": "x", "trace": 42}).encode()
        )


# ------------------------------------------------------------------ CLI
def test_obs_cli_timeline_and_export(live_round, tmp_path, capsys):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.parser import (
        main,
    )

    assert main(["obs", "timeline", "--trace-dir", live_round["dir"]]) == 0
    out = capsys.readouterr().out
    assert "round 0" in out and "compute" in out
    out_path = str(tmp_path / "chrome.json")
    assert (
        main(
            [
                "obs", "export", "--trace-dir", live_round["dir"],
                "--out", out_path,
            ]
        )
        == 0
    )
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    capsys.readouterr()  # drain the export's "wrote ..." line
    # JSON timeline for machines.
    assert (
        main(["obs", "timeline", "--trace-dir", live_round["dir"], "--json"])
        == 0
    )
    rounds = json.loads(capsys.readouterr().out)
    assert rounds and rounds[0]["round"] == 0


def test_obs_cli_refuses_empty_inputs(tmp_path):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.parser import (
        main,
    )

    with pytest.raises(SystemExit):
        main(["obs", "timeline", "--trace-dir", str(tmp_path)])


# ------------------------------------------------------------ grouping
def test_group_rounds_and_foreign_lines(tmp_path):
    """The merger must group on (trace, round) and skip foreign lines
    (metrics-JSONL records, truncated tails) instead of crashing."""
    p = tmp_path / "mixed.jsonl"
    t = Tracer(str(p), proc="x")
    t.record("round", t_start=1.0, dur_s=0.5, trace="aa", round=1)
    t.record("round", t_start=2.0, dur_s=0.5, trace="bb", round=2)
    with open(p, "a") as f:
        f.write(json.dumps({"phase": "serve_batch", "score_hist": [1]}) + "\n")
        f.write('{"truncated": \n')  # partial tail from a crashed writer
    spans = load_spans([str(p)])
    assert len(spans) == 2
    groups = group_rounds(spans)
    assert set(groups) == {("aa", 1), ("bb", 2)}
    assert chrome_trace(spans)["traceEvents"]


# ------------------------------------------- round pipelining attribution
def test_wire_overlap_span_and_timeline_row(tmp_path):
    """ISSUE 5: a streamed round's server emits a wire-overlap span —
    fold work that ran DURING the wire phase, with overlap_frac and
    peak_agg_bytes — the timeline surfaces it next to the exposed agg,
    and the client's wire-upload span carries its chunk/overlap attrs."""
    trace_dir = tmp_path / "stream-spans"
    trace_dir.mkdir()
    server = AggregationServer(
        port=0, num_clients=2, timeout=30, stream_chunk_bytes=8192,
        tracer=Tracer(str(trace_dir / "server.jsonl"), proc="server"),
    )
    out = {}

    def run_server():
        out["r0"] = server.serve_round()
        out["r1"] = server.serve_round()

    def run_client(cid):
        fc = FederatedClient(
            "127.0.0.1", server.port, client_id=cid, timeout=30,
            tracer=Tracer(
                str(trace_dir / f"client{cid}.jsonl"), proc=f"client-{cid}"
            ),
        )
        p = {"w": np.full(40_000, cid + 1.0, np.float32)}
        agg = fc.exchange(p, n_samples=1)
        # Buffered like the real round loop's reply-wait prefetch span.
        fc.note_phase("batch-prefetch", time.time(), 0.01, client=cid)
        fc.exchange({k: v + 1.0 for k, v in agg.items()}, n_samples=1)

    st = threading.Thread(target=run_server)
    cts = [
        threading.Thread(target=run_client, args=(c,)) for c in range(2)
    ]
    st.start()
    for t in cts:
        t.start()
    for t in cts:
        t.join(timeout=60)
    st.join(timeout=60)
    server.close()
    assert server.stream_totals["stream_uploads"] == 2

    spans = load_spans(trace_dir=str(trace_dir))
    overlaps = [s for s in spans if s["span"] == "wire-overlap"]
    assert len(overlaps) == 1  # only the streamed round overlapped
    ov = overlaps[0]
    assert ov["round"] == 1 and ov["proc"] == "server"
    assert ov["folded_bytes"] > 0 and 0.0 < ov["overlap_frac"] <= 1.0
    assert ov["peak_agg_bytes"] > 0
    # The streamed wire-upload spans carry the pipelining attrs.
    ups = [
        s for s in spans
        if s["span"] == "wire-upload" and s.get("round") == 1
    ]
    assert len(ups) == 2
    assert all(u["chunks"] > 1 and u["overlap_s"] >= 0.0 for u in ups)
    # batch-prefetch spans adopted the round identity on the next flush.
    pf = [s for s in spans if s["span"] == "batch-prefetch"]
    assert len(pf) == 2 and all(s.get("round") == 1 for s in pf)

    summaries = round_summaries(spans)
    by_round = {b["round"]: b for b in summaries}
    assert by_round[1]["overlap_s"] > 0.0
    assert by_round[1]["overlap_frac"] == ov["overlap_frac"]
    assert by_round[0]["overlap_s"] == 0.0
    table = timeline_table(spans)
    assert "wire-overlap" in table and "folded during the wire phase" in table
    assert "batch-prefetch" in table


# --------------------------------------------------------- live tailing
def test_tail_spans_follows_appends_and_new_files(tmp_path):
    """ISSUE 9 satellite: the follow-mode reader yields spans as they
    are APPENDED — pre-existing spans only under from_start, files that
    appear mid-tail picked up from their start, foreign/partial lines
    skipped."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
        tail_spans,
    )

    d = tmp_path / "tail"
    d.mkdir()
    pre = Tracer(str(d / "pre.jsonl"), proc="early")
    pre.record("round", t_start=1.0, dur_s=0.5, trace="aa", round=1)

    got: list[dict] = []
    stop_at = [8]

    def collect(**kw):
        for rec in tail_spans(
            trace_dir=str(d), poll_s=0.05,
            stop=lambda: len(got) >= stop_at[0], **kw
        ):
            got.append(rec)

    # Without from_start: the pre-existing span is NOT replayed.
    stop_at[0] = 2
    t = threading.Thread(target=collect, daemon=True)
    t.start()
    time.sleep(0.2)
    pre.record("agg", t_start=2.0, dur_s=0.1, trace="aa", round=1)
    late = Tracer(str(d / "late.jsonl"), proc="late")  # appears mid-tail
    late.record("router-forward", t_start=3.0, dur_s=0.01, replica=0)
    with open(d / "pre.jsonl", "a") as f:
        f.write('{"not": "a span"}\n')  # foreign line: skipped
    t.join(timeout=10)
    assert not t.is_alive()
    assert {r["span"] for r in got} == {"agg", "router-forward"}
    # With from_start: history replays first.
    got.clear()
    stop_at[0] = 3
    t = threading.Thread(
        target=collect, kwargs={"from_start": True}, daemon=True
    )
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert {r["span"] for r in got} == {"round", "agg", "router-forward"}
    # Per-file append order is preserved (cross-file order is by name).
    pre_spans = [r["span"] for r in got if r["proc"] == "early"]
    assert pre_spans == ["round", "agg"]


def test_obs_cli_tail_filters_and_format(tmp_path, capsys):
    """`fedtpu obs tail`: one line per span with proc/span/duration,
    --round and --trace-id filters applied, bounded by --max-seconds;
    an empty directory is NOT an error (tailing it is the point)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.parser import (
        main,
    )

    d = tmp_path / "tailcli"
    d.mkdir()
    t = Tracer(str(d / "s.jsonl"), proc="server")
    t.record("round", t_start=1.0, dur_s=0.5, trace="aa", round=1)
    t.record("agg", t_start=2.0, dur_s=0.25, trace="aa", round=1)
    t.record("replica-drain", t_start=3.0, dur_s=0.1, round=2, replica=1)
    assert (
        main(
            [
                "obs", "tail", "--trace-dir", str(d), "--from-start",
                "--max-seconds", "0.3", "--poll", "0.05",
            ]
        )
        == 0
    )
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert len(lines) == 3
    assert "server" in lines[0] and "round" in lines[0]
    assert "trace=aa" in lines[1]
    assert "replica=1" in lines[2] and "replica-drain" in lines[2]
    # --round filter
    assert (
        main(
            [
                "obs", "tail", "--trace-dir", str(d), "--from-start",
                "--round", "2", "--max-seconds", "0.3", "--poll", "0.05",
            ]
        )
        == 0
    )
    lines = [
        ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
    ]
    assert len(lines) == 1 and "replica-drain" in lines[0]
    # --trace-id filter
    assert (
        main(
            [
                "obs", "tail", "--trace-dir", str(d), "--from-start",
                "--trace-id", "aa", "--max-seconds", "0.3", "--poll",
                "0.05",
            ]
        )
        == 0
    )
    lines = [
        ln for ln in capsys.readouterr().out.splitlines() if ln.strip()
    ]
    assert len(lines) == 2
    # An empty dir tails cleanly (no spans yet — not an error).
    empty = tmp_path / "empty"
    empty.mkdir()
    assert (
        main(
            [
                "obs", "tail", "--trace-dir", str(empty),
                "--max-seconds", "0.2", "--poll", "0.05",
            ]
        )
        == 0
    )
    assert capsys.readouterr().out.strip() == ""
