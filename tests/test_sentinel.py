"""Sentinel plane (obs/sentinel.py): canary fixture + prober identity/
bit-stability, journal-tailing supervised drift, long-horizon retention
ring + regression verdicts, snapshot rotation, health verdict, and the
controller's SentinelLink poke."""

import json
import os

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.control.drift import (
    ErrorRateMonitor,
    SentinelLink,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.fleet import (
    HEALTH_SCHEMA,
    ScrapeHub,
    Target,
    health_verdict,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.sentinel import (
    CANARY_SCHEMA,
    RING_SCHEMA,
    SENTINEL_SCHEMA,
    VERDICT_SCHEMA,
    DEFAULT_TREND_FIELDS,
    CanaryProber,
    JournalTail,
    RetentionRing,
    Sentinel,
    load_canary_flows,
    parse_trend_field_spec,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.trace import (
    SPAN_NAMES,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
    ModelRegistry,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "canary_flows.jsonl")


# ------------------------------------------------------------------- fixture
class TestCanaryFixture:
    def test_loads_and_validates(self):
        flows = load_canary_flows(FIXTURE)
        assert len(flows) >= 10
        assert len({f.id for f in flows}) == len(flows)
        presets = {f.preset for f in flows}
        assert presets == {
            "cicids2017", "cicddos2019", "unswnb15", "cicddos2019-mc"
        }
        # Every preset ships benign AND attack truth.
        for p in presets:
            labels = {f.label for f in flows if f.preset == p}
            assert 0 in labels and any(v > 0 for v in labels), p

    def test_mc_preset_is_k_class(self):
        flows = load_canary_flows(FIXTURE, preset="cicddos2019-mc")
        assert {f.class_label for f in flows} >= {"BENIGN", "Syn"}
        assert max(f.label for f in flows) > 1  # class indices, not 0/1
        benign = [f for f in flows if f.label == 0]
        assert all(f.class_label == "BENIGN" for f in benign)

    def test_texts_match_dataset_templates(self):
        for f in load_canary_flows(FIXTURE):
            if f.preset == "unswnb15":
                assert f.text.startswith("Protocol is ")
            else:
                assert f.text.startswith("Destination port is ")
            assert f.text.endswith(".")

    def test_preset_filter_unknown_fails(self):
        with pytest.raises(ValueError, match="no canaries for preset"):
            load_canary_flows(FIXTURE, preset="nope")

    def test_foreign_and_torn_lines_fail_loudly(self, tmp_path):
        p = tmp_path / "c.jsonl"
        p.write_text('{"schema": "other-v1", "id": "x"}\n')
        with pytest.raises(ValueError, match=CANARY_SCHEMA):
            load_canary_flows(str(p))
        p.write_text('{"schema": "' + CANARY_SCHEMA + '", "id":\n')
        with pytest.raises(ValueError, match="not JSON"):
            load_canary_flows(str(p))

    def test_duplicate_id_and_bad_label_fail(self, tmp_path):
        rec = {
            "schema": CANARY_SCHEMA,
            "id": "a",
            "preset": "p",
            "label": 1,
            "text": "t",
        }
        p = tmp_path / "c.jsonl"
        p.write_text(json.dumps(rec) + "\n" + json.dumps(rec) + "\n")
        with pytest.raises(ValueError, match="duplicate"):
            load_canary_flows(str(p))
        bad = dict(rec, label=-1)
        p.write_text(json.dumps(bad) + "\n")
        with pytest.raises(ValueError, match="label"):
            load_canary_flows(str(p))

    def test_missing_field_fails(self, tmp_path):
        p = tmp_path / "c.jsonl"
        p.write_text(
            json.dumps(
                {"schema": CANARY_SCHEMA, "id": "a", "preset": "p", "label": 0}
            )
            + "\n"
        )
        with pytest.raises(ValueError, match="text"):
            load_canary_flows(str(p))


# -------------------------------------------------------------------- prober
def _registry_with_promotion(root, *, round_index=1, seed=0):
    reg = ModelRegistry(str(root))
    rng = np.random.default_rng(seed)
    params = {"w": rng.normal(size=(4,)).astype(np.float32)}
    aid = reg.add(params, round_index=round_index)
    reg.promote(aid, to="serving")
    return reg, aid


def _fake_probe(prob_by_id, round_id, *, latency_s=0.002):
    """probe_fn stub: fixed prob per canary id (by call order), one
    round id on every reply."""

    def fn(host, port, texts, **kw):
        return [
            (
                {
                    "id": i + 1,
                    "prob": prob_by_id[i],
                    "prediction": int(prob_by_id[i] >= 0.5),
                    "round": round_id,
                },
                latency_s,
            )
            for i in range(len(texts))
        ]

    return fn


class TestCanaryProber:
    def test_clean_pass_no_incidents(self, tmp_path):
        reg, _ = _registry_with_promotion(tmp_path / "reg")
        flows = load_canary_flows(FIXTURE, preset="cicids2017")
        probs = [0.1, 0.2, 0.9, 0.8]
        prober = CanaryProber(
            flows,
            "127.0.0.1",
            1,
            registry=reg,
            probe_fn=_fake_probe(probs, round_id=1),
        )
        for _ in range(3):  # stability across repeat passes
            r = prober.probe(now=1000.0)
            assert r["incidents"] == []
            assert r["mismatches"] == 0 and r["flips"] == 0
            assert r["probes"] == len(flows)
            assert r["latency_p99_ms"] == 2.0
        assert r["wrong_label"] == 0

    def test_flip_without_promotion_is_incident(self, tmp_path):
        reg, _ = _registry_with_promotion(tmp_path / "reg")
        flows = load_canary_flows(FIXTURE, preset="unswnb15")
        probs = [0.1, 0.9]
        fn = _fake_probe(probs, round_id=1)
        prober = CanaryProber(
            flows, "127.0.0.1", 1, registry=reg, probe_fn=fn
        )
        assert prober.probe(now=0.0)["flips"] == 0
        probs[0] = 0.1000001  # same artifact, different bits
        r = prober.probe(now=1.0)
        assert r["flips"] == 1
        assert r["incidents"][0]["kind"] == "score-flip"
        assert r["incidents"][0]["canary"] == flows[0].id

    def test_promotion_rekeys_no_false_fire(self, tmp_path):
        reg, _ = _registry_with_promotion(tmp_path / "reg", round_index=1)
        flows = load_canary_flows(FIXTURE, preset="unswnb15")
        probs = [0.1, 0.9]
        prober = CanaryProber(
            flows,
            "127.0.0.1",
            1,
            registry=reg,
            probe_fn=_fake_probe(probs, round_id=1),
        )
        assert prober.probe(now=0.0)["incidents"] == []
        # A NEW artifact is promoted and the replica swaps with it: the
        # scores legitimately change — no incident.
        rng = np.random.default_rng(7)
        aid2 = reg.add(
            {"w": rng.normal(size=(4,)).astype(np.float32)}, round_index=2
        )
        reg.promote(aid2, to="serving")
        prober._probe_fn = _fake_probe([0.4, 0.6], round_id=2)
        r = prober.probe(now=1.0)
        assert r["flips"] == 0 and r["mismatches"] == 0
        assert r["incidents"] == []

    def test_stale_pointer_fires_mismatch(self, tmp_path):
        reg, _ = _registry_with_promotion(tmp_path / "reg", round_index=1)
        flows = load_canary_flows(FIXTURE, preset="unswnb15")
        prober = CanaryProber(
            flows,
            "127.0.0.1",
            1,
            registry=reg,
            probe_fn=_fake_probe([0.1, 0.9], round_id=1),
        )
        assert prober.probe(now=0.0)["mismatches"] == 0
        # Registry advances; the replica keeps answering for round 1.
        rng = np.random.default_rng(8)
        aid2 = reg.add(
            {"w": rng.normal(size=(4,)).astype(np.float32)}, round_index=2
        )
        reg.promote(aid2, to="serving")
        r = prober.probe(now=1.0)
        assert r["mismatches"] == len(flows)
        assert all(
            i["kind"] == "pointer-mismatch"
            and i["reply_round"] == 1
            and i["expected_round"] == 2
            for i in r["incidents"]
        )

    def test_down_tier_counts_failures_never_raises(self):
        flows = load_canary_flows(FIXTURE, preset="unswnb15")

        def boom(*a, **k):
            raise ConnectionRefusedError("down")

        prober = CanaryProber(flows, "127.0.0.1", 1, probe_fn=boom)
        r = prober.probe(now=0.0)
        assert r["failures"] == len(flows)
        assert r["incidents"][0]["kind"] == "probe-failure"

    def test_rejected_reply_counts_not_flips(self):
        flows = load_canary_flows(FIXTURE, preset="unswnb15")

        def fn(host, port, texts, **kw):
            return [
                (
                    {
                        "rejected": True,
                        "code": 2,
                        "reason": "deadline",
                        "prob": float("nan"),
                        "prediction": 0,
                        "round": None,
                    },
                    0.001,
                )
                for _ in texts
            ]

        prober = CanaryProber(flows, "127.0.0.1", 1, probe_fn=fn)
        for _ in range(2):
            r = prober.probe(now=0.0)
        assert r["flips"] == 0  # NaN never enters bit-stability tracking
        assert r["failures"] == len(flows)

    def test_span_names_registered(self):
        assert "canary-probe" in SPAN_NAMES
        assert "sentinel-eval" in SPAN_NAMES
        assert "regression-fire" in SPAN_NAMES


# -------------------------------------------------------------- journal tail
def _write_lines(path, recs):
    with open(path, "a") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


class TestJournalTail:
    def _tail(self, tmp_path, **kw):
        scored = str(tmp_path / "scored.jsonl")
        journal = str(tmp_path / "journal.jsonl")
        open(scored, "w").close()
        open(journal, "w").close()
        monitor = ErrorRateMonitor(
            reference_error=0.05, margin=0.1, min_joined=8
        )
        return (
            JournalTail(scored, journal, monitor=monitor, **kw),
            scored,
            journal,
        )

    def test_joins_in_both_arrival_orders(self, tmp_path):
        tail, scored, journal = self._tail(tmp_path)
        _write_lines(
            scored,
            [{"schema": "fedtpu-scored-v1", "rid": "a", "prob": 0.9}],
        )
        _write_lines(
            journal,
            [
                {"schema": "fedtpu-label-v1", "rid": "a", "label": 1, "ts": 1.0},
                # label BEFORE its score:
                {"schema": "fedtpu-label-v1", "rid": "b", "label": 0, "ts": 2.0},
            ],
        )
        st = tail.poll(now=10.0)
        assert st["joined"] == 1 and st["unmatched_labels"] == 1
        _write_lines(
            scored,
            [{"schema": "fedtpu-scored-v1", "rid": "b", "prob": 0.2}],
        )
        st = tail.poll(now=11.0)
        assert st["joined"] == 2 and st["unmatched_labels"] == 0
        assert st["window_error"] == 0.0  # both predictions correct

    def test_watermark_advances_monotone(self, tmp_path):
        tail, _, journal = self._tail(tmp_path)
        _write_lines(
            journal,
            [
                {"schema": "fedtpu-label-v1", "watermark": 5.0},
                {"schema": "fedtpu-label-v1", "watermark": 3.0},
            ],
        )
        assert tail.poll(now=0.0)["watermark"] == 5.0

    def test_drift_fires_and_journals_verdict(self, tmp_path):
        verdicts = str(tmp_path / "verdicts.jsonl")
        tail, scored, journal = self._tail(
            tmp_path, verdicts_jsonl=verdicts
        )
        # 10 joined flows all WRONG: error 1.0 >> 0.05 + 0.1.
        _write_lines(
            scored,
            [
                {"schema": "fedtpu-scored-v1", "rid": f"r{i}", "prob": 0.9}
                for i in range(10)
            ],
        )
        _write_lines(
            journal,
            [
                {"schema": "fedtpu-label-v1", "rid": f"r{i}", "label": 0, "ts": float(i)}
                for i in range(10)
            ],
        )
        st = tail.poll(now=100.0)
        assert st["verdict"] is not None
        assert st["verdict"]["schema"] == VERDICT_SCHEMA
        assert st["verdict"]["method"] == "error_rate"
        assert st["fires"] == 1
        lines = [
            json.loads(line)
            for line in open(verdicts).read().splitlines()
        ]
        assert len(lines) == 1 and lines[0]["error"] == 1.0
        # Quiet after the fire (window reset, nothing new joined).
        assert tail.poll(now=101.0)["verdict"] is None

    def test_clean_traffic_never_fires(self, tmp_path):
        tail, scored, journal = self._tail(tmp_path)
        _write_lines(
            scored,
            [
                {"schema": "fedtpu-scored-v1", "rid": f"r{i}", "prob": 0.9}
                for i in range(20)
            ],
        )
        _write_lines(
            journal,
            [
                {"schema": "fedtpu-label-v1", "rid": f"r{i}", "label": 1, "ts": float(i)}
                for i in range(20)
            ],
        )
        st = tail.poll(now=0.0)
        assert st["verdict"] is None and st["joined"] == 20


# ------------------------------------------------------------ retention ring
class TestRetentionRing:
    def test_stride_downsampling_and_bound(self, tmp_path):
        ring = RetentionRing(
            str(tmp_path / "ring.jsonl"),
            max_records=8,
            stride=3,
            baseline_n=2,
            window_n=2,
        )
        for i in range(60):
            ring.note({"latency_p99_ms": float(i)}, now=float(i))
        recs = ring.records
        assert len(recs) == 8  # bounded
        assert all(r["schema"] == RING_SCHEMA for r in recs)
        assert all(r["ts"] % 3 == 0 for r in recs)  # every 3rd kept

    def test_disk_compaction_atomic_roll(self, tmp_path):
        path = str(tmp_path / "ring.jsonl")
        ring = RetentionRing(
            path, max_records=4, stride=1, baseline_n=2, window_n=2
        )
        for i in range(40):
            ring.note({"latency_p99_ms": 1.0}, now=float(i))
        n_lines = len(open(path).read().splitlines())
        assert n_lines <= 2 * 4  # file bounded at ~2x the ring
        assert not [
            p for p in os.listdir(tmp_path) if ".tmp." in p
        ]  # roll left no debris

    def test_restart_resumes_pinned_baseline(self, tmp_path):
        path = str(tmp_path / "ring.jsonl")
        ring = RetentionRing(
            path, max_records=16, baseline_n=3, window_n=2
        )
        for i in range(5):
            ring.note({"latency_p99_ms": 10.0}, now=float(i))
        assert ring.baseline_pinned
        ring2 = RetentionRing(
            path, max_records=16, baseline_n=3, window_n=2
        )
        assert ring2.baseline_pinned  # survived the restart
        assert len(ring2.records) == 5

    def test_trend_fires_up_once_per_excursion(self):
        ring = RetentionRing(max_records=64, baseline_n=4, window_n=4)
        for i in range(8):
            ring.note({"latency_p99_ms": 10.0}, now=float(i))
        assert ring.trend() == []  # current window still at baseline
        for i in range(8, 12):
            ring.note({"latency_p99_ms": 100.0}, now=float(i))
        fired = ring.trend()
        assert len(fired) == 1
        f = fired[0]
        assert f["field"] == "latency_p99_ms"
        assert f["baseline"] == 10.0 and f["now"] == 100.0
        assert ring.trend() == []  # one fire per excursion, not per tick
        # Recovery re-arms...
        for i in range(12, 20):
            ring.note({"latency_p99_ms": 10.0}, now=float(i))
        assert ring.trend() == []
        # ...and a second excursion fires again.
        for i in range(20, 24):
            ring.note({"latency_p99_ms": 100.0}, now=float(i))
        assert len(ring.trend()) == 1

    def test_cadence_regresses_downward(self):
        ring = RetentionRing(max_records=64, baseline_n=3, window_n=3)
        for i in range(6):
            ring.note({"round_cadence": 2.0}, now=float(i))
        for i in range(6, 9):
            ring.note({"round_cadence": 0.1}, now=float(i))
        fired = ring.trend()
        assert [f["field"] for f in fired] == ["round_cadence"]
        assert fired[0]["direction"] == "down"

    def test_no_baseline_no_verdict(self):
        ring = RetentionRing(max_records=16, baseline_n=8, window_n=4)
        for i in range(5):
            ring.note({"latency_p99_ms": 500.0}, now=float(i))
        assert ring.trend() == []  # baseline still filling

    def test_always_slow_fleet_never_self_regresses(self):
        ring = RetentionRing(max_records=64, baseline_n=4, window_n=4)
        for i in range(40):
            ring.note({"latency_p99_ms": 400.0}, now=float(i))
        assert ring.trend() == []

    def test_bad_config_fails(self):
        with pytest.raises(ValueError, match="max_records"):
            RetentionRing(max_records=2, baseline_n=8, window_n=4)
        with pytest.raises(ValueError, match="stride"):
            RetentionRing(max_records=16, stride=0)


# --------------------------------------------------- hub rotation + verdict
class TestSnapshotRotation:
    def test_bounded_snapshot_rolls_atomically(self, tmp_path):
        path = str(tmp_path / "snap.jsonl")
        hub = ScrapeHub(
            [Target(tier="serve", host="127.0.0.1", port=1)],
            snapshot_jsonl=path,
            snapshot_max_mb=0.001,  # ~1 KB: a few polls cross it
            scrape_timeout_s=0.05,
        )
        for i in range(8):
            hub.poll(now=float(i))
        assert os.path.exists(path + ".1")  # rolled generation
        live = os.path.getsize(path)
        assert live <= 2 * 1024 * 1024
        # Both generations hold intact JSON lines (atomic roll).
        for p in (path, path + ".1"):
            for line in open(p).read().splitlines():
                assert json.loads(line)["schema"] == "fedtpu-fleet-v1"

    def test_unbounded_default_unchanged(self, tmp_path):
        path = str(tmp_path / "snap.jsonl")
        hub = ScrapeHub(
            [Target(tier="serve", host="127.0.0.1", port=1)],
            snapshot_jsonl=path,
            scrape_timeout_s=0.05,
        )
        hub.poll(now=0.0)
        hub.poll(now=1.0)
        assert not os.path.exists(path + ".1")
        assert len(open(path).read().splitlines()) == 2

    def test_bad_cap_fails(self):
        with pytest.raises(ValueError, match="snapshot_max_mb"):
            ScrapeHub(
                [Target(tier="serve", host="127.0.0.1", port=1)],
                snapshot_jsonl="x.jsonl",
                snapshot_max_mb=0.0,
            )


class TestHealthVerdict:
    def test_mirrors_snapshot_judgement(self, tmp_path):
        hub = ScrapeHub(
            [Target(tier="serve", host="127.0.0.1", port=1)],
            scrape_timeout_s=0.05,
        )
        snap = hub.poll(now=0.0)
        v = health_verdict(snap)
        assert v["schema"] == HEALTH_SCHEMA
        assert v["healthy"] is False  # the target is down
        assert v["targets"] == 1 and v["targets_up"] == 0
        assert v["targets_down"][0]["tier"] == "serve"
        assert v["slo_firing"] == []
        json.dumps(v)  # fully serializable for cron/CI consumers

    def test_healthy_shape(self):
        v = health_verdict(
            {
                "ts": 1.0,
                "targets": [
                    {
                        "tier": "serve",
                        "instance": "h:1",
                        "up": True,
                        "error": None,
                    }
                ],
                "slo": [
                    {
                        "slo": "x",
                        "instance": "h:1",
                        "firing": False,
                        "severity": "page",
                        "burn": {},
                    }
                ],
                "scrape_lag_ms": 1.5,
            }
        )
        assert v["healthy"] is True
        assert v["slo_total"] == 1 and v["notable"] == []


# -------------------------------------------------------------- sentinel link
class TestSentinelLink:
    def test_skips_preexisting_verdicts(self, tmp_path):
        path = str(tmp_path / "verdicts.jsonl")
        old = {
            "schema": VERDICT_SCHEMA,
            "drift": 0.5,
            "method": "error_rate",
            "scores": 64,
        }
        _write_lines(path, [old])
        link = SentinelLink(path)
        assert link.poll() is None  # history is not a fresh trigger
        new = dict(old, drift=0.7)
        _write_lines(path, [new])
        got = link.poll()
        assert got is not None and got["drift"] == 0.7
        assert link.poll() is None  # consumed

    def test_missing_file_then_created(self, tmp_path):
        path = str(tmp_path / "nope.jsonl")
        link = SentinelLink(path)
        assert link.poll() is None
        _write_lines(
            path,
            [{"schema": VERDICT_SCHEMA, "drift": 0.1, "method": "error_rate"}],
        )
        assert link.poll()["drift"] == 0.1

    def test_foreign_and_torn_lines_skipped(self, tmp_path):
        path = str(tmp_path / "verdicts.jsonl")
        open(path, "w").close()
        link = SentinelLink(path)
        with open(path, "a") as f:
            f.write('{"schema": "other"}\n')
            f.write("not json\n")
            f.write(
                json.dumps(
                    {
                        "schema": VERDICT_SCHEMA,
                        "drift": 0.3,
                        "method": "error_rate",
                    }
                )
                + "\n"
            )
            f.write('{"torn')  # no newline — waits for the next poll
        got = link.poll()
        assert got["drift"] == 0.3 and link.seen == 1

    def test_latest_verdict_wins_per_poll(self, tmp_path):
        path = str(tmp_path / "verdicts.jsonl")
        open(path, "w").close()
        link = SentinelLink(path)
        _write_lines(
            path,
            [
                {"schema": VERDICT_SCHEMA, "drift": d, "method": "error_rate"}
                for d in (0.1, 0.2, 0.3)
            ],
        )
        assert link.poll()["drift"] == 0.3  # one trigger answers all


# ---------------------------------------------------------------- composition
class TestSentinelComposition:
    def test_tick_report_and_counters(self, tmp_path):
        flows = load_canary_flows(FIXTURE, preset="unswnb15")
        probs = [0.1, 0.9]
        fn = _fake_probe(probs, round_id=None)
        prober = CanaryProber(flows, "127.0.0.1", 1, probe_fn=fn)
        ring = RetentionRing(max_records=16, baseline_n=2, window_n=2)
        alerts = str(tmp_path / "alerts.jsonl")
        s = Sentinel(prober=prober, ring=ring, alerts_jsonl=alerts)
        r1 = s.tick(now=0.0)
        assert r1["schema"] == SENTINEL_SCHEMA and r1["tick"] == 1
        assert r1["counters"]["canary_flips"] == 0
        probs[1] = 0.90001  # unexplained flip
        r2 = s.tick(now=1.0)
        assert r2["counters"]["canary_flips"] == 1
        assert s.render_status(r2)  # renders without KeyError

    def test_regression_fire_emits_alert(self, tmp_path):
        flows = load_canary_flows(FIXTURE, preset="unswnb15")
        lat = [0.002]

        def fn(host, port, texts, **kw):
            return [
                (
                    {"prob": 0.5, "prediction": 1, "round": None},
                    lat[0],
                )
                for _ in texts
            ]

        prober = CanaryProber(flows, "127.0.0.1", 1, probe_fn=fn)
        ring = RetentionRing(max_records=32, baseline_n=3, window_n=3)
        alerts = str(tmp_path / "alerts.jsonl")
        s = Sentinel(prober=prober, ring=ring, alerts_jsonl=alerts)
        for i in range(6):
            s.tick(now=float(i))
        lat[0] = 0.2  # 100x latency step
        fired = 0
        for i in range(6, 10):
            fired += len(s.tick(now=float(i))["regressions"])
        assert fired == 1
        assert s.regression_fires == 1
        evs = [
            json.loads(line) for line in open(alerts).read().splitlines()
        ]
        assert evs[0]["slo"] == "sentinel-regression"
        assert evs[0]["severity"] == "page"
        assert evs[0]["evidence"]["field"] == "latency_p99_ms"

    def test_needs_at_least_one_rung(self):
        with pytest.raises(ValueError, match="at least one rung"):
            Sentinel()

    def test_drift_rung_feeds_counters(self, tmp_path):
        scored = str(tmp_path / "scored.jsonl")
        journal = str(tmp_path / "journal.jsonl")
        verdicts = str(tmp_path / "verdicts.jsonl")
        open(scored, "w").close()
        open(journal, "w").close()
        monitor = ErrorRateMonitor(
            reference_error=0.05, margin=0.1, min_joined=8
        )
        tail = JournalTail(
            scored, journal, monitor=monitor, verdicts_jsonl=verdicts
        )
        s = Sentinel(tail=tail, ring=None, alerts_jsonl=None)
        _write_lines(
            scored,
            [
                {"schema": "fedtpu-scored-v1", "rid": f"r{i}", "prob": 0.9}
                for i in range(10)
            ],
        )
        _write_lines(
            journal,
            [
                {"schema": "fedtpu-label-v1", "rid": f"r{i}", "label": 0, "ts": float(i)}
                for i in range(10)
            ],
        )
        r = s.tick(now=0.0)
        assert r["drift"]["verdict"] is not None
        assert r["counters"]["drift_fires"] == 1
        # The verdicts file now feeds a SentinelLink end to end.
        link_path_had_content = os.path.getsize(verdicts) > 0
        assert link_path_had_content

    def test_live_serving_chain_end_to_end(self, tmp_path):
        """Every other prober test injects its ``probe_fn``. Here the
        canaries ride the real SDK, wire and scorer against the real
        registry pointer, and the drift rung joins the journal to the
        scored-JSONL the SERVER wrote: clean ticks fire nothing, a
        promotion (pointer and engine together) re-keys in silence, a
        stale replica fires a mismatch per canary, and labels opposite
        to the live answers fire the drift verdict and poke the
        controller's link."""
        import time

        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
            ModelConfig,
            TrainConfig,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
            default_tokenizer,
            make_synthetic,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
            get_dataset,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
            ScoreEngine,
            ScoringServer,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving.client import (
            probe_scores,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
            Trainer,
        )

        tok = default_tokenizer()
        model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
        trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
        params = [trainer.init_state(seed=s).params for s in range(3)]
        registry = ModelRegistry(str(tmp_path / "registry"))

        def promote(i):
            aid = registry.add(
                params[i], round_index=i + 1, model_config=model_cfg
            )
            registry.promote(aid, to="serving")

        promote(0)
        scored, journal, verdicts = (
            str(tmp_path / n)
            for n in ("scored.jsonl", "journal.jsonl", "verdicts.jsonl")
        )
        for p in (scored, journal):
            open(p, "w").close()
        spec = get_dataset("cicids2017")
        engine = ScoreEngine(
            model_cfg, params[0], pad_id=tok.pad_id, buckets=(1, 8), round_id=1
        )
        flows = load_canary_flows(FIXTURE, preset="cicids2017")
        with ScoringServer(
            engine, tok, spec=spec, scored_jsonl=scored, idle_tick_s=0.01
        ) as server:
            link = SentinelLink(verdicts)
            s = Sentinel(
                prober=CanaryProber(
                    flows, "127.0.0.1", server.port, registry=registry
                ),
                tail=JournalTail(
                    scored,
                    journal,
                    monitor=ErrorRateMonitor(
                        reference_error=0.05, margin=0.2, min_joined=32
                    ),
                    verdicts_jsonl=verdicts,
                ),
            )
            for _ in range(3):
                report = s.tick()
                assert report["canary"]["probes"] == len(flows)
                assert report["canary"]["incidents"] == []
            promote(1)
            engine.swap(params[1], round_id=2)
            assert s.tick()["canary"]["incidents"] == []
            assert s.canary_flips == 0 and s.drift_fires == 0
            promote(2)  # the registry advances, the replica does not
            stale = s.tick()["canary"]
            assert stale["mismatches"] == len(flows)
            assert {i["kind"] for i in stale["incidents"]} == {"pointer-mismatch"}
            engine.swap(params[2], round_id=3)
            assert s.tick()["canary"]["incidents"] == []
            texts = spec.render_texts(make_synthetic("cicids2017", 48, seed=1))
            replies = probe_scores("127.0.0.1", server.port, texts)
            _write_lines(
                journal,
                [
                    {
                        "schema": "fedtpu-label-v1",
                        "rid": str(reply["id"]),
                        "label": 1 - int(reply["prediction"]),
                        "ts": time.time(),
                    }
                    for reply, _latency in replies
                ],
            )
            assert s.tick()["drift"]["verdict"] is not None
            assert s.drift_fires == 1
            assert link.poll()["method"] == "error_rate"


# -------------------------------------------------------- custom trend fields
class TestCustomTrendFields:
    def test_parse_trend_field_spec(self):
        assert parse_trend_field_spec("my_counter") == (
            "my_counter", (1.5, 0.0, "up"),
        )
        assert parse_trend_field_spec(
            "fedtpu_server_stream_fallbacks_total:down"
        ) == ("fedtpu_server_stream_fallbacks_total", (1.5, 0.0, "down"))
        with pytest.raises(ValueError, match="NAME"):
            parse_trend_field_spec(":up")
        with pytest.raises(ValueError, match="up.down"):
            parse_trend_field_spec("x:sideways")

    def test_custom_field_rides_snapshot_cadence_and_fires(self, tmp_path):
        """A --trend-field counter is pulled from the fleet snapshot's
        per-target cadence dicts (max across targets) into the ring row
        and judged by the same baseline/window arithmetic as the stock
        fields — a rate step past baseline*ratio fires exactly once."""
        name, entry = parse_trend_field_spec(
            "fedtpu_server_stream_fallbacks_total"
        )
        ring = RetentionRing(
            max_records=32, baseline_n=3, window_n=3,
            trend_fields={**DEFAULT_TREND_FIELDS, name: entry},
        )
        rate = [1.0]

        class FakeHub:
            def poll(self, *, now):
                return {
                    "targets": [
                        {"up": True, "cadence": {name: rate[0] / 2}},
                        # Hottest instance wins the row.
                        {"up": True, "cadence": {name: rate[0]}},
                        {"up": True, "cadence": {}},  # quiet: no sample
                    ],
                    "slo": [],
                }

        s = Sentinel(
            ring=ring, hub=FakeHub(),
            alerts_jsonl=str(tmp_path / "alerts.jsonl"),
        )
        for i in range(6):
            assert s.tick(now=float(i))["regressions"] == []
        rate[0] = 100.0
        fired = []
        for i in range(6, 10):
            fired += s.tick(now=float(i))["regressions"]
        assert [f["field"] for f in fired] == [name]
        assert fired[0]["direction"] == "up"
        # Baseline mean is the hottest target's 1.0 (max across targets
        # — the half-rate sibling never drags it to 0.5), and the fire
        # crossed baseline * ratio.
        assert fired[0]["baseline"] == 1.0
        assert fired[0]["now"] > 1.5
