"""Delayed ground-truth plane (ISSUE 18): the append-only label journal
(labels/store.py), the deterministic scored-vs-truth join
(labels/join.py), the supervised promotion rung stacked after the
shadow gate, label-aware drift (ErrorRateMonitor) and drift-scaled
cohort sizing (control/drift.py), the ranked-candidate shadow
comparator, the recorded-arrival load replay, and the K-class data
plane's K = 2 bit-identity.

Contracts pinned here:

* The journal tolerates the REAL arrival discipline: duplicates count,
  conflicts resolve last-writer-wins by caller-supplied timestamp (a
  strictly-older conflict never overwrites), labels at or under the
  watermark still apply but count as late, the watermark only moves
  forward, and ``load()`` rebuilds bit-identical state from the file.
* The supervised gate FAILS CLOSED: too few joined flows, coverage
  under the floor, or an uncomputable side are refusals, never passes
  — and a live controller round REJECTS on an empty journal, then
  PROMOTES the same candidate evidence once the delayed labels arrive.
* The K = 2 route of the class-counts plane renders metrics
  bit-identical to the binary path (same floats, same dict).
* Aggregate shadow-gate evidence covers rank 0 only; secondary ranked
  candidates ride the same mirrored traffic without diluting it.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
    AggregationServer,
    FederatedClient,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ControlConfig,
    ExperimentConfig,
    LabelsConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.control import (
    Controller,
    DriftMonitor,
    ErrorRateMonitor,
    drift_cohort_fraction,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.labels import (
    LabelGate,
    LabelStore,
    evaluate_supervised,
    join_records,
    journal_path,
    supervised_verdict,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.trace import (
    append_jsonl_line,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
    ModelRegistry,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
    load_arrival_trace,
    run_load,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.shadow.compare import (
    PAIR_SCHEMA,
    ShadowCompare,
    evaluate_status,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.shadow.gate import (
    pairs_path,
)

TRACE_FIXTURE = os.path.join(
    os.path.dirname(__file__), "data", "arrival_bursty.trace"
)


# ------------------------------------------------------------- the journal
def test_journal_lww_duplicates_conflicts_late_watermark(tmp_path):
    store = LabelStore(str(tmp_path / "journal.jsonl"))
    assert store.ingest("r1", 1, ts=1.0)
    # Same label again: a duplicate, not a conflict; state unchanged.
    assert not store.ingest("r1", 1, ts=2.0)
    # Conflicting re-label with a NEWER ts: last writer wins.
    store.ingest("r1", 0, ts=3.0)
    assert store.get("r1") == 0
    # Conflicting re-label with an OLDER ts: counted, never overwrites.
    store.ingest("r1", 1, ts=2.5)
    assert store.get("r1") == 0
    # The watermark is monotone: a stale advance is a no-op.
    assert store.advance_watermark(5.0) == 5.0
    assert store.advance_watermark(4.0) == 5.0
    assert store.watermark == 5.0
    # A label at/under the watermark still applies but counts as late.
    store.ingest("r2", 1, ts=4.0)
    assert store.get("r2") == 1
    s = store.status()
    assert s["labels"] == 2
    assert s["duplicates"] == 1
    assert s["conflicts"] == 2
    assert s["late"] == 1
    assert s["watermark"] == 5.0


def test_journal_load_replays_bit_identical_state(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    a = LabelStore(path)
    a.ingest("r1", 1, ts=1.0)
    a.ingest("r2", 0, ts=2.0)
    a.advance_watermark(3.0)
    a.ingest("r1", 0, ts=4.0)  # conflict, LWW
    a.ingest("r3", 1, ts=2.5)  # late (under the watermark)
    # Torn tail + foreign line: the replay must skip both.
    with open(path, "a") as f:
        f.write('{"schema": "other-v9", "x": 1}\n')
        f.write('{"schema": "fedtpu-label-v1", "rid": "r9"')  # torn
    b = LabelStore(path)
    b.load()
    assert b.labels_map() == a.labels_map() == {"r1": 0, "r2": 0, "r3": 1}
    assert b.watermark == a.watermark == 3.0
    sa, sb = a.status(), b.status()
    for k in ("labels", "conflicts", "late", "watermark"):
        assert sb[k] == sa[k], k


# ---------------------------------------------------------------- the join
def test_supervised_verdict_arithmetic():
    # K-class labels binarize as != 0: (pred, label) = tp, fp, fn, tn.
    v = supervised_verdict([(1, 1), (1, 0), (0, 3), (0, 0)])
    assert (v["tp"], v["fp"], v["fn"], v["tn"]) == (1, 1, 1, 1)
    assert v["accuracy"] == 0.5 and v["error"] == 0.5
    assert v["fpr"] == 0.5 and v["fnr"] == 0.5
    assert v["per_class"] == {"0": 2, "1": 1, "3": 1}
    empty = supervised_verdict([])
    assert empty["n"] == 0 and empty["error"] is None


def test_join_records_coverage_and_sides():
    labels = {"a": 1, "b": 0, "c": 1}
    records = [
        {"rid": "a", "serving_prob": 0.9, "shadow_prob": 0.2},
        {"rid": "zz", "serving_prob": 0.9, "shadow_prob": 0.9},  # unlabeled
        {"serving_prob": 0.5, "shadow_prob": 0.5},  # no rid: total only
        {"rid": "b", "serving_prob": 0.1},  # one-sided record
        {"rid": "c", "serving_prob": 0.8, "shadow_prob": 0.9, "cand": 1},
    ]
    rep = join_records(records, labels)
    assert rep["total"] == 5 and rep["joined"] == 3
    assert rep["coverage"] == pytest.approx(3 / 5)
    assert rep["models"]["serving"]["n"] == 3
    assert rep["models"]["candidate"]["n"] == 2  # the one-sided miss
    assert rep["per_candidate_joined"] == {"1": 1}
    # The scored-JSONL shape: one model, a "prob" field.
    rep2 = join_records(
        [{"rid": "a", "prob": 0.9}, {"rid": "b", "prob": 0.8}],
        labels,
        sides={"serving": "prob"},
    )
    assert rep2["joined"] == 2
    assert rep2["models"]["serving"]["fp"] == 1  # b: pred 1, label 0


def test_evaluate_supervised_fails_closed_then_rules():
    def rep(joined, total, s_err, c_err):
        return {
            "joined": joined,
            "total": total,
            "coverage": joined / total if total else 0.0,
            "models": {
                "serving": {"error": s_err},
                "candidate": {"error": c_err},
            },
        }

    kw = dict(min_joined=32, coverage_floor=0.05, max_regression=0.0)
    ok, why = evaluate_supervised(rep(8, 100, 0.0, 0.0), **kw)
    assert not ok and "insufficient" in why
    ok, why = evaluate_supervised(rep(40, 4000, 0.0, 0.0), **kw)
    assert not ok and "coverage" in why
    ok, why = evaluate_supervised(rep(40, 100, 0.0, None), **kw)
    assert not ok and "uncomputable" in why
    ok, why = evaluate_supervised(rep(40, 100, 0.01, 0.05), **kw)
    assert not ok and "regression" in why
    ok, why = evaluate_supervised(rep(40, 100, 0.05, 0.05), **kw)
    assert ok and "agreement" in why
    # A tolerated regression budget moves the bar, same arithmetic.
    ok, _ = evaluate_supervised(
        rep(40, 100, 0.01, 0.05),
        min_joined=32,
        coverage_floor=0.05,
        max_regression=0.1,
    )
    assert ok


def _write_pairs(root, aid, rows):
    """rows: (rid, serving_prob, shadow_prob, cand_rank_or_None)."""
    path = pairs_path(root, aid)
    for i, (rid, sp, cp, cand) in enumerate(rows):
        rec = {
            "schema": PAIR_SCHEMA,
            "mid": i + 1,
            "serving_prob": sp,
            "shadow_prob": cp,
            "flip": int((sp >= 0.5) != (cp >= 0.5)),
            "rid": rid,
        }
        if cand:
            rec["cand"] = cand
        append_jsonl_line(path, json.dumps(rec))


def test_label_gate_fails_closed_without_evidence(tmp_path):
    gate = LabelGate(str(tmp_path), min_joined=4)
    ok, verdict = gate.evaluate("ghost")
    assert not ok and "insufficient" in verdict["reason"]
    assert verdict["joined"] == 0 and verdict["total"] == 0


def test_label_gate_rules_on_primary_pairs_only(tmp_path):
    """Secondary ranked candidates tag their pairs with ``cand``; the
    gated verdict must cover the rank-0 candidate's pairs alone — a
    regressing SECONDARY must not fail the primary (and vice versa)."""
    root = str(tmp_path)
    aid = "cand-x"
    rows = [(f"r{i}", 0.9, 0.9, None) for i in range(40)]
    # 40 rank-1 pairs, every one a wrong answer on an attack flow: if
    # the join counted them, candidate error would jump to 0.5.
    rows += [(f"r{i}", 0.9, 0.1, 1) for i in range(40)]
    _write_pairs(root, aid, rows)
    store = LabelStore(journal_path(root))
    for i in range(40):
        store.ingest(f"r{i}", 1, ts=float(i))
    ok, verdict = LabelGate(
        root, min_joined=16, coverage_floor=0.05
    ).evaluate(aid)
    assert ok, verdict["reason"]
    assert verdict["joined"] == 40 and verdict["total"] == 40
    assert verdict["candidate_error"] == 0.0


def test_label_gate_rejects_what_the_flip_rate_gate_passes(tmp_path):
    """The pincer the supervised rung exists for, over ONE body of
    evidence: a candidate that flips 6 of 400 pairs (1.5%, under the
    unsupervised 2% budget, clean PSI) passes `evaluate_status`, and
    every flip turns a right answer into a wrong one, so the label gate
    measures the regression on the joined truth and refuses. The same
    pairs against a journal that labels 8 of the 400 fail closed on the
    coverage floor: a verdict over 2% of the flows is not a verdict."""
    root, aid, n = str(tmp_path), "cand-pincer", 400
    truth = np.arange(n) % 2
    serving = np.where(truth == 1, 0.9, 0.1)
    cand = serving.copy()
    cand[1:12:2] = 0.08  # six attack flows answered benign
    compare = ShadowCompare(threshold=0.5, pairs_jsonl=pairs_path(root, aid))
    for i in range(n):
        compare.register_rid(i, f"r{i}")
        compare.note_serving(i, float(serving[i]))
        compare.note_shadow(i, float(cand[i]))
    ok, why = evaluate_status(
        compare.snapshot(), min_pairs=100, max_flip_rate=0.02,
        psi_threshold=0.25,
    )
    assert ok, why

    store = LabelStore(journal_path(root))
    for i in range(300):  # delayed labels are partial: 75% here
        store.ingest(f"r{i}", int(truth[i]), ts=float(i))
    ok, verdict = LabelGate(
        root, min_joined=64, coverage_floor=0.05, max_regression=0.0
    ).evaluate(aid)
    assert not ok and "regression" in verdict["reason"]
    assert verdict["joined"] == 300
    assert verdict["serving_error"] == 0.0
    assert verdict["candidate_error"] == pytest.approx(6 / 300)

    sparse = str(tmp_path / "sparse.jsonl")
    thin = LabelStore(sparse)
    for i in range(8):
        thin.ingest(f"r{i}", int(truth[i]), ts=float(i))
    ok, verdict = LabelGate(
        root, journal=sparse, min_joined=4, coverage_floor=0.05,
        max_regression=0.0,
    ).evaluate(aid)
    assert not ok and "coverage" in verdict["reason"]


# ------------------------------------------------- label-aware drift plane
def test_drift_cohort_fraction_pins_both_ends_and_midpoint():
    kw = dict(threshold=0.25, min_frac=0.5, max_frac=1.0)
    assert drift_cohort_fraction(0.25, **kw) == pytest.approx(0.5)
    assert drift_cohort_fraction(0.50, **kw) == pytest.approx(1.0)
    assert drift_cohort_fraction(0.375, **kw) == pytest.approx(0.75)
    # Clamped outside the span; degenerate band returns min_frac.
    assert drift_cohort_fraction(0.10, **kw) == pytest.approx(0.5)
    assert drift_cohort_fraction(9.99, **kw) == pytest.approx(1.0)
    assert drift_cohort_fraction(
        0.9, threshold=0.25, min_frac=0.8, max_frac=0.8
    ) == pytest.approx(0.8)


def test_error_rate_monitor_lifecycle():
    em = ErrorRateMonitor(reference_error=0.02, margin=0.05, min_joined=64)
    em.observe(1, 32)
    assert em.check() is None  # too few joined flows
    em.observe(1, 32)
    assert em.check() is None  # 2/64 under reference + margin
    em.observe(10, 64)
    verdict = em.check()  # 12/128 = 0.094 >= 0.02 + 0.05
    assert verdict is not None and verdict["method"] == "error_rate"
    assert verdict["scores"] == 128
    assert verdict["drift"] == pytest.approx(12 / 128 - 0.02, abs=1e-6)
    assert em.observed_joined == 0  # fired verdict resets the window
    # Verdict-dict ingestion (labels/join.py shape) feeds the same path.
    em.observe_verdict({"n": 64, "error": 0.5})
    assert em.check() is not None
    # No reference: never fires, regardless of evidence.
    cold = ErrorRateMonitor(margin=0.05, min_joined=8)
    cold.observe(8, 8)
    assert not cold.has_reference and cold.check() is None
    with pytest.raises(ValueError):
        em.observe(5, 3)
    with pytest.raises(ValueError):
        ErrorRateMonitor(margin=0.0)


def test_labels_config_validates_and_round_trips():
    cfg = ExperimentConfig.from_dict(
        {"labels": {"min_joined": 8, "coverage_floor": 0.2}}
    )
    assert cfg.labels.min_joined == 8
    assert cfg.labels.coverage_floor == 0.2
    assert cfg.labels.journal is None
    with pytest.raises(ValueError):
        LabelsConfig(coverage_floor=1.5)
    with pytest.raises(ValueError):
        LabelsConfig(threshold=1.0)
    with pytest.raises(ValueError):
        LabelsConfig(min_joined=0)
    with pytest.raises(ValueError):
        LabelsConfig(max_regression=-0.1)
    with pytest.raises(ValueError):
        ControlConfig(cohort_min_frac=0.0)
    with pytest.raises(ValueError):
        ControlConfig(cohort_min_frac=0.8, cohort_max_frac=0.5)


# ------------------------------------------------- ranked shadow comparator
def test_shadow_compare_aggregates_rank_zero_only(tmp_path):
    pairs_jsonl = str(tmp_path / "pairs.jsonl")
    compare = ShadowCompare(
        threshold=0.5, candidates=("cand-a", "cand-b"),
        pairs_jsonl=pairs_jsonl,
    )
    compare.register_rid(1, "rid-1")
    compare.note_serving(1, 0.9)
    compare.note_shadow(1, 0.9)  # rank 0, agrees
    compare.register_rid(2, "rid-2")
    compare.note_serving(2, 0.9)
    compare.note_shadow(2, 0.1, 1)  # rank 1, flips
    s = compare.snapshot()
    # The gate's aggregate evidence: the rank-1 flip never dilutes it.
    assert s["pairs"] == 1 and s["flips"] == 0
    pc = s["per_candidate"]
    assert pc["0"] == {
        "candidate": "cand-a", "pairs": 1, "flips": 0, "flip_rate": 0.0,
    }
    assert pc["1"]["candidate"] == "cand-b"
    assert pc["1"]["pairs"] == 1 and pc["1"]["flips"] == 1
    recs = [json.loads(ln) for ln in open(pairs_jsonl)]
    by_mid = {r["mid"]: r for r in recs}
    assert "cand" not in by_mid[1] and by_mid[1]["rid"] == "rid-1"
    assert by_mid[2]["cand"] == 1 and by_mid[2]["rid"] == "rid-2"


# ---------------------------------------------------- recorded arrival load
def test_arrival_trace_fixture_parses_and_validates(tmp_path):
    gaps = load_arrival_trace(TRACE_FIXTURE)
    assert len(gaps) == 24
    assert sum(gaps) == pytest.approx(0.17)
    assert min(gaps) >= 0.0
    empty = tmp_path / "empty.trace"
    empty.write_text("# nothing but comments\n\n")
    with pytest.raises(ValueError):
        load_arrival_trace(str(empty))
    neg = tmp_path / "neg.trace"
    neg.write_text("0.01\n-0.5\n")
    with pytest.raises(ValueError):
        load_arrival_trace(str(neg))
    with pytest.raises(ValueError):
        run_load(
            "127.0.0.1", 1, ["x"], target_qps=10.0, arrival_trace=gaps
        )
    with pytest.raises(ValueError):
        run_load("127.0.0.1", 1, ["x"], arrival_trace=[])


def test_run_load_replays_bursty_trace_open_loop(tmp_path):
    """The recorded schedule actually paces the send side: a run whose
    requests span two trace cycles takes at least the recorded offsets
    of wall time (open loop — reply speed does not compress it)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ModelConfig,
        TrainConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        MicroBatcher,
        ScoreEngine,
        ScoringServer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        Trainer,
    )

    tok = default_tokenizer()
    model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
    trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
    params = trainer.init_state(seed=0).params
    engine = ScoreEngine(
        model_cfg, params, pad_id=tok.pad_id, buckets=(1, 4), round_id=0
    )
    gaps = load_arrival_trace(TRACE_FIXTURE)
    batcher = MicroBatcher(max_batch=4, max_queue=64, gather_window_s=0.002)
    with ScoringServer(
        engine, tok, batcher=batcher, idle_tick_s=0.01
    ) as server:
        stats = run_load(
            "127.0.0.1",
            server.port,
            ["Destination port is 80. Flow duration is 100 microseconds."],
            concurrency=1,
            requests=48,
            arrival_trace=gaps,
            timeout=30,
        )
    assert stats["scored"] == 48 and stats["rejected"] == 0
    assert stats["arrival_trace_len"] == 24
    assert stats["arrival_cycle_s"] == pytest.approx(sum(gaps))
    # Request 47 fires one full cycle + 23 recorded gaps in: >= ~0.30 s.
    assert stats["wall_s"] >= 0.25


# --------------------------------------------------- K = 2 crc bit-identity
def test_kclass_k2_renders_bit_identical_to_binary_path():
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.metrics import (
        binary_counts,
        class_counts,
        finalize_class_metrics,
        finalize_metrics,
    )

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(256, 2)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, size=256).astype(np.int32))
    loss = jnp.asarray(np.float32(0.7))
    mb = finalize_metrics(binary_counts(logits, y, loss))
    mk = finalize_class_metrics(class_counts(logits, y, loss))
    assert set(mb) == set(mk)
    for k in ("Accuracy", "Loss", "Precision", "Recall", "F1-Score"):
        assert mb[k] == mk[k], k  # bit-identical floats, not approx
    assert np.array_equal(mb["confusion_matrix"], mk["confusion_matrix"])
    assert mb["n"] == mk["n"] == 256


def test_kclass_counts_accumulate_full_confusion_matrix():
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.metrics import (
        class_counts,
        finalize_class_metrics,
    )

    rng = np.random.default_rng(11)
    k, n = 7, 224
    logits = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    y = np.asarray(rng.integers(0, k, size=n), np.int32)
    counts = class_counts(logits, jnp.asarray(y), jnp.asarray(np.float32(1.9)))
    cm = np.asarray(counts.cm)
    assert cm.shape == (k, k) and cm.sum() == n
    preds = np.asarray(np.argmax(np.asarray(logits), axis=-1))
    assert float(counts.correct) == float((preds == y).sum())
    assert cm[3].sum() == int((y == 3).sum())  # row = truth support
    m = finalize_class_metrics(counts)
    assert m["n_classes"] == k and len(m["per_class"]) == k
    assert m["Accuracy"] == pytest.approx(100.0 * (preds == y).mean())


def test_multiclass_dataset_preset_labels_strictly():
    import pandas as pd

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
        get_dataset,
    )

    spec = get_dataset("cicddos2019-mc")
    assert spec.n_classes == 7 and spec.classes[0] == "BENIGN"
    df = pd.DataFrame({"Label": ["BENIGN", "Syn", "DrDoS_DNS", "BENIGN"]})
    assert spec.class_labels(df).tolist() == [0, 5, 1, 0]
    assert spec.labels(df).tolist() == [0, 5, 1, 0]
    # The binary view binarizes the SAME rows as != BENIGN.
    assert spec.binary_labels(df).tolist() == [0, 1, 1, 0]
    with pytest.raises(ValueError, match="not in the declared class"):
        spec.class_labels(pd.DataFrame({"Label": ["LDAP-weird"]}))
    # Binary specs refuse the K-class accessor loudly.
    with pytest.raises(ValueError, match="not a multiclass spec"):
        get_dataset("cicids2017").class_labels(df)


# --------------------------------------------- live controller integration
def _mean_eval(params):
    w = params["w"]
    mean = float(np.asarray(w, np.float64).mean())
    acc = mean if np.isfinite(mean) else float("nan")
    rng = np.random.default_rng(7)
    return {"Accuracy": acc, "probs": rng.uniform(0, 1, 128)}


class _SeedingGate(LabelGate):
    """The real LabelGate, but mirror-pair evidence for each candidate
    is seeded at join time (the artifact id is minted mid-round, so a
    test cannot pre-write its pairs file)."""

    def __init__(self, root, writer, **kw):
        super().__init__(root, **kw)
        self._writer = writer

    def join(self, aid):
        self._writer(self.registry_root, aid)
        return super().join(aid)


def test_delayed_labels_flip_a_live_promotion_verdict(tmp_path):
    """Two live TCP rounds, identical candidate evidence: round 1 runs
    before any ground truth arrived — the supervised gate FAILS CLOSED
    and the pointer never moves; the labels then land in the journal,
    and round 2 promotes on the same join arithmetic. The label plane,
    not the candidate, is what changed."""
    root = str(tmp_path / "reg")
    registry = ModelRegistry(root)
    state = str(tmp_path / "state.jsonl")
    truth = [i % 2 for i in range(40)]

    def writer(reg_root, aid):
        if os.path.exists(pairs_path(reg_root, aid)):
            return
        _write_pairs(
            reg_root,
            aid,
            [
                (f"r{i}", 0.9 if truth[i] else 0.1, 0.9 if truth[i] else 0.1,
                 None)
                for i in range(40)
            ],
        )

    gate = _SeedingGate(
        root, writer, min_joined=16, coverage_floor=0.05, max_regression=0.0
    )
    em = ErrorRateMonitor(margin=0.05, min_joined=16)
    store = LabelStore(journal_path(root))
    errors = []
    with AggregationServer(port=0, num_clients=2, timeout=30) as server:
        controller = Controller(
            server,
            registry,
            _mean_eval,
            control=ControlConfig(round_deadline_s=20.0),
            state_path=state,
            label_gate=gate,
            error_monitor=em,
        )

        def loop(cid):
            try:
                fc = FederatedClient(
                    "127.0.0.1", server.port, client_id=cid, timeout=30
                )
                out = fc.exchange({"w": np.full(16, 0.5, np.float32)})
                # Ground truth arrives BETWEEN the rounds — delayed, the
                # way incident review actually delivers it. Wait for the
                # round-0 verdict to land before ingesting (the round
                # reply races the controller's gate evaluation).
                if cid == 0:
                    deadline = time.monotonic() + 20
                    while True:
                        try:
                            if "label_rejected" in open(state).read():
                                break
                        except OSError:
                            pass
                        assert time.monotonic() < deadline
                        time.sleep(0.02)
                    for i in range(40):
                        store.ingest(f"r{i}", truth[i], ts=float(i))
                    store.advance_watermark(40.0)
                fc.exchange({"w": out["w"] + np.float32(0.25)})
            except Exception as e:
                errors.append(e)

        threads = [
            threading.Thread(target=loop, args=(c,), daemon=True)
            for c in range(2)
        ]
        for t in threads:
            t.start()
        stats = controller.run(max_rounds=2)
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors
    assert stats.rounds_completed == 2
    assert stats.label_rejections == 1 and stats.promotions == 1
    events = [json.loads(ln) for ln in open(state)]
    kinds = [e["event"] for e in events]
    assert kinds.count("label_rejected") == 1
    assert kinds.count("promoted") == 1
    rej = next(e for e in events if e["event"] == "label_rejected")
    assert "insufficient ground truth" in rej["label_verdict"]["reason"]
    assert rej["label_verdict"]["joined"] == 0
    pro = next(e for e in events if e["event"] == "promoted")
    assert pro["label_verdict"]["joined"] == 40
    assert pro["label_verdict"]["candidate_error"] == 0.0
    # The rejected candidate is in the registry with the verdict; the
    # pointer belongs to the round-2 artifact.
    manifests = {m["id"]: m for m in registry.list()}
    rejected = [m for m in manifests.values() if m["state"] == "rejected"]
    assert len(rejected) == 1
    assert registry.serving_manifest()["round"] == 1
    # Promotion anchored the supervised drift reference on the
    # candidate's measured error (0.0 here).
    assert em.has_reference
    # A resumed controller replays the label rejection from the state.
    resumed = Controller(
        _StubRoundServer(), registry, _mean_eval, state_path=state
    )
    assert resumed.stats.label_rejections == 1
    assert resumed.stats.promotions == 1


class _StubRoundServer:
    """Minimal round engine for controller tests that never serve a
    real TCP round (resume replay, cohort arithmetic)."""

    dp_clip = 0.0

    def __init__(self, min_clients=4):
        self.min_clients = min_clients
        self.seen_quorums = []
        self.n = 0

    def serve_round(self, *, deadline=None, round_index=None):
        self.seen_quorums.append(self.min_clients)
        self.n += 1
        return {"w": np.full(8, float(self.n), np.float32)}


def test_drift_scaled_cohort_applies_for_one_round_then_restores(tmp_path):
    """A fired drift verdict's magnitude picks the NEXT round's quorum:
    severe drift (>= 2x threshold) demands cohort_max_frac of the
    fleet; the override lasts exactly one round and the server's base
    min_clients comes back even though the stub round succeeded."""
    registry = ModelRegistry(str(tmp_path / "reg"))
    state = str(tmp_path / "state.jsonl")
    dm = DriftMonitor(threshold=0.25, min_scores=64)
    server = _StubRoundServer(min_clients=4)
    controller = Controller(
        server,
        registry,
        _mean_eval,
        control=ControlConfig(
            drift_cohort=True,
            cohort_min_frac=0.25,
            cohort_max_frac=0.5,
            round_deadline_s=20.0,
        ),
        state_path=state,
        drift_monitor=dm,
        drift_poll_s=0.05,
    )
    run_t = threading.Thread(
        target=lambda: controller.run(max_rounds=2), daemon=True
    )
    run_t.start()
    deadline = time.monotonic() + 20
    while registry.serving_info() is None:
        assert time.monotonic() < deadline, "bootstrap round never promoted"
        time.sleep(0.05)
    time.sleep(0.3)  # the controller enters its drift wait
    shifted = np.zeros(10, np.int64)
    shifted[4:6] = 64  # collapsed mass: psi far beyond 2x threshold
    dm.observe(shifted)
    run_t.join(timeout=30)
    assert not run_t.is_alive()
    # Round 0 ran at the base quorum; the drift round at max_frac of it.
    assert server.seen_quorums == [4, 2]
    assert server.min_clients == 4  # restored after the cohort round
    events = [json.loads(ln) for ln in open(state)]
    trig = [e for e in events if e["event"] == "drift_trigger"]
    assert trig and trig[-1]["cohort_target"] == 2
