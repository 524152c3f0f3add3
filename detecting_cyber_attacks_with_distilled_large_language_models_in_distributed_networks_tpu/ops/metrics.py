"""On-device classification metrics.

The reference accumulates predictions on the host and calls sklearn per eval
(reference client1.py:118-150: ``precision_recall_fscore_support``,
``confusion_matrix``). Here the eval step accumulates sufficient statistics
(loss sum, correct count, TP/FP/FN/TN) on device — one scalar pytree per
batch, no [N]-sized host transfers — and the host finalizes the same five
metrics (Accuracy, Loss, Precision, Recall, F1) plus the confusion matrix.

The K-class plane (ISSUE 18) generalizes the same discipline: a
:class:`ClassCounts` carries a dense [K, K] confusion matrix (rows =
truth, cols = prediction) instead of four scalars, and
:func:`finalize_class_metrics` renders macro-averaged P/R/F1 plus
per-class support. K = 2 is NOT a parallel implementation — it routes
through the binary kernels verbatim, so the multi-class path is
bit-identical to the binary one on the same inputs (the crc contract
tests/test_labels.py pins).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class BinaryCounts(NamedTuple):
    """Sufficient statistics for binary classification metrics."""

    loss_sum: jnp.ndarray  # fp32 scalar — sum of per-batch mean losses
    n_batches: jnp.ndarray  # fp32 scalar
    n_examples: jnp.ndarray  # fp32 scalar
    correct: jnp.ndarray  # fp32 scalar
    tp: jnp.ndarray
    fp: jnp.ndarray
    fn: jnp.ndarray
    tn: jnp.ndarray

    @classmethod
    def zero(cls) -> "BinaryCounts":
        z = jnp.zeros((), jnp.float32)
        return cls(z, z, z, z, z, z, z, z)

    def __add__(self, other: "BinaryCounts") -> "BinaryCounts":  # type: ignore[override]
        return BinaryCounts(*(a + b for a, b in zip(self, other)))


def binary_counts(
    logits: jnp.ndarray,  # [B, 2]
    labels: jnp.ndarray,  # [B]
    loss: jnp.ndarray,  # scalar — batch mean loss
    valid: jnp.ndarray | None = None,  # [B] 0/1 — padded-row mask
) -> BinaryCounts:
    preds = jnp.argmax(logits, axis=-1)
    if valid is None:
        valid = jnp.ones_like(labels)
    v = valid.astype(jnp.float32)
    pos = (labels == 1).astype(jnp.float32) * v
    neg = (labels == 0).astype(jnp.float32) * v
    pred_pos = (preds == 1).astype(jnp.float32)
    pred_neg = (preds == 0).astype(jnp.float32)
    has_valid = (v.sum() > 0).astype(jnp.float32)
    return BinaryCounts(
        # All-padding batches (possible when clients' eval splits are stacked
        # to a common length) must not dilute the batch-mean loss.
        loss_sum=loss.astype(jnp.float32) * has_valid,
        n_batches=has_valid,
        n_examples=v.sum(),
        correct=((preds == labels).astype(jnp.float32) * v).sum(),
        tp=(pos * pred_pos).sum(),
        fp=(neg * pred_pos).sum(),
        fn=(pos * pred_neg).sum(),
        tn=(neg * pred_neg).sum(),
    )


def finalize_metrics(counts: BinaryCounts) -> dict[str, float]:
    """Host-side finalization into the reference's five-metric schema
    (Accuracy in percent, as at reference client1.py:143) + confusion matrix.

    Precision/recall/F1 follow sklearn's ``average='binary'`` zero-division
    convention (0.0 when undefined)."""
    c = {k: float(v) for k, v in counts._asdict().items()}
    n = max(c["n_examples"], 1.0)
    precision = c["tp"] / (c["tp"] + c["fp"]) if (c["tp"] + c["fp"]) > 0 else 0.0
    recall = c["tp"] / (c["tp"] + c["fn"]) if (c["tp"] + c["fn"]) > 0 else 0.0
    f1 = (
        2 * precision * recall / (precision + recall)
        if (precision + recall) > 0
        else 0.0
    )
    return {
        "Accuracy": 100.0 * c["correct"] / n,
        "Loss": c["loss_sum"] / max(c["n_batches"], 1.0),
        "Precision": precision,
        "Recall": recall,
        "F1-Score": f1,
        "confusion_matrix": np.array(
            [[c["tn"], c["fp"]], [c["fn"], c["tp"]]], dtype=np.int64
        ),
        "n": int(c["n_examples"]),
    }


# ------------------------------------------------------------- K classes
class ClassCounts(NamedTuple):
    """Sufficient statistics for K-class classification metrics.

    ``cm`` is the dense [K, K] confusion matrix, rows = truth, cols =
    prediction — the full sufficient statistic for every count-derived
    metric, still O(K^2) scalars per eval instead of [N]-sized host
    transfers."""

    loss_sum: jnp.ndarray  # fp32 scalar — sum of per-batch mean losses
    n_batches: jnp.ndarray  # fp32 scalar
    n_examples: jnp.ndarray  # fp32 scalar
    correct: jnp.ndarray  # fp32 scalar
    cm: jnp.ndarray  # [K, K] fp32 — rows truth, cols prediction

    @classmethod
    def zero(cls, n_classes: int) -> "ClassCounts":
        z = jnp.zeros((), jnp.float32)
        return cls(z, z, z, z, jnp.zeros((n_classes, n_classes), jnp.float32))

    def __add__(self, other: "ClassCounts") -> "ClassCounts":  # type: ignore[override]
        return ClassCounts(*(a + b for a, b in zip(self, other)))


def class_counts(
    logits: jnp.ndarray,  # [B, K]
    labels: jnp.ndarray,  # [B]
    loss: jnp.ndarray,  # scalar — batch mean loss
    valid: jnp.ndarray | None = None,  # [B] 0/1 — padded-row mask
) -> ClassCounts:
    """K-class sufficient statistics. K = 2 routes through
    :func:`binary_counts` verbatim and reassembles its four scalars into
    the [2, 2] matrix — bit-identical to the binary path by
    construction, not by accident of arithmetic."""
    k = int(logits.shape[-1])
    if k == 2:
        b = binary_counts(logits, labels, loss, valid)
        return ClassCounts(
            loss_sum=b.loss_sum,
            n_batches=b.n_batches,
            n_examples=b.n_examples,
            correct=b.correct,
            cm=jnp.stack(
                [jnp.stack([b.tn, b.fp]), jnp.stack([b.fn, b.tp])]
            ),
        )
    preds = jnp.argmax(logits, axis=-1)
    if valid is None:
        valid = jnp.ones_like(labels)
    v = valid.astype(jnp.float32)
    classes = jnp.arange(k)
    # One-hot contraction: cm[t, p] = sum_b valid_b [label_b==t][pred_b==p].
    oh_true = (labels[:, None] == classes[None, :]).astype(jnp.float32)
    oh_pred = (preds[:, None] == classes[None, :]).astype(jnp.float32)
    cm = (oh_true * v[:, None]).T @ oh_pred
    has_valid = (v.sum() > 0).astype(jnp.float32)
    return ClassCounts(
        loss_sum=loss.astype(jnp.float32) * has_valid,
        n_batches=has_valid,
        n_examples=v.sum(),
        correct=((preds == labels).astype(jnp.float32) * v).sum(),
        cm=cm,
    )


def finalize_class_metrics(counts: ClassCounts) -> dict[str, float]:
    """Host-side K-class finalization.

    K = 2 delegates to :func:`finalize_metrics` over the reassembled
    :class:`BinaryCounts` — the SAME float arithmetic, so the rendered
    dict is bit-identical to the binary path's. K > 2 renders the same
    five-metric schema with macro-averaged Precision/Recall/F1 (sklearn
    ``average='macro'`` with zero-division -> 0.0) plus ``per_class``
    recall/support rows keyed by class index."""
    cm = np.asarray(counts.cm, dtype=np.float64)
    k = cm.shape[0]
    if k == 2:
        return finalize_metrics(
            BinaryCounts(
                loss_sum=counts.loss_sum,
                n_batches=counts.n_batches,
                n_examples=counts.n_examples,
                correct=counts.correct,
                tp=counts.cm[1, 1],
                fp=counts.cm[0, 1],
                fn=counts.cm[1, 0],
                tn=counts.cm[0, 0],
            )
        )
    n = max(float(counts.n_examples), 1.0)
    diag = np.diag(cm)
    pred_tot = cm.sum(axis=0)  # column sums: predicted-as-c
    true_tot = cm.sum(axis=1)  # row sums: truly-c (support)
    with np.errstate(invalid="ignore", divide="ignore"):
        prec = np.where(pred_tot > 0, diag / np.maximum(pred_tot, 1.0), 0.0)
        rec = np.where(true_tot > 0, diag / np.maximum(true_tot, 1.0), 0.0)
        denom = prec + rec
        f1 = np.where(denom > 0, 2 * prec * rec / np.maximum(denom, 1e-38), 0.0)
    return {
        "Accuracy": 100.0 * float(counts.correct) / n,
        "Loss": float(counts.loss_sum) / max(float(counts.n_batches), 1.0),
        "Precision": float(prec.mean()),
        "Recall": float(rec.mean()),
        "F1-Score": float(f1.mean()),
        "confusion_matrix": cm.astype(np.int64),
        "per_class": {
            str(c): {
                "precision": float(prec[c]),
                "recall": float(rec[c]),
                "f1": float(f1[c]),
                "support": int(true_tot[c]),
            }
            for c in range(k)
        },
        "n": int(float(counts.n_examples)),
        "n_classes": k,
    }
