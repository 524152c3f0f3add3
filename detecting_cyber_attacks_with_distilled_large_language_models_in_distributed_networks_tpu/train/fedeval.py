"""Federated evaluation plumbing: eval-split stacking, the stacked
metrics loop, and the control plane's eval-gate hooks.

The reference evaluates each client separately with a host-side sklearn
pass (client1.py:118-150); here all C clients evaluate in one jitted
vmapped sweep over a padded ``[C, M, ...]`` stack, with on-device
BinaryCounts accumulation and one host sync per evaluation.

:func:`eval_gate` and :func:`reference_histogram` are the train-side
hooks the controller (control/controller.py) gates promotion on: the
gate compares a candidate's held-out metrics against the incumbent's,
and the histogram is the score-distribution fingerprint the drift
monitor later compares live serving traffic against.
"""

from __future__ import annotations

from typing import Any, Mapping, NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from ..data.pipeline import TokenizedSplit, pad_split_to_batch
from ..obs.trace import annotate
from ..ops.metrics import (
    BinaryCounts,
    ClassCounts,
    finalize_class_metrics,
    finalize_metrics,
)


def stack_eval_splits(
    splits: Sequence[TokenizedSplit],
    batch_size: int,
    pad_id: int = 0,
    *,
    target_rows: int | None = None,
) -> tuple[TokenizedSplit, np.ndarray]:
    """Pad per-client eval splits to one common ``[C, M, ...]`` stack (M a
    batch multiple) plus a ``[C, M]`` validity matrix so every real example
    is counted exactly once per client.

    ``target_rows``: minimum row count before batch-rounding — multi-host
    processes pass the GLOBAL max split length so every host agrees on M
    (and therefore on the eval batch count, which is a collective)."""
    target = max(len(s) for s in splits)
    if target_rows is not None:
        target = max(target, target_rows)
    target += (-target) % batch_size
    ids, masks, labels, valid = [], [], [], []
    for s in splits:
        padded, v = pad_split_to_batch(s, batch_size, pad_id=pad_id)
        extra = target - len(padded)
        L = padded.input_ids.shape[1]
        ids.append(
            np.concatenate([padded.input_ids, np.full((extra, L), pad_id, np.int32)])
        )
        masks.append(
            np.concatenate([padded.attention_mask, np.zeros((extra, L), np.int32)])
        )
        labels.append(np.concatenate([padded.labels, np.zeros(extra, np.int32)]))
        valid.append(np.concatenate([v, np.zeros(extra, np.int32)]))
    return (
        TokenizedSplit(np.stack(ids), np.stack(masks), np.stack(labels)),
        np.stack(valid),
    )


class PreparedEval(NamedTuple):
    """Stacked eval splits, padded once and reused across rounds. ROC/PR
    labels come from the stacked arrays' valid rows (padding appends, so
    the valid subsequence preserves split order)."""

    stacked: TokenizedSplit  # [C, M, ...] arrays, M a batch multiple
    valid: np.ndarray  # [C, M] 0/1
    batch_size: int


def evaluate_stacked(
    trainer,
    stacked_params: Any,
    prepared: PreparedEval,
    *,
    collect_probs: bool = False,
) -> list[dict]:
    """Per-client metrics dicts (reference five-metric schema) from one
    sweep of the trainer's jitted eval step over a prepared stack."""
    with annotate("eval"):
        stacked, valid, bs = prepared.stacked, prepared.valid, prepared.batch_size
        C = trainer.C
        M = stacked.labels.shape[1]
        # Accumulate the stacked [C] counts on device; one host sync after
        # the loop (per-batch np.asarray would block async dispatch). The
        # counts type follows the head width (BinaryCounts for K=2,
        # ClassCounts for K>2 — eval_counts' static branch).
        totals: BinaryCounts | ClassCounts | None = None
        probs_dev = []
        for i in range(M // bs):
            sl = slice(i * bs, (i + 1) * bs)
            fed = trainer._feed(
                {
                    "input_ids": stacked.input_ids[:, sl],
                    "attention_mask": stacked.attention_mask[:, sl],
                    "labels": stacked.labels[:, sl],
                    "valid": valid[:, sl],
                }
            )
            batch = {k: fed[k] for k in ("input_ids", "attention_mask", "labels")}
            counts, probs = trainer.eval_step(stacked_params, batch, fed["valid"])
            totals = counts if totals is None else totals + counts
            if collect_probs:
                probs_dev.append(probs)
        with annotate("eval/read"):
            host = (
                trainer._host(totals)
                if totals is not None
                else BinaryCounts(
                    *(np.zeros(C, np.float32) for _ in BinaryCounts._fields)
                )
            )
        out = []
        all_probs = None
        labels_g, valid_g = stacked.labels, valid
        if probs_dev:
            # Probs accumulate as GLOBAL [C, bs] device arrays (the eval
            # step's output sharding); _host replicates across processes
            # first, so every host sees every client's probabilities.
            all_probs = np.asarray(
                trainer._host(jnp.concatenate(probs_dev, axis=1))
            )
            if trainer.P > 1:
                # The host-side labels/validity cover only LOCAL clients;
                # gather them process-major (the global client order).
                from jax.experimental import multihost_utils

                M_pad = stacked.labels.shape[1]
                labels_g = np.asarray(
                    multihost_utils.process_allgather(stacked.labels)
                ).reshape(-1, M_pad)
                valid_g = np.asarray(
                    multihost_utils.process_allgather(valid)
                ).reshape(-1, M_pad)
        for c in range(C):
            client_counts = type(host)(*(v[c] for v in host))
            m = (
                finalize_class_metrics(client_counts)
                if isinstance(client_counts, ClassCounts)
                else finalize_metrics(client_counts)
            )
            if collect_probs and all_probs is not None:
                # Padding appends rows, so the valid-row subsequence IS the
                # original split order (pad_split_to_batch/stack_eval_splits).
                mask_c = valid_g[c, : all_probs.shape[1]] == 1
                m["probs"] = all_probs[c][mask_c]
                m["labels"] = labels_g[c][mask_c]
            out.append(m)
    return out


# ----------------------------------------------------- control-plane hooks
def reference_histogram(probs: Any, *, bins: int = 10) -> np.ndarray:
    """Score-distribution fingerprint of a held-out evaluation: integer
    counts of P(attack) over ``bins`` equal buckets spanning [0, 1].

    Recorded in the registry manifest at artifact creation; once the
    artifact is promoted, the drift monitor (control/drift.py) compares
    live serving-score histograms (the serving tier exports the SAME
    binning, serving/server.py) against this reference — a shift says the
    traffic no longer looks like what the model was validated on."""
    p = np.clip(np.asarray(probs, np.float64).ravel(), 0.0, 1.0)
    counts, _ = np.histogram(p, bins=int(bins), range=(0.0, 1.0))
    return counts.astype(np.int64)


def eval_gate(
    candidate: Mapping[str, Any],
    incumbent: Mapping[str, Any] | None,
    *,
    metric: str = "Accuracy",
    min_delta: float = 0.0,
) -> tuple[bool, str]:
    """The promotion gate: may ``candidate`` replace ``incumbent``?

    Returns ``(ok, reason)``. A candidate whose gate metric is missing or
    non-finite NEVER passes — a corrupted aggregate (NaN params) shows up
    exactly there, and "can't evaluate" must fail closed, not promote.
    With no incumbent (bootstrap) any finite candidate passes. Otherwise
    the candidate must score at least ``incumbent[metric] - min_delta``
    (metrics here are higher-is-better, the reference's five-metric
    schema minus Loss — gate on Loss is not supported)."""
    try:
        cand = float(candidate[metric])
    except (KeyError, TypeError, ValueError):
        return False, f"candidate has no finite {metric!r}"
    if not np.isfinite(cand):
        return False, f"candidate {metric}={cand} is not finite"
    if incumbent is None:
        return True, f"bootstrap: no incumbent ({metric} {cand:.4f})"
    try:
        inc = float(incumbent[metric])
    except (KeyError, TypeError, ValueError):
        # An incumbent with no recorded metric cannot anchor a comparison;
        # treat it like bootstrap rather than blocking every promotion.
        return True, f"incumbent has no {metric!r}; promoting {cand:.4f}"
    if not np.isfinite(inc):
        return True, f"incumbent {metric} not finite; promoting {cand:.4f}"
    if cand >= inc - float(min_delta):
        return True, f"{metric} {cand:.4f} >= incumbent {inc:.4f} - {min_delta}"
    return (
        False,
        f"{metric} {cand:.4f} < incumbent {inc:.4f} - {min_delta} (regression)",
    )
