"""Online inference service: dynamic-batching TCP scoring with hot reload.

The reference trains and evaluates but never deploys (reference
client1.py:379-400); ``fedtpu predict`` closed that gap only for offline
CSVs. This package is the live path from "federated model" to "detector
answering flow queries": a TCP service (``fedtpu infer-serve``) that
accepts flow records over the existing length-framed wire
(comm/framing.py), tokenizes them with the native WordPiece path, and
scores them through a dynamic micro-batcher whose batches are drawn from
a small set of fixed bucket shapes — so XLA compiles one program per
(bucket, seq) and every request thereafter hits a warm jitted path.

Layers (each its own module, composable and unit-testable):

* :mod:`.protocol` — request/reply/reject frame codecs over the scoring
  magics (comm/wire.py ``SCORE_*``).
* :mod:`.batcher`  — bounded request queue + gather-window coalescing
  (admission control happens HERE: a full queue is an immediate reject,
  never unbounded latency).
* :mod:`.engine`   — the bucketed jit cache: pad to the smallest bucket
  that fits, score through one traced-once-per-shape program, with a
  trace-time compile-count hook tests and ops can assert on.
* :mod:`.reload`   — checkpoint watcher: picks up new federated rounds
  between batches (reusing cli/predict's ``_restore_predict_params``)
  so the detector improves every FL round without a restart.
* :mod:`.server`   — the accept loop / scorer thread wiring + telemetry
  (per-request queue wait, batch size, model round; p50/p95/p99 on the
  metrics-JSONL channel).
* :mod:`.client`   — SDK + load generator (tests drive services with it).
"""

from .batcher import MicroBatcher, ScoreRequest
from .client import (
    AsyncScoringClient,
    PipelinedScoringClient,
    ScoreRejected,
    ScoringClient,
    fetch_stats,
    load_arrival_trace,
    run_load,
)
from .engine import ScoreEngine
from .protocol import (
    build_reject,
    build_reply,
    build_request,
    parse_reject,
    parse_reply,
    parse_request,
)
from .reload import CheckpointWatcher, RegistryWatcher
from .server import ScoringServer

__all__ = [
    "AsyncScoringClient",
    "CheckpointWatcher",
    "RegistryWatcher",
    "MicroBatcher",
    "PipelinedScoringClient",
    "ScoreEngine",
    "ScoreRejected",
    "ScoreRequest",
    "ScoringClient",
    "ScoringServer",
    "build_reject",
    "build_reply",
    "build_request",
    "fetch_stats",
    "load_arrival_trace",
    "parse_reject",
    "parse_reply",
    "parse_request",
    "run_load",
]
