"""Hierarchical fold tree: the ``fedtpu relay`` intermediate aggregator.

PR 5 made the server's aggregation state O(model + in-flight) and PR 7
made the reply fan-out symmetric (streamed both ways) — but one process
still terminated every client connection, which is the real ceiling on
cohort size (the Smart NIC FL-server study, arXiv:2307.06561, names the
server datapath as the fleet-scale bottleneck; the communication survey,
arXiv:2405.20431, frames hierarchical aggregation as the standard way
past it). A relay terminates a SUBTREE of client connections, folds them
into a partial weighted mean with the same streaming machinery the root
uses (comm/stream_agg.py — leaves fold as chunks land), and forwards ONE
streamed upload to its parent. The root then terminates ``n_relays``
connections instead of ``n_clients``: a 256-client cohort at depth 2
with fanout 16 is 16 connections per process, every hop streamed.

Composition over invention: a relay IS an :class:`~.server.
AggregationServer` (subtree-facing — auth, streamed uploads, eager
folds, obs spans, all unchanged) plus a :class:`~.client.
FederatedClient` (parent-facing — streamed upload up, streamed reply
down), glued by the server's ``reply_via`` hook: between aggregation and
the reply fan-out, the subtree partial goes up, and the ROOT's aggregate
comes back down to be fanned out to the subtree's clients. Clients
cannot tell a relay from a root server — same wire protocol, same
capability adverts, same retries.

Weight contract (what makes the tree a mean, not an artifact of its
shape): the relay's subtree mean is ALWAYS sample-count weighted, and
its upward upload carries ``n_samples = sum(subtree n_samples)``; run
the ROOT with ``--weighted`` so subtree means recombine by their true
mass. With uniform counts this degrades to the uniform mean exactly.

Bit-exactness contract (the PR 5/6 A/B contract, generalized): every
fold in the tree is individually crc-pinned bit-exact against
``aggregate_flat`` over its own inputs — the relay's partial vs the
barrier mean of its subtree's uploads, the root's aggregate vs the
barrier mean of the relay partials — so the depth-2 result equals
:func:`aggregate_tree` (the pinned order: ascending client id within a
subtree, fixed subtree order at the root) BIT-EXACTLY, replayable from
captured uploads. The depth-2 result differs from the single-process
``aggregate_flat`` over all N clients by fp32 reduction-ORDER ulps only
(fp32 addition is non-associative; same class of divergence as the
data-parallel client's gradient-reduction note in train/client_mesh.py)
— below every metric's resolution, and exactly reproducible from the
pinned order.

Out of scope by design (ROADMAP residuals): secure aggregation stays
single-aggregator (the unmask protocol needs one process holding the
full contributor set) and central DP stays at the root (a subtree
partial forwarded pre-noise would be an un-noised release).
"""

from __future__ import annotations

import time

import numpy as np

from ..utils.logging import get_logger
from . import wire
from .client import FederatedClient
from .server import AggregationServer, aggregate_flat

log = get_logger()


def aggregate_tree(
    models: list[dict[str, np.ndarray]],
    weights: list[float] | None,
    groups: list,
) -> dict[str, np.ndarray]:
    """The fold tree's pinned arithmetic, replayed flat: per group (a
    subtree, indices into ``models`` in ascending client-id order) the
    weighted barrier mean, then the barrier mean of the partials
    weighted by each group's weight mass — exactly the fp32 ops, in
    exactly the order, the relay tier performs. The A/B harnesses
    (tests/test_fleet.py, tests/test_scenario.py) pin the live depth-2 root
    aggregate against this crc-bit-exactly.

    ``groups`` may nest to ANY depth: an element that is itself a list
    is a deeper subtree (a relay whose parent is another relay — the
    wire composes, and this is its replay). Each subtree folds bottom-up
    to a (weighted mean, weight mass) pair; the parent folds child
    partials weighted by their masses. The classic depth-2 call shape
    (``[[0, 1], [2, 3]]``) takes exactly the code path — and produces
    exactly the fp32 ops in exactly the order — it always did."""
    if not isinstance(groups, list) or not groups:
        raise ValueError("aggregate_tree needs non-empty groups")

    def _fold(node) -> tuple[dict[str, np.ndarray], float]:
        if isinstance(node, (int, np.integer)):
            w = 1.0 if weights is None else float(weights[node])
            return models[node], w
        if not isinstance(node, list) or not node:
            raise ValueError("aggregate_tree needs non-empty groups")
        parts: list[dict[str, np.ndarray]] = []
        masses: list[float] = []
        for child in node:
            part, mass = _fold(child)
            parts.append(part)
            masses.append(mass)
        return aggregate_flat(parts, masses), sum(masses)

    agg, _mass = _fold(groups)
    return agg


class RelayAggregator:
    """One ``fedtpu relay`` process: subtree-facing AggregationServer +
    parent-facing FederatedClient, joined by the server's ``reply_via``
    hook.

    ``relay_id`` is this relay's client id on the PARENT's tier (the
    fixed subtree order at the root: relays fold in ascending relay id,
    exactly as clients fold in ascending client id within the subtree).
    ``num_clients`` is the SUBTREE size — the ids this relay terminates
    are whatever its clients present, validated by the same rules as any
    server's.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        parent_host: str,
        parent_port: int,
        relay_id: int,
        num_clients: int,
        min_clients: int | None = None,
        timeout: float = 300.0,
        compression: str = "none",
        auth_key: bytes | None = None,
        stream_chunk_bytes: int = wire.DEFAULT_STREAM_CHUNK,
        stream: bool = True,
        subtree_deadline_factor: float = 0.5,
        tracer=None,
        strategy: str = "fedavg",
        upward_topk: float | None = None,
    ):
        # Sparse upward hops (--upward-topk): the relay's parent-facing
        # leg runs the existing sparse round-delta machinery — its
        # upward upload becomes topk(subtree partial - last root
        # aggregate it fanned down, + error-feedback residual), with
        # base agreement pinned by the root's agg_crc stamp exactly as
        # for a leaf client. The subtree partial drifts by one round's
        # client training, so the upward delta is small even when every
        # leaf uploads dense — upward bytes drop superlinearly with
        # depth (each tier re-sparsifies its own partial). Round 1 (and
        # any round after a base refusal) ships dense automatically; a
        # root running lossy reply compression never confirms a base,
        # so the relay stays dense rather than diverging.
        if upward_topk is not None:
            if compression.startswith("topk"):
                raise ValueError(
                    "upward_topk composes the relay's own upward "
                    "sparsifier; give the subtree-facing --compression "
                    "a non-topk value"
                )
            # Range validation lives in wire.parse_compression.
            wire.parse_compression(f"topk:{float(upward_topk)}")
        # Per-subtree straggler deadline, STRICTLY tighter than the
        # round budget (config.py FedConfig validates the same bound):
        # a slow subtree sheds its stragglers at factor * timeout — run
        # this relay with --min-clients below the subtree size to
        # proceed over survivors — instead of stalling the root until
        # ITS deadline. factor >= 1 would re-create exactly the failure
        # mode this tier exists to remove, so it is refused.
        if not 0.0 < float(subtree_deadline_factor) < 1.0:
            raise ValueError(
                f"subtree_deadline_factor={subtree_deadline_factor} "
                "must be in (0, 1): the subtree deadline has to be "
                "strictly tighter than the round budget"
            )
        # Sample-count weighting is the relay-tier contract (module
        # docstring): subtree means must recombine at the parent by
        # their true mass, so the subtree fold is always weighted
        # (uniform counts make it the uniform mean bit-exactly —
        # aggregate_flat normalizes ones and explicit equal weights to
        # identical float64 values).
        self.server = AggregationServer(
            host,
            port,
            num_clients=num_clients,
            weighted=True,
            min_clients=min_clients,
            timeout=timeout,
            compression=compression,
            auth_key=auth_key,
            stream_chunk_bytes=stream_chunk_bytes,
            tracer=tracer,
        )
        self.parent = FederatedClient(
            parent_host,
            parent_port,
            client_id=relay_id,
            timeout=timeout,
            compression=(
                f"topk:{float(upward_topk)}"
                if upward_topk is not None
                else compression
            ),
            auth_key=auth_key,
            stream=stream,
            tracer=tracer,
        )
        self.upward_topk = (
            float(upward_topk) if upward_topk is not None else None
        )
        #: Cumulative parent-facing upload payload bytes (the
        #: ``fedtpu_relay_upward_bytes_total`` /metrics counter):
        #: what the sparse upward tier exists to shrink.
        self.upward_bytes = 0
        from ..obs import metrics as _obs_metrics

        self._m_upward_bytes = _obs_metrics.default_registry().counter(
            "fedtpu_relay_upward_bytes_total",
            help="parent-facing upload payload bytes shipped by this "
            "relay (sparse upward deltas shrink this, not the subtree "
            "tier's receive totals)",
        )
        self.relay_id = int(relay_id)
        self.subtree_deadline_factor = float(subtree_deadline_factor)
        self.tracer = tracer
        # Strategy agreement stamp (strategies/, wire.STRATEGY_META_KEY):
        # strategies apply at the ROOT only — a subtree partial is not a
        # global, so the relay's own fold never transforms — but the
        # relay declares which strategy it believes the fleet runs on
        # every upward upload, and the root refuses a mismatch (a
        # split-brain fleet folding under two aggregation rules). The
        # declaration is validated here so a typo'd --strategy fails at
        # relay start, not at the root's round.
        from .. import strategies as _strategies

        self.strategy_name = _strategies.make_strategy(strategy).name
        self.server.reply_via = self._forward
        self.port = self.server.port

    # ------------------------------------------------------------ rounds
    def _forward(self, agg: dict, info: dict) -> dict:
        """The ``reply_via`` hook: ship the subtree partial (with its
        aggregate sample mass) to the parent, return the root aggregate
        the subtree's clients will receive. Emits the ``relay-forward``
        span — the upward exchange window, the tree tier's line on the
        obs timeline."""
        total = sum(info["n_samples"].values())
        # fedtpu: allow(determinism): span wall-clock timestamp — feeds the
        # obs timeline only, never the fold value or order
        t_unix = time.time()
        t0 = time.monotonic()
        out = self.parent.exchange(
            agg,
            n_samples=max(1, int(round(total))),
            # Contributor record for the parent's assignment ledger
            # (wire.SUBTREE_IDS_META_KEY): the ascending client ids this
            # partial folded — how the root replays (and crc-pins) the
            # round's ACTUAL tree, re-homed adoptions included, and how
            # it detects a double-counted re-homed upload.
            meta={
                wire.SUBTREE_IDS_META_KEY: [int(i) for i in info["ids"]],
                # Strategy agreement: the root WireErrors this upload if
                # its active strategy id differs (split-brain guard).
                wire.STRATEGY_META_KEY: {"name": self.strategy_name},
            },
        )
        dur = time.monotonic() - t0
        up_bytes = int(self.parent.last_upload_bytes)
        self.upward_bytes += up_bytes
        self._m_upward_bytes.inc(float(up_bytes))
        if self.tracer is not None:
            parent_trace, parent_round = self.parent.last_trace
            self.tracer.record(
                "relay-forward",
                t_start=t_unix,
                dur_s=dur,
                trace=info.get("trace"),
                round=info.get("round"),
                relay=self.relay_id,
                subtree_clients=len(info["ids"]),
                parent_trace=parent_trace,
                parent_round=parent_round,
                # Wire-efficiency attribution: what the upward hop
                # actually cost, and whether it went sparse/quantized.
                upward_bytes=up_bytes,
                upward_sparse=1 if self.upward_topk is not None else None,
                wire_dtype=self.parent.last_wire_dtype,
            )
        log.info(
            f"[RELAY {self.relay_id}] forwarded subtree partial "
            f"({len(info['ids'])} client(s), mass {total:g}, "
            f"{up_bytes / 1e6:.2f} MB up) and received "
            f"the root aggregate in {dur:.3f}s"
        )
        return wire.flatten_params(out)

    def serve_round(self, **kw) -> dict | None:
        """One relay round: gather + fold the subtree, forward the
        partial, fan the root aggregate out to the subtree's clients.
        Returns the ROOT aggregate (flat).

        The default round deadline is ``subtree_deadline_factor *
        timeout`` — strictly tighter than the round budget, so a slow
        subtree resolves (sheds its stragglers, or fails its local
        quorum) while the root is still accepting the other subtrees'
        uploads, instead of stalling the whole tree."""
        kw.setdefault(
            "deadline",
            self.subtree_deadline_factor * self.server.timeout,
        )
        return self.server.serve_round(**kw)

    def serve(self, rounds: int = 1) -> None:
        """Multi-round loop with the server's keep-going contract: a
        failed round (subtree quorum miss, parent unreachable) is logged
        and the next proceeds, so retrying clients can complete it."""
        for r in range(rounds):
            log.info(f"[RELAY {self.relay_id}] round {r + 1}/{rounds}")
            try:
                self.serve_round()
            except (RuntimeError, ConnectionError, OSError) as e:
                log.info(
                    f"[RELAY {self.relay_id}] round {r + 1} failed: {e}"
                )

    # --------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Tear the relay down PROMPTLY, mid-round included: abort the
        parent-facing exchange first (a forward blocked on the root's
        reply — or in a dial backoff — must not wait out its socket
        timeout), then close the subtree server, which sheds every
        pending child upload as an explicit failure (comm/server.py
        close: shutdown-then-close, the prompt-close discipline). The
        children's dead connections are what trigger their re-homing —
        so this teardown path is the failover plane's latency floor."""
        self.parent.abort()
        self.server.close()

    def __enter__(self) -> "RelayAggregator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
