"""Aggregation server for the cross-host demo-parity mode.

On TPU, FedAvg is a ``pmean`` on the mesh and there is no server at all
(parallel/fedavg.py). This module exists for the reference's *other*
capability: genuinely separate client processes on separate hosts
(reference server.py end-to-end). Differences from the reference, by
design:

* one port, request/response on a single connection — the reference's
  second listening port plus 1 s client polling (client1.py:298-311,
  server.py:81-114) is a built-in race: probe connects are accepted by the
  send loop and kill it (WinError 10053 in the golden logs,
  server_terminal_output.txt:19,27). With request/response there is nothing
  to poll: the reply arrives on the connection the upload used.
* clients are identified by the ``client_id`` in the message meta, not by
  accept order (the reference can serve one client twice and starve
  another, SURVEY.md §5).
* weighted FedAvg by ``n_samples`` (optional) and a ``min_clients``
  quorum with a round deadline, instead of hanging forever when a client
  dies (reference server.py:69-71 + 124-132).
* wire format is non-executable (comm/wire.py) — no pickle RCE.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from .. import strategies
from ..obs import flight as obs_flight
from ..obs import metrics as obs_metrics
from ..obs.profile import note_memory
from ..obs.trace import new_trace_id
from ..utils.logging import get_logger
from . import framing, secure, wire
from .stream_agg import StreamAgg

log = get_logger()


def aggregate_flat(
    models: list[dict[str, np.ndarray]], weights: list[float] | None = None
) -> dict[str, np.ndarray]:
    """Weighted element-wise mean of flat param dicts (fp32 accumulation),
    the reference's ``aggregate_models`` (server.py:67-79) without the
    in-place mutation of client 0's weights."""
    if not models:
        raise ValueError("no models to aggregate")
    keys = set(models[0])
    for i, m in enumerate(models[1:], 1):
        if set(m) != keys:
            raise wire.WireError(f"model {i} key set differs from model 0")
    if weights is None:
        w = np.ones(len(models), np.float64)
    else:
        w = np.asarray(weights, np.float64)
        if w.shape != (len(models),) or w.sum() <= 0:
            raise ValueError(f"bad weights {weights}")
    w = w / w.sum()
    out: dict[str, np.ndarray] = {}
    for key in models[0]:
        acc = np.zeros_like(np.asarray(models[0][key], np.float32))
        for wi, m in zip(w, models):
            if m[key].shape != acc.shape:
                raise wire.WireError(f"shape mismatch for {key!r}")
            acc += np.float32(wi) * np.asarray(m[key], np.float32)
        out[key] = acc
    return out


@dataclass
class _Round:
    """One aggregation round's rendezvous state."""

    expected: int
    round_no: int = 0
    #: Round-scoped trace id (obs/trace.py), minted by serve_round and
    #: stamped into every reply's meta so clients adopt the same identity.
    trace: str = ""
    models: dict[int, dict] = field(default_factory=dict)  # client_id -> flat params
    # Sparse-delta uploads (topk clients): flat params holds the DENSIFIED
    # round delta; the absolute model is base + delta at aggregation time.
    deltas: dict[int, bool] = field(default_factory=dict)
    n_samples: dict[int, float] = field(default_factory=dict)
    conns: dict[int, socket.socket] = field(default_factory=dict)
    nonces: dict[int, str] = field(default_factory=dict)  # auth mode only
    # Secure mode: each participant's (pubkey, tag) hello, relayed to all
    # once everyone's arrived (keys_ready) — or, after the key grace
    # window, to the quorum subset that did arrive (key_set). The server
    # never holds any private key — it only forwards public values.
    pubkeys: dict[int, bytes] = field(default_factory=dict)
    key_set: list | None = None  # sorted ids the keys frame covered
    keys_ready: threading.Event = field(default_factory=threading.Event)
    # Double-masking (secure_protocol="double"): each dealer's encrypted
    # share blobs ({holder: blob}) + its b-seed commitment; U2 (share_set)
    # is the share-complete subset everyone masks over.
    share_blobs: dict[int, dict] = field(default_factory=dict)
    share_commits: dict[int, bytes] = field(default_factory=dict)
    share_set: list | None = None
    shares_ready: threading.Event = field(default_factory=threading.Event)
    # Central DP: each upload's declared round-base crc; the round only
    # aggregates when all are identical (a common anchor is what makes
    # the clipped-delta mean well-defined).
    dp_crcs: dict[int, int] = field(default_factory=dict)
    # Poisson cohort sampling (dp_participation < 1): the round's sampled
    # id set, drawn once per round from OS entropy; non-sampled clients
    # register here to receive the round's reply without contributing.
    cohort: set | None = None
    skip_conns: dict[int, socket.socket] = field(default_factory=dict)
    lock: threading.Lock = field(default_factory=threading.Lock)
    complete: threading.Event = field(default_factory=threading.Event)
    # Set (under lock) when serve_round snapshots the round; a handler that
    # finishes its recv after this must drop the connection, not register
    # into an abandoned round.
    closed: bool = False
    # True once any upload this round came from a sparse-delta-capable
    # client (meta ``delta`` or ``wants_delta``): gates the reply's
    # ``agg_crc`` stamp, a full fp32 pass + tobytes() copy over the whole
    # model that deployments with no topk client shouldn't pay every round.
    wants_delta: bool = False
    # Streaming chunk aggregation (comm/stream_agg.py): every plain/DP
    # upload — streamed or single-frame — registers here; leaves fold
    # into the running mean as they complete. None in secure-agg mode
    # (masked sums keep the barrier path).
    stream: Any = None
    # Clients whose upload meta advertised streamed-REPLY capability
    # (wire.STREAM_REPLY_META_KEY): their reply fan-out goes out as
    # STRH/STRC/STRT frames instead of one dense model-sized frame.
    stream_replies: set = field(default_factory=set)
    # Per-client quantized-reply capability (wire.REPLY_DTYPE_META_KEY):
    # the stream leaf encodings each stream-reply client said it can
    # dequantize. A --reply-dtype server only sends its lossy encoding
    # to clients whose advert includes it; everyone else gets the fp32
    # stream (capability-negotiated, like the upload leg's wire_dtypes).
    reply_dtype_encs: dict[int, tuple] = field(default_factory=dict)
    # Wire dtype each STREAMED upload actually arrived in ("fp32" /
    # "bf16" / "int8"), derived from its header's leaf encodings — the
    # wire-overlap span's wire_dtypes attr and the by-dtype /metrics
    # label. Dense single-frame uploads are not recorded here (their
    # encoding is the legacy compression knob, not a wire dtype).
    wire_dtypes: dict[int, str] = field(default_factory=dict)
    # Survivable fold trees (comm/relay.py): ids adopted into this round
    # via the re-home marker (wire.REHOME_META_KEY) — EXTRA contributors
    # from a dead sibling subtree. They fold with everyone else
    # (ascending id) but never count toward ``expected``, so adoption
    # cannot mask a local quorum miss; completion additionally waits for
    # every adopted upload to finish (they widen the fold set).
    adopted: set = field(default_factory=set)
    # Per-upload contributor record (wire.SUBTREE_IDS_META_KEY, stamped
    # by relays on their upward upload): uploader id -> the ascending
    # client ids its partial folded. The round's ACTUAL (relay ->
    # contributors) assignment — the crc contract's replay input — and
    # the double-count tripwire (one client in two subtrees' lists
    # fails the round loudly).
    subtree_ids: dict[int, list] = field(default_factory=dict)


class AggregationServer:
    """Receive ``num_clients`` models, FedAvg, reply on the same connections.

    ``serve_round()`` runs one round; ``serve(rounds=N)`` loops. A round
    deadline plus ``min_clients`` lets the mean proceed over survivors
    (masked mean) instead of hanging on a dead client.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        num_clients: int = 2,
        weighted: bool = False,
        min_clients: int | None = None,
        timeout: float = 300.0,  # the reference's TIMEOUT (server.py:10)
        compression: str = "none",
        auth_key: bytes | None = None,
        secure_agg: bool = False,
        fp_bits: int = secure.DEFAULT_FP_BITS,
        key_grace: float | None = None,
        dp_clip: float = 0.0,
        dp_noise_multiplier: float = 0.0,
        client_keys: dict[int, bytes] | None = None,
        secure_protocol: str = "double",
        secure_threshold: int | None = None,
        dp_participation: float = 1.0,
        dp_resync_rounds: int = 8,
        dp_history_path: str | None = None,
        tracer=None,
        stream_chunk_bytes: int = wire.DEFAULT_STREAM_CHUNK,
        strategy: str | None = None,
        strategy_state_path: str | None = None,
        reply_dtype: str = "fp32",
    ):
        if client_keys is not None and auth_key is None:
            raise ValueError(
                "client_keys (per-client DH identity binding) requires "
                "auth_key: the wire messages and the relayed keys frame "
                "are authenticated under the group key"
            )
        if dp_noise_multiplier > 0.0 and dp_clip <= 0.0:
            raise ValueError("dp_noise_multiplier needs dp_clip > 0")
        if dp_clip > 0.0 and weighted:
            raise ValueError(
                "central DP is a uniform mean over clipped updates; "
                "weighted=True is incompatible"
            )
        if secure_agg and weighted:
            raise ValueError(
                "secure aggregation is an unweighted ring sum; "
                "weighted=True is incompatible"
            )
        if secure_agg and min_clients is not None and min_clients < 2:
            raise ValueError(
                "secure aggregation needs min_clients >= 2: a lone "
                "survivor's 'sum' is its raw update"
            )
        if secure_protocol not in ("reveal", "double"):
            raise ValueError(
                f"secure_protocol {secure_protocol!r} must be reveal|double"
            )
        if secure_agg and secure_protocol == "double" and num_clients > 254:
            raise ValueError(
                "double-masking Shamir x-coordinates support <= 254 clients"
            )
        if secure_threshold is not None and secure_threshold < 2:
            raise ValueError(
                "secure_threshold < 2 would let the server reconstruct "
                "secrets from a single holder"
            )
        if not 0.0 < dp_participation <= 1.0:
            raise ValueError(
                f"dp_participation={dp_participation} must be in (0, 1]"
            )
        if dp_participation < 1.0 and dp_clip <= 0.0:
            raise ValueError(
                "dp_participation < 1 is the DP cohort sampler; it needs "
                "dp_clip > 0 (the sampling exists for the accountant's "
                "privacy amplification)"
            )
        if compression.startswith("topk"):
            raise ValueError(
                "topk is an upload-side (sparse round-delta) compression; "
                "the reply is an absolute aggregate — use none/bf16/int8"
            )
        # Quantized streamed replies (--reply-dtype): the downward mirror
        # of the upload leg's --wire-dtype. Only the STREAMED reply leg
        # quantizes — dense replies (old clients, non-advertisers, resync
        # payloads) stay exactly self.compression — so the knob composes
        # per client via capability negotiation, never by assumption.
        if reply_dtype not in wire.WIRE_DTYPE_ENCS:
            raise ValueError(
                f"reply_dtype {reply_dtype!r} must be one of "
                f"{sorted(wire.WIRE_DTYPE_ENCS)}"
            )
        if reply_dtype != "fp32":
            if secure_agg:
                # Mirror of the upload rule: the unmask protocol releases
                # the exact masked sum; a lossy re-encode of that release
                # would hand clients a DIFFERENT value than the protocol
                # authorized (and break the bit-exact base agreement the
                # masked rounds depend on).
                raise ValueError(
                    "lossy reply_dtype is refused under secure aggregation:"
                    " the unmask release is bit-exact by contract"
                )
            if compression != "none":
                raise ValueError(
                    "reply_dtype and a reply compression are two encoders "
                    "for the same leg; pass one (compression "
                    f"{compression!r} already re-encodes the reply)"
                )
        self.reply_dtype = reply_dtype
        # Server aggregation strategy (strategies/): a pure transform of
        # (previous global, folded mean) applied at finalize — the fold
        # itself is untouched, so "fedavg" is bit-identical to the
        # historical round. Validated here so a typo fails at construction,
        # not mid-round.
        self._strategy = strategies.make_strategy(strategy)
        if self._strategy.name != "fedavg":
            if secure_agg:
                raise ValueError(
                    f"strategy {self._strategy.name!r} is incompatible "
                    "with secure aggregation: the unmask protocol "
                    "releases exactly the masked SUM; a server-side "
                    "post-transform would operate on (and leak through) "
                    "a different release"
                )
            if dp_clip > 0.0:
                raise ValueError(
                    f"strategy {self._strategy.name!r} is incompatible "
                    "with central DP: the DP release is the noised mean "
                    "DELTA with a calibrated sensitivity; an optimizer "
                    "transform on top would change what is released "
                    "without re-deriving the bound"
                )
        self.num_clients = num_clients
        self.weighted = weighted
        self.min_clients = num_clients if min_clients is None else min_clients
        self.timeout = timeout
        self.compression = compression
        self.auth_key = auth_key
        self.secure_agg = secure_agg
        # "double" (default): full Bonawitz double-masking — self-mask +
        # Shamir-shared seeds, unmask round every round; closes the
        # false-death unmask and survives dropouts during unmasking.
        # "reveal": the cheaper reveal-round variant (no share
        # distribution; a dropout during its reveal fails the round).
        self.secure_protocol = secure_protocol
        # Shamir threshold; None = strict majority of the round's U2 (the
        # default that makes the either/or share-reveal rule binding).
        self.secure_threshold = secure_threshold
        self.fp_bits = fp_bits
        # Central DP (dp_clip > 0): uploads must be clipped round deltas
        # (the client flag --dp; the advert carries clip+noise); the
        # aggregate is mean(clipped deltas) + Gaussian(noise*clip/n), and
        # the reply is that noised mean DELTA — this server never holds
        # absolute model weights in DP mode. Base agreement is enforced by
        # requiring every upload's dp_base_crc to be identical.
        self.dp_clip = float(dp_clip)
        self.dp_noise_multiplier = float(dp_noise_multiplier)
        # Poisson cohort sampling rate: each registered client is drawn
        # independently with probability q every round — the sampler the
        # subsampled-Gaussian accountant assumes, so the TCP tier's
        # epsilon is exact under q < 1 (privacy amplification), mirroring
        # the mesh tier's participation_mode="poisson".
        self.dp_participation = float(dp_participation)
        # Stranded-client resync (plain DP only): the server retains the
        # last ``dp_resync_rounds`` released round deltas together with
        # the base crc their round's uploads agreed on. A client that
        # missed a reply declares a base crc matching one of those
        # retained rounds; instead of failing the whole round, its (stale)
        # upload is excluded from the mean and it is answered with the
        # catch-up SEQUENCE of retained deltas (every one from its base
        # forward, including this round's), which it replays in round
        # order — the same fp32 additions every current client performed,
        # so the resynced base matches the fleet's bit-exactly and the
        # next round's crc agreement holds. Privacy cost: zero — each
        # retained delta is a post-noise DP OUTPUT, and re-releasing
        # released values is post-processing. Memory cost:
        # dp_resync_rounds model-sized fp32 trees. Not available under
        # secure-agg DP (a masked upload cannot be excluded from the sum
        # — the masks only cancel over the full set), under lossy reply
        # compression (the fleet's bases are the DECODED deltas, which
        # the fp32 retention cannot reproduce), or across server
        # restarts (history is in-memory);
        # a client staler than the window still fails the round's crc
        # agreement exactly as before.
        self.dp_resync_rounds = int(dp_resync_rounds)
        self._dp_history: list[tuple[int, dict]] = []
        # Resync-history persistence (ROADMAP's last resync residual):
        # with a path set, the retained post-noise deltas are written
        # after every round and RELOADED on construction, so a server
        # restart between rounds no longer re-strands stale clients —
        # they heal bit-exactly from the reloaded fp32 history (npz is
        # lossless). Post-noise deltas are DP outputs: persisting and
        # re-releasing them is free post-processing, same argument as
        # the in-memory retention.
        self.dp_history_path = dp_history_path
        # Single background writer with a latest-snapshot slot: the
        # window is up to dp_resync_rounds model-sized fp32 trees, and
        # re-serializing it synchronously inside serve_round would put
        # GB-scale disk I/O on the aggregation critical path every
        # round. Entries are immutable once appended, so a snapshot
        # list is safe to write off-thread; close() drains the writer
        # so a clean shutdown always leaves the newest window on disk.
        self._dp_persist_lock = threading.Lock()
        self._dp_persist_pending: list | None = None
        self._dp_persist_thread: threading.Thread | None = None
        if dp_history_path:
            self._load_dp_history()
        # Noise generator: Philox (counter-based, 128-bit crypto-derived
        # keying) keyed from OS entropy, never seeded deterministically —
        # the draw sequence is not predictable from any run artifact.
        # Residual caveat (stated in the serve banner): the samples are
        # float32 Gaussians, which the Mironov (2012) floating-point
        # precision attack applies to; a fully attack-hardened mechanism
        # would use a discrete Gaussian over the integers.
        self._dp_rng = np.random.Generator(
            np.random.Philox(key=int.from_bytes(os.urandom(16), "little"))
        )
        # Per-client DH identity keys (secure.py threat model): a hello
        # claiming id i must carry a tag under client i's OWN key, so no
        # group member can impersonate another in the key exchange.
        self.client_keys = dict(client_keys) if client_keys else None
        # Dropout-before-keys window: once a connected participant has
        # waited this long without the full fleet's DH hellos, the key set
        # closes at the min_clients quorum and the round proceeds without
        # the missing clients (secure.py "dropout recovery"). This is the
        # liveness/straggler trade-off knob: a client arriving after the
        # cut is ejected for the ROUND (its retries fail fast; it rejoins
        # next round), so the default is half the round budget — generous
        # to compute/shard skew, while a genuinely dead client still costs
        # at most half the deadline instead of failing the round outright.
        self.key_grace = timeout / 2.0 if key_grace is None else key_grace
        # Monotonic round counter plus a per-run random session nonce,
        # advertised to secure clients on connect: mask streams are keyed
        # by (session, round), so they are fresh across rounds AND across
        # server restarts (a restarted counter alone would reuse streams,
        # letting an observer difference two runs' uploads).
        self._round_counter = 0
        self._session = os.urandom(16)
        # Last completed aggregate (flat fp32) + its round index: the base
        # that sparse-delta (topk) uploads difference against. Advertised
        # to clients via the reply's ``agg_round`` meta; a restarted server
        # has no base and rejects delta uploads, which makes clients fall
        # back to a dense resend.
        self._last_agg: dict | None = None
        self._last_agg_round = -1
        # Server-state persistence (``strategy_state_path``): the last
        # post-strategy global, its round index, and the strategy's
        # optimizer-state leaves, written atomically after every plain
        # finalized round (dp_history_path's background-writer pattern)
        # and RELOADED on construction. Closes the PR 16 residual where
        # a restarted FedOpt/momentum root lost its optimizer memory and
        # re-adopted the bare mean on its first round — and, since
        # ``_last_agg``/``_last_agg_round`` come back too, sparse-delta
        # clients keep their base across the restart instead of paying a
        # dense resend. A reloaded state whose strategy describe() does
        # not match the configured strategy is ignored (operator swapped
        # strategies between runs: fresh optimizer memory is correct).
        self.strategy_state_path = strategy_state_path
        self._strategy_persist_lock = threading.Lock()
        self._strategy_persist_pending: tuple | None = None
        self._strategy_persist_thread: threading.Thread | None = None
        if strategy_state_path:
            self._load_strategy_state()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        # Backlog sized for fleet cohorts: 256 clients dialing one round
        # start simultaneously overflow the old num_clients*2 backlog on
        # small fleets' defaults (refused dials burn client retries);
        # the kernel clamps to SOMAXCONN, so asking high is free.
        self._sock.listen(max(128, num_clients * 2))
        self._sock.settimeout(timeout)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        # Bounded upload-handler pool (one task per accepted connection).
        # Unbounded thread-per-dial let a retry storm (or a port scan)
        # spawn without limit; the bound must still exceed the fleet —
        # secure-mode handlers all block concurrently on the DH/key
        # rendezvous, and duplicate retry dials legitimately coexist with
        # the originals — hence 2x the fleet plus slack, queueing the
        # excess instead of spawning it.
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(
            max_workers=2 * num_clients + 8,
            thread_name_prefix="fedtpu-upload",
        )
        # Every connection a handler is CURRENTLY serving (registered or
        # not — a mid-upload child is not in rnd.conns yet): close()
        # must be able to shed them all promptly.
        self._conn_lock = threading.Lock()
        self._open_conns: set[socket.socket] = set()
        # Observability (obs/): optional span tracer + always-on cheap
        # phase accounting. phase_seconds accumulates where each round's
        # wall went — wait (accept + straggler + upload wire), agg
        # (aggregation compute), reply (fan-out) — the measured comm/
        # compute breakdown the timeline tool and the /metrics endpoint
        # report (tests/test_obs.py). last_trace is the most recent
        # round's (trace id, round index) for callers (the controller)
        # that stamp their own follow-on spans with the round's identity.
        # Streamed uploads + streaming chunk aggregation (PR 5): the
        # preferred chunk size advertised in every reply's meta (wire.py
        # STREAM_META_KEY — plain meta, old clients interop unchanged).
        # 0 disables BOTH the advert and eager folding: every round then
        # runs the stop-the-world barrier shape (test_stream.py's A/B arm).
        # Secure-agg rounds never advertise: a masked upload's unmask
        # protocol needs the full contributor set resolved before any
        # aggregate exists, so those stay single-frame by design.
        stream_chunk_cap = framing.MAX_FRAME - wire.STREAM_CHUNK_OVERHEAD
        if not 0 <= int(stream_chunk_bytes) <= stream_chunk_cap:
            # The cap leaves room for the STRC envelope (magic + seq +
            # auth tag): a full chunk must still encode into a frame the
            # transport accepts, or every streamed attempt would fail
            # and silently pay a dense retry.
            raise ValueError(
                f"stream_chunk_bytes={stream_chunk_bytes} must be in "
                f"[0, {stream_chunk_cap}] (0 = streaming off)"
            )
        self.stream_chunk_bytes = int(stream_chunk_bytes)
        # Cross-round streaming totals: bytes folded during the wait
        # phase (overlapped with the wire) vs after it, and the peak
        # aggregation-state footprint — what comm_overlap_frac() and the
        # wire-overlap span's peak_agg_bytes report.
        # One lock for every stream_totals mutation: upload handlers on
        # the pool increment fallback/upload counters while serve_round
        # folds reply/peak stats — per-key dict ops are GIL-atomic, but
        # the discipline "all writers hold _totals_lock" is what the
        # static concurrency pass can actually verify, and it makes the
        # read side (comm_overlap_frac's two-key ratio) consistent
        # instead of torn-across-keys.
        self._totals_lock = threading.Lock()
        self.stream_totals = {
            "early_bytes": 0,
            "late_bytes": 0,
            "early_s": 0.0,
            "late_s": 0.0,
            "peak_agg_bytes": 0,
            "last_round_peak_bytes": 0,
            "stream_uploads": 0,
            # Reply-side streaming + fallback accounting (PR 7): replies
            # shipped as chunk streams, and dense uploads accepted while
            # the streaming advert was active (topk/secure-agg/old-peer/
            # retry fallbacks — the client logs its one-line reason).
            "stream_replies": 0,
            "stream_fallbacks": 0,
            # Compiled-fold telemetry (ops/fold.py), refreshed per round.
            "fold_engine": "",
            "last_fold_throughput_gbps": 0.0,
        }
        # Hierarchical fold tree hook (comm/relay.py): when set, the
        # plain round's aggregate is handed to this callable BETWEEN
        # aggregation and the reply fan-out — the relay forwards the
        # subtree partial to its parent and returns the ROOT aggregate,
        # which is what this subtree's clients then receive (and what
        # next round's sparse-delta base tracks). Incompatible with DP
        # (the partial would be an un-noised release) and secure-agg
        # (the unmask protocol needs the single-aggregator shape).
        self.reply_via = None
        self.tracer = tracer
        self.phase_seconds = {"wait": 0.0, "agg": 0.0, "reply": 0.0}
        self.last_trace: tuple[str, int] | None = None
        m = obs_metrics.default_registry()
        self._m_stream_uploads = m.counter(
            "fedtpu_server_stream_uploads_total",
            help="chunk-streamed client uploads accepted into a round",
        )
        self._m_stream_replies = m.counter(
            "fedtpu_server_stream_replies_total",
            help="aggregate replies fanned out as chunk streams",
        )
        self._m_stream_fallbacks = m.counter(
            "fedtpu_server_stream_fallbacks_total",
            help="dense single-frame uploads accepted while streaming "
            "was advertised (topk/secure-agg/old-peer/retry fallbacks)",
        )
        self._g_inflight_streams = m.gauge(
            "fedtpu_server_stream_inflight",
            help="chunk-streamed uploads currently mid-transfer",
        )
        # Wire efficiency (quantized uploads + compiled fold): uploads
        # by the wire dtype they actually arrived in, and the last
        # round's fold throughput. Label families are created per value
        # at record time (the registry memoizes on (name, labels)).
        self._m_uploads_by_dtype = lambda wd: m.counter(
            "fedtpu_server_stream_uploads_by_wire_dtype_total",
            help="chunk-streamed uploads accepted, by negotiated wire "
            "dtype (fp32|bf16|int8)",
            labels={"wire_dtype": wd},
        )
        self._g_fold_throughput = m.gauge(
            "fedtpu_server_fold_throughput_gbps",
            help="last round's fold throughput (bytes folded / fold "
            "seconds), by the active fold engine",
        )
        self._g_peak_agg = m.gauge(
            "fedtpu_server_peak_agg_bytes",
            help="peak aggregation-state bytes of the last round "
            "(accumulator + pending leaves)",
        )
        self._m_rounds = m.counter(
            "fedtpu_server_rounds_total",
            help="aggregation rounds started",
        )
        # Strategy plane (strategies/): rounds finalized per strategy —
        # the /metrics label postmortems join against the round trace's
        # strategy attr and the reply meta stamp. Created per label value
        # at finalize (set_strategy can swap mid-run); the registry
        # memoizes on (name, labels) so this is the single family owner.
        self._m_strategy_rounds = lambda name: m.counter(
            "fedtpu_strategy_rounds_total",
            help="aggregation rounds finalized, by server strategy",
            labels={"strategy": name},
        )
        self._m_round_failures = m.counter(
            "fedtpu_server_round_failures_total",
            help="rounds that raised (quorum miss, deadline, bad uploads)",
        )
        self._m_uploads = m.counter(
            "fedtpu_server_uploads_total",
            help="client model uploads accepted into a round",
        )
        self._m_bytes_in = m.counter(
            "fedtpu_server_wire_bytes_received_total",
            help="model upload payload bytes received",
        )
        self._m_bytes_out = m.counter(
            "fedtpu_server_wire_bytes_sent_total",
            help="aggregate reply payload bytes sent",
        )
        self._m_phase = {
            p: m.counter(
                "fedtpu_server_round_phase_seconds_total",
                help="round wall-clock by phase (wait|agg|reply)",
                labels={"phase": p},
            )
            for p in ("wait", "agg", "reply")
        }
        self._m_subtree_failures = m.counter(
            "fedtpu_relay_subtree_failures_total",
            help="expected fold-tree children missing from a completed "
            "round at a parent of relays (the subtree was dropped from "
            "the fold; the mean renormalized over survivors)",
        )
        self._m_stragglers_shed = m.counter(
            "fedtpu_relay_stragglers_shed_total",
            help="expected leaf clients missing from a completed quorum "
            "round (shed locally at this aggregator's deadline instead "
            "of stalling its parent)",
        )
        # Plain attribute twins for harnesses that hold the server object
        # (the scenario harness, tests): mutated under _totals_lock like
        # stream_totals.
        self.tree_totals = {
            "subtree_failures": 0,
            "stragglers_shed": 0,
            "degraded_rounds": 0,
        }
        # The last completed round's ACTUAL aggregation assignment:
        # {"round": n, "groups": [...]} where each group is either a
        # bare uploader id (a leaf client / relay with no contributor
        # record) or the list of client ids a relay's partial folded —
        # exactly aggregate_tree's ``groups`` argument, in the root's
        # fold order. The crc contract replays over THIS, so a degraded
        # round (dead subtree, re-homed contributors) stays bit-exactly
        # checkable.
        self.last_assignment: dict | None = None
        self._cur_rnd: _Round | None = None
        self._h_round = m.histogram(
            "fedtpu_server_round_seconds",
            help="aggregation round wall-clock, failed rounds included "
            "(the round-duration SLO's burn-rate source, obs/slo.py)",
            buckets=(0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0),
        )

    # -------------------------------------------------------------- strategy
    @property
    def strategy(self) -> "strategies.Strategy":
        return self._strategy

    def set_strategy(self, spec) -> "strategies.Strategy":
        """Swap the aggregation strategy BETWEEN rounds (per-round
        selection: a controller reads the round-START meta, decides, and
        swaps before calling ``serve_round``). Same compatibility rules
        as the constructor; optimizer state starts fresh — a strategy's
        server-optimizer memory is meaningless across a rule change."""
        strat = strategies.make_strategy(spec)
        if strat.name != "fedavg" and (self.secure_agg or self.dp_clip > 0.0):
            raise ValueError(
                f"strategy {strat.name!r} is incompatible with "
                "secure-agg/DP rounds (see the constructor's rationale)"
            )
        self._strategy = strat
        return strat

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        self._stop.set()
        self._sock.close()
        # Shed the current round's registered connections as EXPLICIT
        # failures, promptly: shutdown(SHUT_RDWR) interrupts both ends'
        # blocked recvs (the child waiting on its reply, the handler
        # mid-stream) where a bare close() is deferred by the
        # interpreter while a sibling thread sits in a syscall on the fd
        # (the faults-layer prompt-close discipline, PR 6). Without
        # this, a relay torn down mid-round left its children blocked
        # until their own socket timeouts — exactly the window client
        # re-homing needs to be short.
        rnd = self._cur_rnd
        shed: list[socket.socket] = []
        if rnd is not None:
            with rnd.lock:
                shed += list(rnd.conns.values()) + list(
                    rnd.skip_conns.values()
                )
        with self._conn_lock:
            # Mid-upload connections too: their handlers are still
            # reading the payload, so they are not registered yet — but
            # their clients are equally blocked and must fail now.
            shed += list(self._open_conns)
        for c in shed:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # Queued-but-unstarted handler tasks are abandoned (their
        # connections close with the process); running ones are daemons
        # of the pool and drop out on their socket errors.
        self._pool.shutdown(wait=False, cancel_futures=True)
        # Drain the history writer: a clean shutdown must leave the
        # NEWEST resync window on disk, or a restart would re-strand
        # exactly the clients persistence exists to heal.
        with self._dp_persist_lock:
            t = self._dp_persist_thread
        if t is not None:
            t.join(timeout=60.0)
        # Same drain for the strategy-state writer: a clean shutdown is
        # exactly the restart this persistence exists to survive.
        with self._strategy_persist_lock:
            t = self._strategy_persist_thread
        if t is not None:
            t.join(timeout=60.0)

    def __enter__(self) -> "AggregationServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------------- round
    def _handle_upload(
        self, conn: socket.socket, rnd: _Round, deadline: float | None = None
    ) -> None:
        if deadline is None:
            deadline = time.monotonic() + self.timeout
        with self._conn_lock:
            self._open_conns.add(conn)
        try:
            conn.settimeout(self.timeout)
            nonce_hex = None
            if self.auth_key is not None:
                # Freshness + direction binding: a per-connection challenge
                # the client must echo inside its authenticated header, so a
                # captured upload can't be replayed into a later round, and
                # the reply (which echoes the same nonce with role=server)
                # can't be reflected. Without a key, the wire is the
                # reference-style open protocol and no challenge is sent.
                nonce_hex = os.urandom(wire.NONCE_LEN).hex()
                framing.send_frame(
                    conn, wire.NONCE_MAGIC + bytes.fromhex(nonce_hex)
                )
            dpid = None
            if self.dp_clip > 0.0:
                import struct as _dstruct

                # DP handshake, server-first: the mode advert lets a
                # mis-configured plain client diagnose the mismatch; the
                # client then identifies itself so the round's Poisson
                # cohort verdict can be made (and told) before any model
                # bytes move.
                framing.send_frame(
                    conn,
                    wire.DP_MAGIC
                    + _dstruct.pack(
                        "<ddd",
                        self.dp_clip,
                        self.dp_noise_multiplier,
                        self.dp_participation,
                    ),
                )
                idf = framing.recv_frame(conn)
                if len(idf) != len(wire.DPID_MAGIC) + 8 or (
                    not idf.startswith(wire.DPID_MAGIC)
                ):
                    raise wire.WireError("bad DP id hello")
                dpid = _dstruct.unpack("<q", idf[len(wire.DPID_MAGIC) :])[0]
                if not 0 <= dpid < self.num_clients:
                    raise wire.WireError(f"DP id hello from unknown client {dpid}")
                with rnd.lock:
                    sampled = rnd.cohort is None or dpid in rnd.cohort
                framing.send_frame(
                    conn,
                    wire.DPCOHORT_MAGIC + bytes([1 if sampled else 0]),
                )
                if not sampled:
                    # Sitting out: no upload, but the client still gets
                    # the round's reply (its base must track the fleet's).
                    if self.auth_key is not None:
                        # The contributor path authenticates via its
                        # HMAC'd upload; a sitting-out client must prove
                        # key knowledge too, or anyone could claim a
                        # non-sampled id, evict the real registration,
                        # and collect the aggregate.
                        import hmac as _hmac

                        ack = framing.recv_frame(conn)
                        want = wire.DPSKIP_MAGIC + _hmac.new(
                            self.auth_key,
                            wire.DPSKIP_DOMAIN
                            + bytes.fromhex(nonce_hex)
                            + _dstruct.pack("<q", dpid),
                            "sha256",
                        ).digest()
                        if not _hmac.compare_digest(ack, want):
                            raise wire.WireError(
                                f"sit-out ack for client {dpid} failed "
                                "its authenticity check"
                            )
                    with rnd.lock:
                        if rnd.closed:
                            conn.close()
                            return
                        old = rnd.skip_conns.pop(dpid, None)
                        if old is not None and old is not conn:
                            old.close()
                        rnd.skip_conns[dpid] = conn
                        if nonce_hex is not None:
                            rnd.nonces[dpid] = nonce_hex
                        done = self._round_done(rnd)
                    log.info(
                        f"[SERVER] client {dpid} sits out round "
                        f"{rnd.round_no} (cohort sampling "
                        f"q={self.dp_participation})"
                    )
                    if done:
                        rnd.complete.set()
                    return
            if self.secure_agg:
                # Advertise (round, session, protocol) so every participant
                # keys its mask streams identically — and freshly — for
                # this round, and speaks the same recovery protocol. The
                # client REFUSES a protocol differing from its own config
                # (a malicious advert can't downgrade double -> reveal).
                import struct as _struct

                proto = (
                    secure.PROTO_DOUBLE
                    if self.secure_protocol == "double"
                    else secure.PROTO_REVEAL
                )
                framing.send_frame(
                    conn,
                    wire.ROUND_MAGIC
                    + _struct.pack("<Q", rnd.round_no)
                    + self._session
                    + bytes([proto]),
                )
                # DH relay: collect this client's ephemeral public key,
                # wait for the full fleet's, then hand everyone the whole
                # set. The server only forwards public values — it cannot
                # derive any pair's mask secret.
                hello = framing.recv_frame(conn)
                tag_len = wire.AUTH_TAG_LEN if self.auth_key is not None else 0
                want_len = len(wire.PUBKEY_MAGIC) + 8 + secure.DH_PUB_LEN + tag_len
                if len(hello) != want_len or not hello.startswith(wire.PUBKEY_MAGIC):
                    raise wire.WireError("bad DH pubkey hello")
                off = len(wire.PUBKEY_MAGIC)
                hello_id = _struct.unpack("<q", hello[off : off + 8])[0]
                pub_and_tag = hello[off + 8 :]
                pub = pub_and_tag[: secure.DH_PUB_LEN]
                secure.check_dh_public(pub)
                if self.auth_key is not None:
                    if self.client_keys is not None:
                        # Identity binding: the tag must verify under the
                        # CLAIMED id's own key — a member holding only its
                        # own key (and the group key) cannot forge it.
                        hello_key = self.client_keys.get(hello_id)
                        if hello_key is None:
                            raise wire.WireError(
                                f"DH hello from client {hello_id} with no "
                                "registered per-client key"
                            )
                    else:
                        hello_key = self.auth_key
                    secure.verify_pubkey_tag(
                        hello_key, self._session, rnd.round_no,
                        hello_id, pub,
                        pub_and_tag[secure.DH_PUB_LEN :],
                    )
                    if self.client_keys is not None:
                        # Re-tag under the GROUP key for the relay:
                        # receivers hold the group key, not each other's
                        # identity keys. (The server attests what it
                        # verified — a malicious server could lie, which
                        # is the documented remaining adversary.)
                        pub_and_tag = pub + secure.pubkey_tag(
                            self.auth_key, self._session, rnd.round_no,
                            hello_id, pub,
                        )
                with rnd.lock:
                    if rnd.closed:
                        conn.close()
                        return
                    if not 0 <= hello_id < self.num_clients:
                        raise wire.WireError(
                            f"DH hello from unknown client id {hello_id}"
                        )
                    prev_hello = rnd.pubkeys.get(hello_id)
                    if prev_hello is not None and prev_hello != pub_and_tag:
                        # First registration wins. A DIFFERENT key for an
                        # already-registered id is either an impersonation
                        # attempt or a client that lost its per-round
                        # keypair — after distribution a new key could
                        # never cancel, and before it, honoring the swap
                        # would let a group member evict the honest holder.
                        log.info(
                            f"[SERVER] conflicting DH hello for client "
                            f"{hello_id}; dropping connection"
                        )
                        conn.close()
                        return
                    if prev_hello is None and rnd.keys_ready.is_set():
                        # Keys already relayed: a NEW participant key now
                        # would break mask cancellation for everyone who
                        # already derived pair secrets.
                        log.info(
                            f"[SERVER] late DH hello from client {hello_id} "
                            "after key distribution; dropping connection"
                        )
                        conn.close()
                        return
                    # Fresh registration, or an idempotent re-hello (same
                    # pubkey — a retrying client reuses its per-round
                    # keypair) which re-binds the connection.
                    old = rnd.conns.pop(hello_id, None)
                    if old is not None and old is not conn:
                        old.close()
                    rnd.pubkeys[hello_id] = pub_and_tag
                    # Register now so a failed round's cleanup closes this
                    # socket instead of leaving the client blocked on the
                    # keys frame until its own timeout.
                    rnd.conns[hello_id] = conn
                    if len(rnd.pubkeys) >= rnd.expected:
                        rnd.key_set = sorted(rnd.pubkeys)
                        rnd.keys_ready.set()
                log.info(
                    f"[SERVER] DH pubkey from client {hello_id} "
                    f"({len(rnd.pubkeys)}/{rnd.expected})"
                )
                # Wait for the full fleet's hellos — but after key_grace
                # without completion, close the key set at the quorum that
                # did arrive (dropout-before-keys recovery): the round
                # proceeds over the subset instead of failing outright.
                grace_end = time.monotonic() + self.key_grace
                while not rnd.keys_ready.is_set():
                    now = time.monotonic()
                    if now >= deadline:
                        raise wire.WireError(
                            "round deadline passed waiting for the "
                            "remaining participants' DH public keys"
                        )
                    # Before grace expiry, wake at grace_end to try the
                    # quorum close; after (quorum not met yet), sleep
                    # until the deadline — another hello's handler will
                    # close the set and wake everyone if a quorum forms.
                    wait_until = grace_end if now < grace_end else deadline
                    if rnd.keys_ready.wait(
                        timeout=max(0.0, wait_until - now)
                    ):
                        break
                    with rnd.lock:
                        if (
                            not rnd.keys_ready.is_set()
                            and time.monotonic() >= grace_end
                            and len(rnd.pubkeys) >= max(2, self.min_clients)
                        ):
                            rnd.key_set = sorted(rnd.pubkeys)
                            rnd.keys_ready.set()
                            log.info(
                                f"[SERVER] key grace expired; closing the "
                                f"key set at quorum {rnd.key_set}"
                            )
                            break
                with rnd.lock:
                    key_set = list(rnd.key_set or [])
                    entries = b"".join(
                        _struct.pack("<q", cid) + rnd.pubkeys[cid]
                        for cid in key_set
                    )
                if hello_id not in key_set:
                    # Arrived during finalization but after the cut: a key
                    # outside the distributed set could never cancel.
                    log.info(
                        f"[SERVER] client {hello_id} missed the key set "
                        f"{key_set}; dropping connection"
                    )
                    conn.close()
                    return
                framing.send_frame(conn, wire.KEYS_MAGIC + entries)
                if self.secure_protocol == "double":
                    if not self._shares_exchange(
                        conn, rnd, hello_id, key_set, deadline
                    ):
                        return
            payload = framing.recv_frame(conn)
            self._m_bytes_in.inc(float(len(payload)))
            if bytes(payload[:4]) == wire.STREAM_MAGIC:
                # Streamed upload (wire.py "Streamed uploads"): header
                # now, leaves folded into the running mean as chunks
                # arrive. Only plain/DP rounds aggregate incrementally.
                self._handle_stream_upload(
                    conn, payload, rnd, nonce_hex=nonce_hex, dpid=dpid
                )
                return
            flat, meta = wire.decode(payload, auth_key=self.auth_key)
            # Cohort enforcement needs no separate membership check here:
            # a non-sampled dpid already returned on the sit-out path
            # (its upload frame is never read as a model), and this id
            # binding stops a sampled connection smuggling another id.
            client_id = self._validate_upload_identity(
                meta, nonce_hex=nonce_hex, dpid=dpid
            )
            flat = wire.flatten_params(flat)
            is_delta = bool(meta.get("delta", False))
            if is_delta:
                if self.secure_agg:
                    raise wire.WireError(
                        "sparse-delta upload in secure-aggregation mode"
                    )
                base = self._last_agg
                try:
                    base_round = int(meta.get("base_agg_round", -2))
                except (TypeError, ValueError):
                    raise wire.WireError(
                        f"malformed base_agg_round "
                        f"{meta.get('base_agg_round')!r} in delta upload"
                    ) from None
                if base is None or base_round != self._last_agg_round:
                    raise wire.WireError(
                        f"delta upload against base round "
                        f"{meta.get('base_agg_round')} but server base is "
                        f"{self._last_agg_round if base is not None else 'absent'} "
                        "(restart or stale client) — client will resend dense"
                    )
                if not wire.shapes_compatible(flat, base):
                    raise wire.WireError(
                        "delta upload's tensor set/shapes do not match the base"
                    )
            if bool(meta.get("secure", False)) != self.secure_agg:
                raise wire.WireError(
                    f"secure-aggregation mode mismatch: server "
                    f"secure_agg={self.secure_agg}, upload "
                    f"secure={meta.get('secure', False)}"
                )
            dp_mode, dp_crc = self._validate_dp_meta(meta, is_delta=is_delta)
            if dp_mode:
                if not self.secure_agg:
                    # ENFORCED clipping (not just trusted): a client that
                    # skipped its clip cannot widen the mechanism's
                    # sensitivity for anyone. (Masked uploads can't be
                    # re-clipped; there the guarantee assumes honest
                    # clients clip, as standard for secure-agg DP.)
                    norm = wire.flat_l2_norm(flat)
                    if norm > self.dp_clip * (1.0 + 1e-5):
                        flat, _, _ = wire.clip_flat(flat, self.dp_clip)
                        log.info(
                            f"[SERVER] re-clipped client "
                            f"{meta.get('client_id')}'s delta "
                            f"({norm:.4g} -> {self.dp_clip})"
                        )
            if self.secure_agg:
                if int(meta.get("fp_bits", -1)) != self.fp_bits:
                    raise wire.WireError(
                        f"secure upload fp_bits={meta.get('fp_bits')} != server "
                        f"fp_bits={self.fp_bits}: de-quantization would be wrong"
                    )
                with rnd.lock:
                    mask_set = (
                        rnd.share_set
                        if self.secure_protocol == "double"
                        else rnd.key_set
                    )
                    n_mask = len(mask_set or [])
                if int(meta.get("participants", -1)) != n_mask:
                    # A client masking against a different participant set
                    # would carry uncancelled pair masks — the sum would
                    # silently de-quantize to ring noise.
                    raise wire.WireError(
                        f"secure upload masked for "
                        f"{meta.get('participants')} participants, server "
                        f"distributed the round's mask set to {n_mask}"
                    )
                if int(meta.get("round", -1)) != rnd.round_no:
                    raise wire.WireError(
                        f"secure upload keyed to round {meta.get('round')}, "
                        f"server round is {rnd.round_no}"
                    )
            with rnd.lock:
                if rnd.closed:
                    # Round already snapshotted (deadline hit mid-upload):
                    # close so the client fails fast and retries next round
                    # instead of blocking on a reply that will never come.
                    log.info(
                        f"[SERVER] late upload from client {client_id} after "
                        "round close; dropping connection"
                    )
                    conn.close()
                    return
                if not self._register_tree_meta(
                    rnd, conn, client_id, meta
                ):
                    return
                dup_folded = False
                if client_id in rnd.models or (
                    # A still-in-flight STREAM from this client (intent
                    # registered, trailer not yet processed) is a
                    # duplicate too: a dense retry must not stack a
                    # second intent/leaf set on top of it (the stalled
                    # handler's cleanup would then poison the round or
                    # strip the retry's state out from under it).
                    rnd.stream is not None
                    and client_id in rnd.stream.intents
                ):
                    # Replace the first upload — unless aggregation folds
                    # already consumed it (streaming path): then the
                    # folded original STANDS, and only the connection is
                    # adopted so the (usually retrying, dead-socketed)
                    # client still gets the round's reply.
                    dup_folded = rnd.stream is not None and (
                        not rnd.stream.drop_client(client_id, poison=False)
                    )
                    log.info(
                        f"[SERVER] duplicate upload from client "
                        f"{client_id}; "
                        + (
                            "keeping the already-aggregated original"
                            if dup_folded
                            else "replacing"
                        )
                    )
                    old = rnd.conns.pop(client_id, None)
                    if old is not None and old is not conn:
                        old.close()
                    if dup_folded and client_id not in rnd.models:
                        # The folded original is an in-flight stream that
                        # never reached its trailer (its socket is dead);
                        # mark the client complete from its intent so the
                        # round doesn't barrier on a connection that will
                        # never finish.
                        it = rnd.stream.intents.get(client_id, {})
                        rnd.models[client_id] = {}
                        rnd.deltas[client_id] = bool(it.get("delta", False))
                        if it.get("dp_crc") is not None:
                            rnd.dp_crcs[client_id] = it["dp_crc"]
                        rnd.n_samples[client_id] = float(
                            it.get("n_samples", 1.0)
                        )
                        if set(flat) == set(it.get("keys", ())) and bool(
                            is_delta
                        ) == bool(it.get("delta", False)):
                            # Folds consumed the original's early leaves
                            # and its socket will never deliver the rest;
                            # the retry re-sends the same upload, so its
                            # (validated, re-clipped) leaves complete the
                            # remaining folds to the exact barrier mean.
                            # A diverging retry (key set / mode mismatch)
                            # skips this and fails the ROUND at finalize,
                            # never the server.
                            rnd.stream.add_dense(client_id, flat)
                if not dup_folded:
                    # In a plain/DP round the StreamAgg owns the upload's
                    # arrays (registered below) and frees each leaf as it
                    # folds — keep only the completion sentinel here, so
                    # dense clients reach the O(model + in-flight) peak
                    # too. The secure path has no StreamAgg and
                    # aggregates from rnd.models directly.
                    rnd.models[client_id] = (
                        {} if rnd.stream is not None else flat
                    )
                    rnd.deltas[client_id] = is_delta
                    if dp_crc is not None:
                        rnd.dp_crcs[client_id] = dp_crc
                    rnd.n_samples[client_id] = float(meta.get("n_samples", 1.0))
                if is_delta or bool(meta.get("wants_delta", False)):
                    rnd.wants_delta = True
                if bool(meta.get(wire.STREAM_REPLY_META_KEY, False)):
                    rnd.stream_replies.add(client_id)
                    encs = meta.get(wire.REPLY_DTYPE_META_KEY)
                    if isinstance(encs, (list, tuple)):
                        rnd.reply_dtype_encs[client_id] = tuple(
                            str(e) for e in encs
                        )
                if (
                    self.stream_chunk_bytes > 0
                    and not self.secure_agg
                    and not dup_folded
                ):
                    # Upload arrived dense while streaming was advertised:
                    # a fallback (old peer, topk, a retry, or round 1
                    # before the client saw the advert). The client logs
                    # its one-line reason; this side just counts.
                    with self._totals_lock:
                        self.stream_totals["stream_fallbacks"] += 1
                    self._m_stream_fallbacks.inc()
                rnd.conns[client_id] = conn
                if nonce_hex is not None:
                    rnd.nonces[client_id] = nonce_hex
                if rnd.stream is not None and not dup_folded:
                    # Single-frame uploads join the same incremental fold
                    # as streamed ones (mixed fleets fold in one pass).
                    rnd.stream.register(
                        client_id,
                        keys=tuple(flat),
                        n_samples=float(meta.get("n_samples", 1.0)),
                        delta=is_delta,
                        dp_crc=dp_crc,
                    )
                    rnd.stream.add_dense(client_id, flat)
                    self._try_freeze_stream(rnd)
                done = self._round_done(rnd)
            self._m_uploads.inc()
            log.info(
                f"[SERVER] received model from client {client_id} "
                f"({len(rnd.models)}/{rnd.expected})"
            )
            if done:
                rnd.complete.set()
        except (
            OSError,
            wire.WireError,
            secure.SecureAggError,
            ConnectionError,
            # Defense in depth: meta fields are attacker-controlled, and a
            # parse slipping through as ValueError/TypeError must still
            # close the connection instead of killing the thread and
            # leaving the client blocked until its socket timeout.
            ValueError,
            TypeError,
            # A decode that survives the size caps but still overcommits
            # (many large-claiming tensors in one message) must close the
            # connection, not kill the handler thread.
            MemoryError,
        ) as e:
            log.info(f"[SERVER] upload failed: {e}")
            conn.close()
        finally:
            with self._conn_lock:
                self._open_conns.discard(conn)

    def _round_done(self, rnd: _Round) -> bool:
        """Round completion test (caller holds ``rnd.lock``): every
        expected upload arrived — the full fleet, the secure keyed subset,
        or the sampled cohort — AND, under cohort sampling, every
        non-sampled client has connected to collect the round's reply
        (their bases must track the fleet's). Adopted (re-homed) ids
        never count toward ``expected`` — adoption must not let a
        stranger's upload mask a missing local child — but every adopted
        upload must itself complete before the round does (it widened
        the fold set)."""
        own = len(rnd.models) - len(rnd.adopted & set(rnd.models))
        uploads_done = (
            own >= rnd.expected and rnd.adopted <= set(rnd.models)
        ) or (
            # Secure subset round (dropout before keys): complete as soon
            # as every KEYED participant uploaded — the unkeyed never will.
            self.secure_agg
            and rnd.key_set is not None
            and set(rnd.key_set).issubset(rnd.models)
        )
        if rnd.cohort is None:
            return uploads_done
        skips_done = (
            len(rnd.skip_conns) >= self.num_clients - len(rnd.cohort)
        )
        return uploads_done and skips_done

    def _try_freeze_stream(self, rnd: _Round) -> None:
        """Freeze the round's fold set once every expected client's
        upload intent has arrived (caller holds ``rnd.lock``). Mirrors
        the close-time contributor logic — DP staleness partition
        included — over the SAME inputs, so the frozen set always equals
        the set ``serve_round`` later aggregates over (``_dp_history`` is
        only mutated in the agg phase, after the wait ends). A DP fleet
        whose current-base clients disagree on their crc is left
        unfrozen: nothing folds, and the close-time path raises the
        usual base-mismatch error."""
        st = rnd.stream
        if (
            st is None
            or not st.eager
            or st.fold_ids is not None
            or st.poisoned
        ):
            return
        have = set(st.intents)
        if rnd.cohort is not None:
            if not set(rnd.cohort).issubset(have):
                return
            ids_all = sorted(rnd.cohort)
        else:
            # Adopted (re-homed) intents join the fold set but do not
            # satisfy the expected count — freezing over strangers while
            # a local child is still dialing would fix the weights
            # without it.
            if len(have - rnd.adopted) < rnd.expected:
                return
            ids_all = sorted(have)
        if self.dp_clip > 0.0:
            crcs = {c: st.intents[c].get("dp_crc") for c in ids_all}
            hist = {crc for crc, _ in self._dp_history}
            stale = [c for c in ids_all if crcs[c] in hist]
            current = [c for c in ids_all if c not in stale]
            if not current and stale and len({crcs[c] for c in stale}) == 1:
                # Fleet-wide missed reply: the consensus IS the base
                # (same rule as the close-time resync logic).
                current, stale = stale, []
            if not current or len({crcs[c] for c in current}) != 1:
                return
            ids = current
        else:
            ids = ids_all
        # Same weight rule as serve_round's close-time aggregation —
        # n_samples weights whenever the server is weighted, DP or not.
        weights = (
            [st.intents[c]["n_samples"] for c in ids]
            if self.weighted
            else None
        )
        st.freeze(ids, weights)

    def _validate_upload_identity(
        self, meta, *, nonce_hex: str | None, dpid: int | None
    ) -> int:
        """Freshness + identity binding every upload shape shares. The
        single-frame and streamed wire paths MUST apply identical
        security checks, so both call this one helper — a check added to
        only one path would open a validation gap between the two
        shapes. Returns the bound client id."""
        if self.auth_key is not None and (
            meta.get("role") != "client" or meta.get("nonce") != nonce_hex
        ):
            raise wire.WireError(
                "authenticated upload failed the freshness check "
                "(stale nonce or wrong role) — possible replay"
            )
        client_id = int(meta.get("client_id", -1))
        if dpid is not None and client_id != dpid:
            raise wire.WireError(
                f"upload claims client {client_id} but the DP id "
                f"hello said {dpid}"
            )
        return client_id

    def _validate_dp_meta(self, meta, *, is_delta: bool) -> tuple[bool, int | None]:
        """Central-DP mode agreement + base-crc parse, shared by both
        upload shapes (see _validate_upload_identity). Returns
        ``(dp_mode, dp_crc)``."""
        dp_mode = self.dp_clip > 0.0
        if bool(meta.get("dp", False)) != dp_mode:
            raise wire.WireError(
                f"central-DP mode mismatch: server dp={dp_mode}, "
                f"upload dp={meta.get('dp', False)} — run the client "
                f"with --dp iff the server has --dp-clip"
            )
        dp_crc = None
        if dp_mode:
            if is_delta:
                raise wire.WireError(
                    "sparse-delta upload in central-DP mode"
                )
            try:
                dp_crc = int(meta["dp_base_crc"])
            except (KeyError, TypeError, ValueError):
                raise wire.WireError(
                    "DP upload missing its dp_base_crc"
                ) from None
        return dp_mode, dp_crc

    def _register_tree_meta(
        self, rnd: _Round, conn: socket.socket, client_id: int, meta
    ) -> bool:
        """Survivable-fold-tree meta handling shared by the dense and
        streamed upload paths (caller holds ``rnd.lock``; the two wire
        shapes MUST treat the tree meta identically, same rationale as
        ``_validate_upload_identity``). Records a relay upload's
        contributor list (the round's assignment record) and adopts a
        re-homed NEW id as an extra contributor — widening a frozen-but-
        unfolded fold set, refusing once folds consumed it. Returns
        False when the adoption was refused: the connection is closed
        and the client retries against its next parent or next round."""
        sub = meta.get(wire.SUBTREE_IDS_META_KEY)
        if sub is not None:
            try:
                rnd.subtree_ids[client_id] = [int(c) for c in sub]
            except (TypeError, ValueError):
                raise wire.WireError(
                    f"malformed {wire.SUBTREE_IDS_META_KEY} meta {sub!r} "
                    "(want a list of client ids)"
                ) from None
        # Strategy agreement (strategies/): a relay stamps the strategy
        # id it believes the fleet runs on its upward upload. A mismatch
        # means a split-brain fleet — two aggregation rules folding into
        # one global — so the ROOT refuses the upload loudly instead of
        # silently folding it. Absent stamp = old peer, accepted as-is.
        claimed = meta.get(wire.STRATEGY_META_KEY)
        if claimed is not None:
            name = (
                claimed.get("name") if isinstance(claimed, dict) else claimed
            )
            if str(name) != self._strategy.name:
                raise wire.WireError(
                    f"relay {client_id} fans down strategy {name!r} but "
                    f"this root runs {self._strategy.name!r}; refusing "
                    "the split-brain round (restart the relay with the "
                    "root's --strategy)"
                )
        if not bool(meta.get(wire.REHOME_META_KEY, False)):
            return True
        if self.secure_agg or self.dp_clip > 0.0:
            # Single-aggregator modes never sit behind a fold tree
            # (reply_via refuses them); the marker is ignored and the
            # upload faces those modes' own validation.
            return True
        known = client_id in rnd.models or (
            rnd.stream is not None and client_id in rnd.stream.intents
        )
        if known or client_id in rnd.adopted:
            # An adopted client's retry: the duplicate path's rules
            # apply (supersede pre-fold, keep the folded original).
            return True
        if rnd.stream is not None and not rnd.stream.admit(client_id):
            log.info(
                f"[SERVER] re-homed client {client_id} arrived after "
                "folds began; refusing the adoption (it retries against "
                "its next parent or the next round)"
            )
            conn.close()
            return False
        rnd.adopted.add(client_id)
        log.info(
            f"[SERVER] adopted re-homed client {client_id} into round "
            f"{rnd.round_no} as an extra contributor"
        )
        return True

    def _handle_stream_upload(
        self,
        conn: socket.socket,
        header,
        rnd: _Round,
        *,
        nonce_hex: str | None,
        dpid: int | None,
    ) -> None:
        """Receive one chunk-streamed upload: validate the header's meta
        exactly as a single-frame upload's, register the intent, then
        decode leaves as their bytes complete and hand each to the
        round's StreamAgg — which folds it into the running mean the
        moment every cohort member's copy arrived. The trailer frame is
        the upload-complete handshake; only then does the client count
        toward the round quorum."""
        st = rnd.stream
        if st is None:
            raise wire.WireError(
                "streamed upload refused: this round aggregates masked "
                "uploads (secure-agg), which are single-frame by design"
            )
        tensors, meta, chunk_bytes, payload_nbytes = wire.decode_stream_header(
            header,
            auth_key=self.auth_key,
            max_payload=framing.MAX_FRAME,
            direction="up",
        )
        client_id = self._validate_upload_identity(
            meta, nonce_hex=nonce_hex, dpid=dpid
        )
        if bool(meta.get("delta", False)):
            raise wire.WireError(
                "sparse-delta uploads are single-frame (topk payload "
                "sizes are data-dependent; nothing to stream)"
            )
        if bool(meta.get("secure", False)):
            raise wire.WireError(
                "secure-aggregation mode mismatch: server "
                "secure_agg=False, upload secure=True"
            )
        dp_mode, dp_crc = self._validate_dp_meta(meta, is_delta=False)
        n_samples = float(meta.get("n_samples", 1.0))
        # The upload's wire dtype, from what the header actually encodes
        # (ground truth over any meta claim): the by-dtype /metrics
        # label and the wire-overlap span's wire_dtypes attr.
        encs = {t["enc"] for t in tensors}
        up_dtype = (
            "int8" if "int8c" in encs else "bf16" if "bf16" in encs else "fp32"
        )
        # Duplicate stream after folds consumed the first upload: a
        # COMPLETED original stands and this stream is DRAINED (protocol
        # kept intact, bytes discarded) so the retrying client still gets
        # the round's reply on its fresh connection. A half-folded
        # IN-FLIGHT original (socket died before its trailer) is instead
        # ADOPTED: the retry re-sends the same upload, so its leaves
        # complete the remaining folds — the streamed twin of the
        # dense-retry heal below; a diverging plan is drained (the fold
        # cannot reach a correct mean from it; the round fails at close,
        # never the server).
        discard = False
        adopt = False
        with rnd.lock:
            if rnd.closed:
                conn.close()
                return
            if not self._register_tree_meta(rnd, conn, client_id, meta):
                return
            if client_id in rnd.models or client_id in st.intents:
                folded = not st.drop_client(client_id, poison=False)
                if folded and client_id not in rnd.models:
                    it = st.intents[client_id]
                    adopt = (
                        tuple(t["key"] for t in tensors) == tuple(it["keys"])
                    )
                    if adopt:
                        # The frozen fold weights came from the original
                        # intent; complete the round's bookkeeping with
                        # the SAME values, not the retry's meta.
                        n_samples = float(it["n_samples"])
                        dp_crc = it["dp_crc"]
                discard = folded and not adopt
                log.info(
                    f"[SERVER] duplicate upload from client {client_id}; "
                    + (
                        "draining it and keeping the already-aggregated "
                        "original"
                        if discard
                        else (
                            "adopting it to complete the half-folded "
                            "original"
                            if adopt
                            else "replacing"
                        )
                    )
                )
                old = rnd.conns.pop(client_id, None)
                if old is not None and old is not conn:
                    old.close()
                if not (discard or adopt):
                    rnd.models.pop(client_id, None)
            if not (discard or adopt):
                st.register(
                    client_id,
                    keys=tuple(t["key"] for t in tensors),
                    n_samples=n_samples,
                    delta=False,
                    dp_crc=dp_crc,
                )
            # Register the connection now: a failed round's cleanup must
            # close a mid-stream client too, not leave it blocked.
            rnd.conns[client_id] = conn
            self._try_freeze_stream(rnd)
        self._g_inflight_streams.inc()
        in_flight = True
        nonce = bytes.fromhex(nonce_hex) if nonce_hex else b""
        # Lossy-encoded DP uploads (bf16/int8): the decode can inflate an
        # honestly-clipped norm past the tolerance, and the dense path's
        # answer — silently re-clip — needs the WHOLE upload before any
        # leaf folds (a post-fold re-clip fails the round closed). Hold
        # those leaves and join the fold at trailer time, after the same
        # clip_flat the dense path applies; raw streams fold eagerly
        # (lossless decode — the client-side clip stands).
        dp_hold: dict[str, np.ndarray] | None = (
            {}
            if dp_mode and any(t["enc"] != "raw" for t in tensors)
            else None
        )
        ti = 0
        leaf_buf = bytearray()
        received = 0
        seq = 0
        sumsq = 0.0  # running clip-enforcement norm (header key order =
        # sorted keys = flat_l2_norm's accumulation order, bit-identical)

        def _consume(data) -> None:
            nonlocal ti, leaf_buf, sumsq
            off = 0
            while True:
                while ti < len(tensors) and len(leaf_buf) == int(
                    tensors[ti]["nbytes"]
                ):
                    t = tensors[ti]
                    if not discard:
                        arr = wire.decode_tensor_entry(t, bytes(leaf_buf))
                        if dp_hold is not None:
                            dp_hold[t["key"]] = arr
                        else:
                            if dp_mode:
                                sumsq += float(
                                    np.sum(np.asarray(arr, np.float64) ** 2)
                                )
                            st.add_leaf(client_id, t["key"], arr)
                    leaf_buf = bytearray()
                    ti += 1
                if off >= len(data):
                    return
                if ti >= len(tensors):
                    raise wire.WireError(
                        "stream carries bytes past its last tensor"
                    )
                take = min(
                    int(tensors[ti]["nbytes"]) - len(leaf_buf),
                    len(data) - off,
                )
                leaf_buf += data[off : off + take]
                off += take

        try:
            _consume(b"")  # zero-size leading leaves / empty payloads
            while received < payload_nbytes:
                frame = framing.recv_frame(conn, send_ack=False)
                self._m_bytes_in.inc(float(len(frame)))
                data = wire.decode_stream_chunk(
                    frame,
                    expect_seq=seq,
                    auth_key=self.auth_key,
                    nonce=nonce,
                    direction="up",
                )
                if not data:
                    # A well-formed sender never chunks to zero bytes
                    # (payload_nbytes == 0 skips this loop entirely);
                    # accepting them would let a peer pin this handler
                    # in a no-progress receive loop forever.
                    raise wire.WireError(f"empty stream chunk (seq {seq})")
                seq += 1
                if received + len(data) > payload_nbytes:
                    raise wire.WireError(
                        "stream overruns its declared payload size"
                    )
                received += len(data)
                _consume(data)
            if ti != len(tensors) or leaf_buf:
                raise wire.WireError("stream ended mid-tensor")
            wire.decode_stream_end(
                framing.recv_frame(conn),
                expect_chunks=seq,
                auth_key=self.auth_key,
                nonce=nonce,
                direction="up",
            )
            self._g_inflight_streams.dec()
            in_flight = False
            if not discard and dp_hold is not None:
                # The dense path's exact enforcement (same functions,
                # same accumulation order): re-clip the decoded upload,
                # then join the fold in one piece — add_dense marks the
                # client complete.
                norm = wire.flat_l2_norm(dp_hold)
                if norm > self.dp_clip * (1.0 + 1e-5):
                    dp_hold, _, _ = wire.clip_flat(dp_hold, self.dp_clip)
                    log.info(
                        f"[SERVER] re-clipped client {client_id}'s "
                        f"streamed lossy-encoded delta "
                        f"({norm:.4g} -> {self.dp_clip})"
                    )
                st.add_dense(client_id, dp_hold)
            elif not discard:
                st.mark_complete(client_id)
            if dp_mode and not discard and dp_hold is None:
                # ENFORCED clipping, streamed flavor: the full-upload norm
                # is only known now. While none of this client's leaves
                # have folded, the re-clip is applied bit-identically to
                # the barrier path (wire.clip_flat); once folds consumed
                # unscaled leaves the round fails closed instead — a
                # cheater cannot widen the mechanism's sensitivity either
                # way, and honest clients (which clip client-side) never
                # trigger this.
                norm = float(np.sqrt(sumsq))
                if norm > self.dp_clip * (1.0 + 1e-5):
                    scale = min(1.0, self.dp_clip / max(norm, 1e-12))
                    if not st.scale_client(client_id, scale):
                        raise wire.WireError(
                            f"client {client_id} exceeded its DP clip "
                            f"({norm:.4g} > {self.dp_clip}) after folds "
                            "already consumed its leaves — round fails "
                            "closed"
                        )
                    log.info(
                        f"[SERVER] re-clipped client {client_id}'s "
                        f"streamed delta ({norm:.4g} -> {self.dp_clip})"
                    )
        except BaseException:
            # Mid-stream death: forget the client's unfolded leaves; if
            # folds already consumed any, the StreamAgg is poisoned and
            # the round fails with that reason at close. Skip the drop
            # when a retry already took over this client's slot (the
            # round's registered connection is no longer ours) — the
            # client's state now belongs to that retry, and dropping it
            # here would poison a round the retry just saved.
            if in_flight:
                self._g_inflight_streams.dec()
            if not discard:
                with rnd.lock:
                    if rnd.conns.get(client_id) is conn:
                        st.drop_client(client_id)
                        # A dead ADOPTED stream must also stop gating
                        # round completion (it widened the wait set).
                        rnd.adopted.discard(client_id)
            raise
        with rnd.lock:
            if rnd.closed:
                log.info(
                    f"[SERVER] late upload from client {client_id} after "
                    "round close; dropping connection"
                )
                conn.close()
                return
            if rnd.conns.get(client_id) is not conn:
                # A retry superseded this stream mid-read (duplicate
                # handling adopted a newer connection and owns the
                # client's round state now); finishing here would stamp
                # stale completion info over the retry's.
                log.info(
                    f"[SERVER] stream from client {client_id} superseded "
                    "by a retry; dropping connection"
                )
                conn.close()
                return
            if not discard:
                # Sentinel entry: the StreamAgg holds (or already folded)
                # the actual tensors; rnd.models only tracks WHO completed.
                rnd.models[client_id] = {}
                rnd.deltas[client_id] = False
                rnd.wire_dtypes[client_id] = up_dtype
                if dp_crc is not None:
                    rnd.dp_crcs[client_id] = dp_crc
                rnd.n_samples[client_id] = n_samples
            if bool(meta.get("wants_delta", False)):
                rnd.wants_delta = True
            if bool(meta.get(wire.STREAM_REPLY_META_KEY, False)):
                rnd.stream_replies.add(client_id)
                encs = meta.get(wire.REPLY_DTYPE_META_KEY)
                if isinstance(encs, (list, tuple)):
                    rnd.reply_dtype_encs[client_id] = tuple(
                        str(e) for e in encs
                    )
            rnd.conns[client_id] = conn
            if nonce_hex is not None:
                rnd.nonces[client_id] = nonce_hex
            if not discard:
                # Drained duplicates contributed nothing — the counters
                # (and /metrics' "accepted into a round" totals) only
                # count uploads that did.
                with self._totals_lock:
                    self.stream_totals["stream_uploads"] += 1
            done = self._round_done(rnd)
        if discard:
            log.info(
                f"[SERVER] drained duplicate stream from client "
                f"{client_id} ({payload_nbytes / 1e6:.1f} MB discarded)"
            )
        else:
            self._m_uploads.inc()
            self._m_stream_uploads.inc()
            self._m_uploads_by_dtype(up_dtype).inc()
            log.info(
                f"[SERVER] received streamed model from client {client_id} "
                f"({payload_nbytes / 1e6:.1f} MB in {seq} chunk(s); "
                f"{len(rnd.models)}/{rnd.expected})"
            )
        if done:
            rnd.complete.set()

    def _client_wire_key(self, cid: int) -> bytes | None:
        """The key server<->client control frames (reveal/unmask/shares)
        ride for ``cid``: its per-client identity key when provisioned,
        the group key otherwise (comm/secure.py threat model)."""
        if self.client_keys is not None:
            return self.client_keys[cid]
        return self.auth_key

    def _shares_exchange(
        self,
        conn: socket.socket,
        rnd: _Round,
        hello_id: int,
        key_set: list,
        deadline: float,
    ) -> bool:
        """Double-masking share distribution for one connection: collect
        this dealer's encrypted share blobs, wait (grace-bounded) for the
        keyed fleet's, close U2, relay this holder's shareset. Returns
        False when the connection was dropped (late/conflicting dealer or
        a holder outside U2)."""
        frame = framing.recv_frame(conn)
        dealer, dealt_t, commit, blobs = secure.parse_shares_frame(
            frame,
            session=self._session,
            round_index=rnd.round_no,
            auth_key=(
                self._client_wire_key(hello_id)
                if self.auth_key is not None
                else None
            ),
        )
        if dealer != hello_id:
            raise wire.WireError(
                f"shares frame claims dealer {dealer} on client "
                f"{hello_id}'s connection"
            )
        # Both ends derive t from U1 (key_set) — majority by default, or
        # the operator's explicit threshold set identically on both. A
        # mismatched degree could never reconstruct, so fail it now.
        want_t = (
            self.secure_threshold
            if self.secure_threshold is not None
            else secure.majority_threshold(len(key_set))
        )
        if dealt_t != want_t:
            raise wire.WireError(
                f"client {hello_id} dealt shares at threshold {dealt_t}, "
                f"server expects {want_t} (set secure_threshold "
                "identically on both ends)"
            )
        # U2 must stay >= t: fewer dealers than the Shamir threshold could
        # never unmask, so closing such a round would doom it AFTER all
        # the masking/upload work — refuse at the quorum close instead.
        share_floor = max(2, self.min_clients, want_t)
        want = set(key_set) - {hello_id}
        if set(blobs) != want:
            raise wire.WireError(
                f"shares frame covers holders {sorted(blobs)}, expected "
                f"every other keyed participant {sorted(want)}"
            )
        with rnd.lock:
            if rnd.closed:
                conn.close()
                return False
            prev = rnd.share_blobs.get(hello_id)
            if prev is not None and (
                prev != blobs or rnd.share_commits.get(hello_id) != commit
            ):
                # Like a conflicting DH hello: first deal wins — different
                # shares for the same dealer could never reconstruct.
                log.info(
                    f"[SERVER] conflicting shares from client {hello_id}; "
                    "dropping connection"
                )
                conn.close()
                return False
            if prev is None and rnd.shares_ready.is_set():
                log.info(
                    f"[SERVER] late shares from client {hello_id} after "
                    "shareset distribution; dropping connection"
                )
                conn.close()
                return False
            rnd.share_blobs[hello_id] = blobs
            rnd.share_commits[hello_id] = commit
            if set(key_set).issubset(rnd.share_blobs):
                rnd.share_set = sorted(rnd.share_blobs)
                rnd.shares_ready.set()
        # Wait for the fleet's shares — after the grace window, close U2
        # at the quorum that dealt (dropout-after-keys-before-shares
        # recovery: nobody masked against the missing yet, so the round
        # simply proceeds over the dealers).
        grace_end = time.monotonic() + self.key_grace
        while not rnd.shares_ready.is_set():
            now = time.monotonic()
            if now >= deadline:
                raise wire.WireError(
                    "round deadline passed waiting for the remaining "
                    "participants' secret shares"
                )
            wait_until = grace_end if now < grace_end else deadline
            if rnd.shares_ready.wait(timeout=max(0.0, wait_until - now)):
                break
            with rnd.lock:
                if (
                    not rnd.shares_ready.is_set()
                    and time.monotonic() >= grace_end
                    and len(rnd.share_blobs) >= share_floor
                ):
                    rnd.share_set = sorted(rnd.share_blobs)
                    rnd.shares_ready.set()
                    log.info(
                        f"[SERVER] share grace expired; closing U2 at "
                        f"quorum {rnd.share_set}"
                    )
                    break
        with rnd.lock:
            u2 = list(rnd.share_set or [])
            entries = {
                d: rnd.share_blobs[d][hello_id] for d in u2 if d != hello_id
            }
        if hello_id not in u2:
            log.info(
                f"[SERVER] client {hello_id} missed the share set {u2}; "
                "dropping connection"
            )
            conn.close()
            return False
        framing.send_frame(
            conn,
            secure.build_shareset_frame(
                u2,
                entries,
                session=self._session,
                round_index=rnd.round_no,
                auth_key=(
                    self._client_wire_key(hello_id)
                    if self.auth_key is not None
                    else None
                ),
            ),
        )
        return True

    def _aggregate_double(
        self,
        rnd: _Round,
        models: dict[int, dict],
        conns: dict[int, socket.socket],
    ) -> dict:
        """Double-masking round completion: one unmask round (EVERY round
        — self-masks never cancel on their own), Shamir reconstruction of
        contributors' self-mask seeds and dead participants' key seeds,
        then residue subtraction and de-quantization over contributors.

        Tolerates further dropouts during unmasking: any ``t`` responders
        suffice (t = secure_threshold, default majority of U2)."""
        from . import shamir

        with rnd.lock:
            u2 = list(rnd.share_set or [])
            u1 = list(rnd.key_set or [])
            commits = dict(rnd.share_commits)
            pubs = {
                cid: rnd.pubkeys[cid][: secure.DH_PUB_LEN]
                for cid in rnd.pubkeys
            }
        alive = sorted(models)
        extra = [i for i in alive if i not in u2]
        if extra:
            raise RuntimeError(
                f"secure uploads from clients {extra} outside the share "
                f"set {u2}"
            )
        dead = [i for i in u2 if i not in alive]
        # t derives from U1 — the set the shares were DEALT over (their
        # polynomial degree is fixed there); U2 only selects who masked.
        t = (
            self.secure_threshold
            if self.secure_threshold is not None
            else secure.majority_threshold(len(u1))
        )
        if len(alive) < t:
            # Unmask needs t responders and only contributors hold open
            # connections — fail with the real cause before burning an
            # unmask round that cannot succeed.
            raise RuntimeError(
                f"only {len(alive)} secure uploads survived, below the "
                f"Shamir threshold {t} — the self-masks cannot be "
                "reconstructed (dropouts exceeded the double-masking "
                "tolerance)"
            )
        budget = min(self.timeout, 30.0)
        responses: dict[int, tuple] = {}
        errs: dict[int, Exception] = {}

        def _ask(cid: int) -> None:
            conn = conns[cid]
            try:
                conn.settimeout(budget)
                framing.send_frame(
                    conn,
                    secure.build_unmask_request(
                        alive,
                        dead,
                        session=self._session,
                        round_index=rnd.round_no,
                        auth_key=(
                            self._client_wire_key(cid)
                            if self.auth_key is not None
                            else None
                        ),
                    ),
                )
                responses[cid] = secure.parse_unmask_response(
                    framing.recv_frame(conn),
                    session=self._session,
                    round_index=rnd.round_no,
                    client_id=cid,
                    expect_alive=alive,
                    expect_dead=dead,
                    auth_key=(
                        self._client_wire_key(cid)
                        if self.auth_key is not None
                        else None
                    ),
                )
                conn.settimeout(self.timeout)
            except (
                OSError,
                ConnectionError,
                wire.WireError,
                secure.SecureAggError,
            ) as e:
                errs[cid] = e

        threads = [
            threading.Thread(target=_ask, args=(cid,), daemon=True)
            for cid in alive
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=budget + 5.0)
        if len(responses) < t:
            raise RuntimeError(
                f"unmask round got {len(responses)} responses "
                f"(clients {sorted(responses)}), need the Shamir "
                f"threshold {t}; failures: "
                f"{ {c: str(e) for c, e in errs.items()} }"
            )
        # Reconstruct contributors' self-mask seeds, verified against the
        # dealt commitments (corrupted shares fail loudly, not silently).
        b_seeds: dict[int, bytes] = {}
        for d in alive:
            shares = {
                secure.share_x(h): responses[h][0][d] for h in responses
            }
            seed = shamir.combine(shares)
            if (
                secure.b_seed_commitment(
                    seed, self._session, rnd.round_no, d
                )
                != commits[d]
            ):
                raise RuntimeError(
                    f"reconstructed self-mask seed for client {d} fails "
                    "its commitment — inconsistent shares"
                )
            b_seeds[d] = seed
        # Reconstruct dead participants' key seeds, verified against their
        # registered DH public keys; regenerate the uncancelled pair masks.
        revealed: dict[int, dict[int, bytes]] = {}
        for d in dead:
            shares = {
                secure.share_x(h): responses[h][1][d] for h in responses
            }
            sk_seed = shamir.combine(shares)
            priv, pub = secure.dh_keypair(entropy=sk_seed)
            if pub != pubs.get(d):
                raise RuntimeError(
                    f"reconstructed key seed for dead client {d} does not "
                    "match its registered public key — inconsistent shares"
                )
            for s in alive:
                revealed.setdefault(s, {})[d] = secure.dh_pair_secret(
                    priv, pubs[s]
                )
        summed = secure.sum_masked([models[i] for i in alive])
        self_res = secure.self_mask_sum(
            summed, b_seeds, session=self._session, round_index=rnd.round_no
        )
        out = {k: summed[k] - self_res[k] for k in summed}
        if revealed:
            pair_res = secure.residual_mask_sum(
                summed,
                revealed,
                session=self._session,
                round_index=rnd.round_no,
            )
            out = {k: out[k] - pair_res[k] for k in out}
        log.info(
            f"[SERVER] double-mask unmasked {len(alive)} uploads with "
            f"{len(responses)}/{len(alive)} responders (threshold {t})"
            + (f", {len(dead)} dropout(s) recovered" if dead else "")
        )
        return secure.dequantize_sum(out, len(alive), self.fp_bits)

    def _load_dp_history(self) -> None:
        """Reload the persisted resync window (``dp_history_path``). A
        missing file is a fresh deployment; a corrupt one is logged and
        ignored (the server must come up — clients staler than the
        recoverable window fail their rounds exactly as before)."""
        import json as _json
        import zipfile as _zipfile

        try:
            with np.load(self.dp_history_path, allow_pickle=False) as z:
                index = _json.loads(bytes(z["__index__"].tobytes()).decode())
                self._dp_history = [
                    (
                        int(entry["crc"]),
                        {
                            k: np.asarray(z[f"e{i}_{j}"], np.float32)
                            for j, k in enumerate(entry["keys"])
                        },
                    )
                    for i, entry in enumerate(index)
                ]
            log.info(
                f"[SERVER] reloaded {len(self._dp_history)} retained DP "
                f"round delta(s) from {self.dp_history_path}"
            )
        except FileNotFoundError:
            pass
        except (
            OSError,
            ValueError,
            KeyError,
            # A truncated write that kept the zip magic: np.load raises
            # BadZipFile, which is neither OSError nor ValueError.
            _zipfile.BadZipFile,
        ) as e:
            log.warning(
                f"[SERVER] could not reload DP resync history from "
                f"{self.dp_history_path} ({e}); starting with an empty "
                "window"
            )
            self._dp_history = []

    def _persist_dp_history(self) -> None:
        """Queue the current window for the background writer (see the
        constructor comment): serve_round never blocks on history I/O.
        Coalescing is by design — only the NEWEST snapshot matters, so
        a slow disk skips intermediate windows instead of queueing
        them."""
        if not self.dp_history_path:
            return
        snap = list(self._dp_history)
        with self._dp_persist_lock:
            self._dp_persist_pending = snap
            if (
                self._dp_persist_thread is None
                or not self._dp_persist_thread.is_alive()
            ):
                self._dp_persist_thread = threading.Thread(
                    target=self._dp_persist_loop, daemon=True
                )
                self._dp_persist_thread.start()

    def _dp_persist_loop(self) -> None:
        while True:
            with self._dp_persist_lock:
                snap = self._dp_persist_pending
                self._dp_persist_pending = None
                if snap is None:
                    self._dp_persist_thread = None
                    return
            self._write_dp_history(snap)

    def _write_dp_history(self, history: list[tuple[int, dict]]) -> None:
        """Write one window snapshot atomically (tmp + replace).
        Layout: a JSON index array (per entry: base crc + leaf key
        order) plus positionally-named fp32 arrays — leaf keys can
        contain any character without fighting npz member naming."""
        import json as _json

        index = [
            {"crc": int(crc), "keys": list(d)} for crc, d in history
        ]
        arrays: dict[str, np.ndarray] = {
            "__index__": np.frombuffer(
                _json.dumps(index).encode(), dtype=np.uint8
            )
        }
        for i, (_, d) in enumerate(history):
            for j, k in enumerate(d):
                arrays[f"e{i}_{j}"] = np.asarray(d[k], np.float32)
        tmp = self.dp_history_path + ".tmp"
        try:
            # makedirs INSIDE the guard: an unwritable parent is the
            # same best-effort failure as a full disk — persistence
            # must never fail a round that already released its delta.
            os.makedirs(
                os.path.dirname(os.path.abspath(tmp)) or ".",
                exist_ok=True,
            )
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, self.dp_history_path)
        except OSError as e:
            log.warning(
                f"[SERVER] could not persist DP resync history to "
                f"{self.dp_history_path}: {e}"
            )

    # ------------------------------------------- strategy-state persistence
    def _load_strategy_state(self) -> None:
        """Reload the persisted server state (``strategy_state_path``):
        the last post-strategy global + round index, and the strategy's
        optimizer-state leaves. Missing file = fresh deployment; corrupt
        file or a strategy mismatch = logged and ignored (the server
        must come up; a fresh optimizer memory is merely the pre-PR
        behavior, never wrong)."""
        import json as _json
        import zipfile as _zipfile

        try:
            with np.load(self.strategy_state_path, allow_pickle=False) as z:
                index = _json.loads(bytes(z["__index__"].tobytes()).decode())
                agg = {
                    k: np.asarray(z[f"a{j}"], np.float32)
                    for j, k in enumerate(index["keys"])
                }
                opt_leaves = [
                    np.asarray(z[f"o{j}"])
                    for j in range(int(index.get("n_opt", 0)))
                ]
        except FileNotFoundError:
            return
        except (OSError, ValueError, KeyError, _zipfile.BadZipFile) as e:
            log.warning(
                f"[SERVER] could not reload server strategy state from "
                f"{self.strategy_state_path} ({e}); starting fresh"
            )
            return
        if index.get("strategy") != self._strategy.describe():
            log.warning(
                f"[SERVER] persisted strategy state is for "
                f"{index.get('strategy')}, this server runs "
                f"{self._strategy.describe()}; starting fresh"
            )
            return
        self._last_agg = agg
        self._last_agg_round = int(index["round"])
        # Continue the round numbering monotonically: the restored base
        # is keyed by its round index on both ends of the wire (delta
        # uploads declare base_round; replies advertise agg_round).
        self._round_counter = self._last_agg_round + 1
        restored_opt = False
        if opt_leaves:
            restored_opt = self._strategy.restore_state(opt_leaves, agg)
            if not restored_opt:
                log.warning(
                    "[SERVER] persisted optimizer-state leaves do not "
                    "match this strategy/model; optimizer memory starts "
                    "fresh"
                )
        log.info(
            f"[SERVER] reloaded round {self._last_agg_round} global"
            + (" + optimizer state" if restored_opt else "")
            + f" from {self.strategy_state_path} "
            f"(strategy {self._strategy.name})"
        )

    def _persist_strategy_state(self) -> None:
        """Queue the current global + optimizer state for the background
        writer (the dp-history pattern: latest-snapshot slot, coalescing
        writes — serve_round never blocks on model-sized disk I/O)."""
        if not self.strategy_state_path or self._last_agg is None:
            return
        opt = self._strategy.export_state()
        snap = (
            int(self._last_agg_round),
            {
                k: np.asarray(v, np.float32)
                for k, v in self._last_agg.items()
            },
            self._strategy.describe(),
            [np.asarray(a) for a in (opt or [])],
        )
        with self._strategy_persist_lock:
            self._strategy_persist_pending = snap
            if (
                self._strategy_persist_thread is None
                or not self._strategy_persist_thread.is_alive()
            ):
                self._strategy_persist_thread = threading.Thread(
                    target=self._strategy_persist_loop, daemon=True
                )
                self._strategy_persist_thread.start()

    def _strategy_persist_loop(self) -> None:
        while True:
            with self._strategy_persist_lock:
                snap = self._strategy_persist_pending
                self._strategy_persist_pending = None
                if snap is None:
                    self._strategy_persist_thread = None
                    return
            self._write_strategy_state(snap)

    def _write_strategy_state(self, snap: tuple) -> None:
        """One atomic snapshot (tmp + replace): a JSON index (round,
        strategy describe, agg key order, opt leaf count) plus
        positionally-named arrays — same layout discipline as the DP
        history file, and the same best-effort failure contract."""
        import json as _json

        round_no, agg, described, opt_leaves = snap
        index = {
            "round": int(round_no),
            "strategy": described,
            "keys": list(agg),
            "n_opt": len(opt_leaves),
        }
        arrays: dict[str, np.ndarray] = {
            "__index__": np.frombuffer(
                _json.dumps(index).encode(), dtype=np.uint8
            )
        }
        for j, k in enumerate(agg):
            arrays[f"a{j}"] = agg[k]
        for j, leaf in enumerate(opt_leaves):
            arrays[f"o{j}"] = leaf
        tmp = self.strategy_state_path + ".tmp"
        try:
            os.makedirs(
                os.path.dirname(os.path.abspath(tmp)) or ".",
                exist_ok=True,
            )
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, self.strategy_state_path)
        except OSError as e:
            log.warning(
                f"[SERVER] could not persist server strategy state to "
                f"{self.strategy_state_path}: {e}"
            )

    def _heal_stale_clients(
        self,
        rnd: _Round,
        stale_resync: dict[int, int],
        conns: dict[int, socket.socket],
        nonces: dict[int, str],
    ) -> None:
        """Serve catch-up sequences of RETAINED deltas to stale clients of
        a round that is about to FAIL (quorum miss after their exclusion):
        no new delta exists, but the retained rounds alone land them on
        the fleet's current base so the retried round can succeed. Send
        failures are logged and ignored — the round is failing anyway."""
        for cid, j in stale_resync.items():
            conn = conns.get(cid)
            entries = [d for _, d in self._dp_history[j:]]
            if conn is None or not entries:
                continue
            if not all(
                wire.shapes_compatible(d, entries[0]) for d in entries
            ):
                continue
            try:
                conn.settimeout(min(self.timeout, 30.0))
                framing.send_frame(
                    conn,
                    self._encode_reply(
                        {
                            str(i): wire.unflatten_params(d)
                            for i, d in enumerate(entries)
                        },
                        {
                            "agg_round": rnd.round_no,
                            "trace": rnd.trace,
                            "dp_reply": "resync",
                            "dp_resync_rounds": len(entries),
                        },
                        nonces.get(cid),
                    ),
                )
                log.info(
                    f"[SERVER] client {cid} healed with a catch-up "
                    f"sequence of {len(entries)} retained round delta(s) "
                    "(round itself failed quorum)"
                )
            except (OSError, ConnectionError, wire.WireError) as e:
                log.info(f"[SERVER] catch-up to client {cid} failed: {e}")

    def _round_quorum(self, cohort: set[int] | None) -> int:
        """Upload quorum for one round.

        A sampled round can't demand more uploads than the cohort it drew
        (the draw is data-independent; gating on it would only hurt
        liveness, not privacy) — but the cohort clamp must never lower
        the secure-agg floor below 2: a 1-member cohort's "sum" IS that
        client's raw update, so aggregating it defeats the masking
        outright. Clients enforce their own min_participants floor; the
        server must not construct the degenerate round either:
        ``quorum = max(2, min(min_clients, |cohort|))`` under secure
        aggregation (the constructor already pins min_clients >= 2
        there, so only the cohort clamp can drive the value below 2)."""
        quorum = self.min_clients
        if cohort is not None:
            quorum = min(quorum, len(cohort))
        if self.secure_agg:
            quorum = max(2, quorum)
        return quorum

    def serve_round(
        self, *, deadline: float | None = None, round_index: int | None = None
    ) -> dict | None:
        """Accept uploads until all clients arrive (or deadline), aggregate,
        reply to every contributor. Returns the aggregated flat params.

        ``round_index`` overrides the internal monotonic round counter
        (secure clients key their mask streams off the advertised value)."""
        if self.reply_via is not None and (
            self.dp_clip > 0.0 or self.secure_agg
        ):
            raise ValueError(
                "reply_via (the relay tier's parent-forward hook) is "
                "incompatible with central DP (the subtree partial would "
                "be an un-noised release) and secure aggregation (the "
                "unmask protocol needs the single-aggregator shape)"
            )
        rnd = _Round(
            expected=self.num_clients,
            round_no=self._round_counter if round_index is None else round_index,
        )
        self._round_counter = rnd.round_no + 1
        # close() mid-round sheds THIS round's registered connections
        # promptly (explicit failures, not timeouts).
        self._cur_rnd = rnd
        # Round trace identity (obs/): minted here, stamped into every
        # reply's meta — clients adopt it for their own spans, so the
        # obs timeline can correlate both sides of the wire. Old clients
        # simply ignore the extra meta key (free-form JSON).
        rnd.trace = new_trace_id()
        self.last_trace = (rnd.trace, rnd.round_no)
        self._m_rounds.inc()
        t_round_unix = time.time()
        t_round0 = time.monotonic()
        wait_s = 0.0
        if self.dp_clip > 0.0 and self.dp_participation < 1.0:
            # Per-round Poisson cohort from OS entropy: each registered
            # client independently with probability q — exactly the
            # sampler the subsampled-Gaussian accountant assumes. An
            # empty draw is a legitimate sample: the round becomes a
            # clean no-op (no release, no privacy spent beyond the
            # accountant's bound, which already covers this branch).
            rnd.cohort = {
                i
                for i in range(self.num_clients)
                if self._dp_rng.random() < self.dp_participation
            }
            rnd.expected = len(rnd.cohort)
            log.info(
                f"[SERVER] round {rnd.round_no} Poisson cohort "
                f"(q={self.dp_participation}): {sorted(rnd.cohort)}"
            )
        if not self.secure_agg:
            # Incremental fold state for every plain/DP upload, streamed
            # or single-frame. eager=False (streaming disabled) holds all
            # uploads and folds only at close — the exact barrier shape.
            # Quorum deployments (min_clients < num_clients) also fold at
            # close: an eager fold commits to the full contributor set,
            # so one mid-stream death after folds began would fail a
            # round that the barrier shape completes over the survivors
            # — eager folding must not silently change those failure
            # semantics. Full-participation rounds (the default) lose
            # nothing: a death fails them under either shape.
            rnd.stream = StreamAgg(
                eager=self.stream_chunk_bytes > 0
                and self.min_clients >= self.num_clients,
                base=self._last_agg,
            )
        deadline = time.monotonic() + (self.timeout if deadline is None else deadline)
        futures: list = []
        listener_closed = False
        # Sitting-out liveness bound: once every cohort upload has landed,
        # missing non-sampled clients get a short grace to connect for
        # their reply, not the whole round deadline (one crashed skip
        # client must not stall every sampled round).
        uploads_done_at = None
        skip_grace = min(self.key_grace, 10.0)
        while not rnd.complete.is_set() and time.monotonic() < deadline:
            if rnd.cohort is not None:
                with rnd.lock:
                    up_done = len(rnd.models) >= rnd.expected
                    all_done = self._round_done(rnd)
                if up_done and not all_done:
                    if uploads_done_at is None:
                        uploads_done_at = time.monotonic()
                    elif time.monotonic() - uploads_done_at > skip_grace:
                        log.info(
                            "[SERVER] cohort uploads complete; proceeding "
                            "without the missing sitting-out client(s) "
                            f"after {skip_grace:.0f}s grace"
                        )
                        # Set the event too: the post-loop complete.wait
                        # must not re-stall for the full round deadline.
                        rnd.complete.set()
                        break
                else:
                    uploads_done_at = None
            try:
                # settimeout inside the guard: close() mid-round (a test
                # or operator shutdown) invalidates the fd and must end
                # the loop, not crash the round thread.
                self._sock.settimeout(
                    max(0.05, min(1.0, deadline - time.monotonic()))
                )
                conn, addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                # Only a real close() (the _stop event) takes the prompt
                # shutdown path below; any other accept() OSError (e.g.
                # EMFILE) keeps the original deadline-bounded wait so an
                # in-flight final upload can still complete the round.
                listener_closed = self._stop.is_set()
                break
            try:
                # Bounded pool, not thread-per-dial: a 256-client cohort
                # (or a retry storm) queues beyond 2*num_clients + 8
                # concurrent handlers instead of spawning without limit.
                futures.append(
                    self._pool.submit(self._handle_upload, conn, rnd, deadline)
                )
            except RuntimeError:
                # close() shut the pool between accept and submit.
                conn.close()
                listener_closed = True
                break
        import concurrent.futures as _cf

        if listener_closed:
            # No new connection can ever arrive: waiting out the full round
            # deadline would just stall shutdown (and leak the round thread
            # past the caller's join window). In-flight handlers may still
            # legitimately complete the round — give them a short bound.
            _cf.wait(futures, timeout=1.0)
        else:
            rnd.complete.wait(timeout=max(0.0, deadline - time.monotonic()))
            _cf.wait(futures, timeout=max(0.1, deadline - time.monotonic()))

        # Everything up to here — accept loop, straggler wait, upload
        # reads — is the round's "wait" phase; aggregation compute and
        # the reply fan-out are timed separately below. Leaf folds that
        # already ran (handler threads, overlapped with the wire) were
        # hidden inside it — that overlap is what the wire-overlap span
        # and comm_overlap_frac report.
        wait_s = time.monotonic() - t_round0
        if rnd.stream is not None:
            rnd.stream.mark_wait_end()

        with rnd.lock:
            rnd.closed = True
            models = dict(rnd.models)
            deltas = dict(rnd.deltas)
            conns = dict(rnd.conns)
            skip_conns = dict(rnd.skip_conns)
            n_samples = dict(rnd.n_samples)
            nonces = dict(rnd.nonces)
            dp_crcs = dict(rnd.dp_crcs)
            adopted = set(rnd.adopted)
            subtree_ids = {k: list(v) for k, v in rnd.subtree_ids.items()}
        # Failure cleanup must cover every registered connection,
        # contributors and sitting-out clients alike.
        all_conns = {**skip_conns, **conns}
        t_agg_unix = time.time()
        t_agg0 = time.monotonic()
        try:
            if rnd.cohort is not None and len(rnd.cohort) == 0:
                # Empty Poisson cohort: a clean no-op round. No model is
                # aggregated and nothing is released; connected clients
                # get a "noop" reply telling them to keep their base.
                log.info(
                    f"[SERVER] round {rnd.round_no}: empty Poisson "
                    "cohort — no-op round, replying noop to "
                    f"{len(skip_conns)} client(s)"
                )
                noop_meta = {
                    "round_clients": [],
                    "agg_round": rnd.round_no,
                    "dp_reply": "noop",
                    "trace": rnd.trace,
                }
                if self.stream_chunk_bytes > 0 and not self.secure_agg:
                    noop_meta[wire.STREAM_META_KEY] = self.stream_chunk_bytes
                    noop_meta[wire.WIRE_DTYPE_META_KEY] = sorted(
                        set(wire.WIRE_DTYPE_ENCS.values())
                    )
                self._reply_all(
                    {
                        cid: self._encode_reply(
                            {}, noop_meta, nonces.get(cid)
                        )
                        for cid in skip_conns
                    },
                    skip_conns,
                )
                self._finish_round(
                    rnd, t_round_unix, t_round0, wait_s,
                    time.monotonic() - t_agg0, 0.0,
                )
                return None
            quorum = self._round_quorum(rnd.cohort)
            if len(models) < quorum:
                raise RuntimeError(
                    f"only {len(models)}/{self.num_clients} clients arrived "
                    f"(min_clients={self.min_clients}"
                    + (
                        f", cohort {sorted(rnd.cohort)}"
                        if rnd.cohort is not None
                        else ""
                    )
                    + ")"
                )
            ids = sorted(models)
            # Survivable fold trees: missing expected contributors (the
            # degraded-round accounting below distinguishes dropped
            # SUBTREES — this server parents relays, some uploads carry
            # contributor records — from locally shed leaf stragglers),
            # the round's ACTUAL assignment, and the double-count
            # tripwire: one client id claimed by two subtree partials
            # means a re-homed upload was also folded by a surviving old
            # parent — no renormalization can fix that mean, so the
            # round fails loudly and the fleet retries.
            missing_n = max(
                0, rnd.expected - (len(models) - len(adopted & set(models)))
            )
            listed = [c for i in ids for c in subtree_ids.get(i, [])]
            if len(listed) != len(set(listed)):
                dup_claims = sorted(
                    {c for c in listed if listed.count(c) > 1}
                )
                raise RuntimeError(
                    f"clients {dup_claims} appear in more than one "
                    "subtree's contributor record — a re-homed upload "
                    "was double-counted; failing the round"
                )
            dp_mode = self.dp_clip > 0.0
            stale_resync: dict[int, int] = {}  # client id -> history index
            resync_payloads: dict[int, tuple[dict, int]] = {}
            if dp_mode:
                if not self.secure_agg and self.compression == "none":
                    # Resyncable stale clients: base crc matches a retained
                    # round (latest entry wins on the impossible collision).
                    # Lossless replies only: under bf16/int8 the bases the
                    # fleet adopted are the DECODED (lossy) deltas, which
                    # the fp32 retention cannot reproduce bit-exactly — a
                    # "resynced" base would miss the crc agreement anyway.
                    hist_index = {
                        crc: j for j, (crc, _) in enumerate(self._dp_history)
                    }
                    stale_resync = {
                        i: hist_index[dp_crcs[i]]
                        for i in ids
                        if dp_crcs[i] in hist_index
                    }
                current = [i for i in ids if i not in stale_resync]
                if not current and stale_resync:
                    group_crcs = {dp_crcs[i] for i in stale_resync}
                    if len(group_crcs) == 1:
                        # EVERY upload agrees on a RETAINED base: the
                        # previously released delta(s) past it were never
                        # adopted by anyone (fleet-wide reply loss), so
                        # the consensus IS the fleet base. Proceed
                        # normally from it — exactly what the pre-resync
                        # server did — instead of misclassifying the
                        # whole fleet as stale; the orphaned history
                        # entries are shadowed by this round's re-release
                        # (hist_index keeps the latest entry per crc).
                        log.info(
                            "[SERVER] all uploads share a retained base "
                            "crc (fleet-wide missed reply); treating the "
                            "consensus as current"
                        )
                        current = sorted(stale_resync)
                        stale_resync = {}
                crc_set = {dp_crcs[i] for i in current}
                if not current or len(crc_set) != 1:
                    # A stale client outside the resync window (or a
                    # different init) would shift the mean by an unbounded
                    # base gap.
                    raise RuntimeError(
                        "DP round base mismatch: clients disagree on the "
                        f"round base (crcs per client: "
                        f"{ {i: f'{dp_crcs[i]:#010x}' for i in ids} }) — "
                        "every client must start the round from the same "
                        "adopted aggregate / shared init (stale clients "
                        f"resync only within the last {self.dp_resync_rounds} "
                        "retained round(s) of this server process)"
                    )
                if stale_resync:
                    if len(current) < quorum:
                        # The round cannot proceed — but the stale clients
                        # must STILL be healed now, with the retained
                        # rounds alone (this round produced no delta).
                        # Under the default quorum (min_clients ==
                        # num_clients) this is the ONLY path that ever
                        # engages: excluding the stale upload always drops
                        # the round below quorum, so without healing here
                        # the fleet would wedge forever — the exact
                        # deadlock the resync exists to close. Healed
                        # clients rejoin current next round, which then
                        # meets quorum.
                        self._heal_stale_clients(
                            rnd, stale_resync, all_conns, nonces
                        )
                        raise RuntimeError(
                            f"only {len(current)} current-base clients "
                            f"uploaded (stale: {sorted(stale_resync)}, "
                            "served catch-up sequences), below the quorum "
                            f"of {quorum} — retrying clients complete the "
                            "next round from the common base"
                        )
                    log.info(
                        f"[SERVER] clients {sorted(stale_resync)} declared "
                        "stale round bases; excluding their uploads and "
                        "serving composed catch-up deltas "
                        f"(contributors: {current})"
                    )
                    ids = current
            if self.secure_agg and self.secure_protocol == "double":
                agg = self._aggregate_double(rnd, models, conns)
                log.info(
                    f"[SERVER] secure-aggregated {len(ids)} masked models "
                    "(double-masking; server never saw raw weights)"
                )
            elif self.secure_agg:
                key_set = list(rnd.key_set or [])
                extra = [i for i in ids if i not in key_set]
                if extra:
                    # Can't happen via the protocol (uploads require the
                    # keys frame) but a forged upload must not poison the
                    # ring sum.
                    raise RuntimeError(
                        f"secure uploads from clients {extra} outside the "
                        f"key set {key_set}"
                    )
                dead = [i for i in key_set if i not in models]
                if dead:
                    # Reveal round (secure.py "dropout recovery"):
                    # survivors disclose their pair secrets with the dead,
                    # and the uncancelled mask halves are subtracted from
                    # the ring sum before de-quantizing over survivors.
                    log.info(
                        f"[SERVER] secure round lost clients {dead}; "
                        f"asking {ids} to reveal their pair secrets"
                    )
                    # Reveal frames are tagged under each survivor's OWN
                    # identity key when per-client keys are provisioned
                    # (group key otherwise, _client_wire_key): an in-group
                    # adversary holding only the group key can then
                    # neither forge a REVEAL_REQ naming a victim that
                    # actually uploaded nor spoof a survivor's response
                    # (secure.py threat model).
                    # Parallel per-survivor exchange with a bounded budget
                    # (same rationale as the reply fan-out below): a
                    # stalled survivor must neither block the others'
                    # requests nor extend the round by a full socket
                    # timeout. Healthy survivors are already blocked in
                    # recv and answer in milliseconds.
                    reveal_budget = min(self.timeout, 30.0)
                    revealed: dict[int, dict] = {}
                    reveal_errs: dict[int, Exception] = {}

                    def _reveal_from(cid: int) -> None:
                        conn = conns[cid]
                        try:
                            conn.settimeout(reveal_budget)
                            framing.send_frame(
                                conn,
                                secure.build_reveal_request(
                                    dead,
                                    session=self._session,
                                    round_index=rnd.round_no,
                                    auth_key=self._client_wire_key(cid),
                                ),
                            )
                            revealed[cid] = secure.parse_reveal_response(
                                framing.recv_frame(conn),
                                session=self._session,
                                round_index=rnd.round_no,
                                client_id=cid,
                                expect_dead=dead,
                                auth_key=self._client_wire_key(cid),
                            )
                            conn.settimeout(self.timeout)
                        except (
                            OSError,
                            ConnectionError,
                            wire.WireError,
                            secure.SecureAggError,
                        ) as e:
                            reveal_errs[cid] = e

                    rthreads = [
                        threading.Thread(
                            target=_reveal_from, args=(cid,), daemon=True
                        )
                        for cid in ids
                    ]
                    for t in rthreads:
                        t.start()
                    for t in rthreads:
                        t.join(timeout=reveal_budget + 5.0)
                    if reveal_errs or set(revealed) != set(ids):
                        # A dropout DURING the reveal is unrecoverable
                        # without Shamir shares (secure.py threat model).
                        raise RuntimeError(
                            f"reveal round failed for clients "
                            f"{sorted(set(ids) - set(revealed))}: "
                            f"{ {c: str(e) for c, e in reveal_errs.items()} }"
                        )
                    summed = secure.sum_masked([models[i] for i in ids])
                    residue = secure.residual_mask_sum(
                        summed,
                        revealed,
                        session=self._session,
                        round_index=rnd.round_no,
                    )
                    agg = secure.dequantize_sum(
                        {k: summed[k] - residue[k] for k in summed},
                        len(ids),
                        self.fp_bits,
                    )
                else:
                    agg = secure.aggregate_masked(
                        [models[i] for i in ids], self.fp_bits
                    )
                log.info(
                    f"[SERVER] secure-aggregated {len(ids)} masked models "
                    + (f"after revealing {len(dead)} dropout(s) " if dead else "")
                    + "(server never saw raw weights)"
                )
            else:
                weights = [n_samples[i] for i in ids] if self.weighted else None
                # Incremental fold (comm/stream_agg.py): leaves already
                # folded during the wait phase — overlapped with the wire
                # — are reused; whatever remains folds here. Sparse-delta
                # uploads become absolute models against the last
                # aggregate at fold time (validated at upload time), so
                # dense, sparse, and streamed clients mix freely in one
                # round. The result is BIT-EXACT with the barrier
                # aggregate_flat (same fp32 ops, same ascending-id order
                # per leaf — pinned by the parity tests).
                try:
                    agg = rnd.stream.finalize(ids, weights)
                except wire.WireError as e:
                    # Incomplete fold input (a superseded stream whose
                    # retry diverged, a key-set mismatch): serve()'s
                    # contract is that this fails the ROUND, not the
                    # server — WireError is a ValueError and would
                    # otherwise escape serve()'s RuntimeError guard.
                    raise RuntimeError(
                        f"streamed aggregation failed: {e}"
                    ) from e
                n_sparse = sum(bool(deltas.get(i)) for i in ids)
                s_stats = rnd.stream.stats()
                log.info(
                    f"[SERVER] aggregated {len(ids)} models (clients {ids}"
                    + (f", {n_sparse} sparse-delta" if n_sparse else "")
                    + (
                        f"; {s_stats['overlap_frac']:.0%} of fold input "
                        "consumed during the wire phase"
                        if s_stats["early_bytes"]
                        else ""
                    )
                    + ")"
                )
            if self.reply_via is not None:
                # Hierarchical fold tree (comm/relay.py): hand the
                # subtree's partial weighted mean to the parent exchange;
                # what comes back — the ROOT's aggregate — is what this
                # subtree's clients receive, adopt, and (sparse tier)
                # difference their next deltas against. A parent failure
                # raises here and the BaseException cleanup below fails
                # the round for the whole subtree (clients retry).
                agg = {
                    k: np.asarray(v, np.float32)
                    for k, v in self.reply_via(
                        agg,
                        {
                            "ids": list(ids),
                            "n_samples": {i: n_samples[i] for i in ids},
                            "round": rnd.round_no,
                            "trace": rnd.trace,
                        },
                    ).items()
                }
            if dp_mode:
                # agg is the uniform mean of CLIPPED DELTAS (plain mode:
                # aggregate_flat over re-clipped uploads; secure mode: the
                # de-quantized masked sum of client-clipped deltas). Add
                # the Gaussian mechanism's noise and reply with the noised
                # mean delta — no absolute weights ever exist server-side,
                # and the sparse-tier base bookkeeping does not apply.
                n = len(ids)
                sigma = self.dp_noise_multiplier * self.dp_clip / n
                if sigma > 0.0:
                    # fp32 draws: Generator.normal would materialize a
                    # float64 model-sized array per tensor first.
                    agg = {
                        k: np.asarray(v, np.float32)
                        + self._dp_rng.standard_normal(
                            np.shape(v), dtype=np.float32
                        )
                        * np.float32(sigma)
                        for k, v in agg.items()
                    }
                log.info(
                    f"[SERVER] central DP: mean of {n} clipped deltas "
                    f"(clip {self.dp_clip}) + Gaussian noise "
                    f"std {sigma:.3g}/coordinate"
                )
                reply_meta = {
                    "agg_round": rnd.round_no,
                    "trace": rnd.trace,
                    "dp_reply": "delta",
                    # The base this delta applies to. A receiver whose own
                    # base differs (a STALE client sitting a sampled round
                    # out) must NOT apply it — compounding a foreign delta
                    # onto a stale base would create a base the retained
                    # history never saw, making the client permanently
                    # unresyncable. It keeps its base instead and resyncs
                    # on its next contributing round.
                    "dp_base_crc": next(iter(crc_set)),
                }
                if rnd.cohort is None:
                    # Under cohort sampling the sampled set stays OUT of
                    # the replies: privacy amplification by subsampling
                    # assumes the adversary cannot condition on who was
                    # sampled. With full participation the "cohort" is
                    # public knowledge anyway.
                    reply_meta["round_clients"] = ids
                if not self.secure_agg and self.compression == "none":
                    # Retain this round's released delta for the resync
                    # window (post-noise: a DP output, so retaining and
                    # re-releasing compositions of it is free
                    # post-processing), keyed by the base crc the round's
                    # current uploads agreed on. An EXACTLY-ZERO delta
                    # (noiseless round, all clients at their base) is NOT
                    # retained: the new base equals the old one, so the
                    # retained crc would collide with every current
                    # client's next declaration and misclassify the whole
                    # fleet as stale — and a zero delta contributes
                    # nothing to any composition anyway.
                    if any(np.any(np.asarray(v)) for v in agg.values()):
                        self._dp_history.append(
                            (
                                next(iter(crc_set)),
                                {
                                    k: np.asarray(v, np.float32)
                                    for k, v in agg.items()
                                },
                            )
                        )
                    for cid, j in stale_resync.items():
                        # Catch-up: every retained delta from the client's
                        # base forward — the tail INCLUDES the entry just
                        # appended. Shipped as the SEQUENCE (keys "0","1",
                        # ...), never pre-summed: the client replays each
                        # round's fp32 addition in order, which is the
                        # only arithmetic that reproduces the fleet's base
                        # BIT-EXACTLY (fp32 addition is not associative —
                        # a server-side sum would land ulps away and fail
                        # the next round's crc agreement for everyone).
                        entries = [d for _, d in self._dp_history[j:]]
                        if not all(
                            wire.shapes_compatible(d, agg) for d in entries
                        ):
                            log.info(
                                f"[SERVER] client {cid} cannot resync: "
                                "retained deltas changed shape mid-window"
                            )
                            continue
                        resync_payloads[cid] = (
                            {
                                str(i): wire.unflatten_params(d)
                                for i, d in enumerate(entries)
                            },
                            len(entries),
                        )
                    # Trim AFTER composing: stale_resync indices address
                    # the pre-trim list (append only extends the tail).
                    if len(self._dp_history) > self.dp_resync_rounds:
                        del self._dp_history[
                            : len(self._dp_history) - self.dp_resync_rounds
                        ]
                    self._persist_dp_history()
            else:
                if self.reply_via is None:
                    # Aggregation strategy (strategies/): a pure transform
                    # of (previous global, folded mean) — the fold above
                    # stays bit-exact, fedavg's transform is the identity,
                    # and relays never transform (the root already did;
                    # a subtree partial is not a global). The per-client
                    # fold stats ride along for telemetry.
                    agg = self._strategy.apply(
                        self._last_agg,
                        agg,
                        round_no=rnd.round_no,
                        client_stats=(
                            rnd.stream.client_stats()
                            if rnd.stream is not None
                            else None
                        ),
                    )
                    self._m_strategy_rounds(self._strategy.name).inc()
                # The new base for next round's sparse deltas, advertised
                # in every reply. Secure mode tracks it too (harmless), but
                # delta uploads are refused there (mask streams carry no
                # sparsity). Under a non-fedavg strategy the base is the
                # POST-transform global — exactly what clients adopt, so
                # next round's deltas difference against the right tree.
                self._last_agg = agg
                self._last_agg_round = rnd.round_no
                # Persist the post-strategy global + optimizer state so
                # a restarted server resumes instead of re-adopting the
                # mean (no-op without strategy_state_path; background
                # writer keeps the fan-out off the disk's latency).
                self._persist_strategy_state()
                # agg_crc: the base-agreement contract. Clients only adopt
                # the decoded reply as their next delta base when it hashes
                # to the server's exact fp32 aggregate — under a lossy
                # reply compression (bf16/int8) it never will, and they
                # stay dense. Lazily computed: it is a full fp32 pass over
                # the model, paid only when a delta-capable client showed
                # up this round (and never in secure mode, where delta
                # uploads are refused).
                reply_meta = {
                    "round_clients": ids,
                    "agg_round": rnd.round_no,
                    "trace": rnd.trace,
                }
                if self.reply_via is None:
                    # Strategy stamp (wire.STRATEGY_META_KEY): which
                    # strategy produced THIS global, doubling as the
                    # round-START advert for the next round — a fedprox
                    # stamp carries the mu clients should anchor their
                    # local loss with. Plain meta: old clients ignore it.
                    reply_meta[wire.STRATEGY_META_KEY] = (
                        self._strategy.describe()
                    )
                if rnd.wants_delta and not self.secure_agg:
                    reply_meta["agg_crc"] = wire.flat_crc32(agg)
            if self.stream_chunk_bytes > 0 and not self.secure_agg:
                # Streamed-upload capability advert (same pattern as the
                # trace field): capable clients chunk-stream their NEXT
                # upload; old peers ignore the extra meta key.
                reply_meta[wire.STREAM_META_KEY] = self.stream_chunk_bytes
                # Wire-dtype advert: the stream leaf encodings this
                # server decodes. A --wire-dtype client quantizes its
                # NEXT streamed upload only after seeing its encoding
                # here (old servers never advertise -> clients stay
                # fp32; old clients ignore the key — interop unchanged
                # both ways).
                reply_meta[wire.WIRE_DTYPE_META_KEY] = sorted(
                    set(wire.WIRE_DTYPE_ENCS.values())
                )
            # Sitting-out clients (cohort sampling) receive the identical
            # reply: the aggregate is the round's public output and their
            # bases must track the fleet's.
            reply_targets = ids + sorted(skip_conns)
            # Streamed replies (wire.py "Streamed replies"): contributors
            # that advertised the capability get STRH/STRC/STRT frames.
            # The payload chunks are built ONCE and shared across the
            # fan-out; resync sequences and sit-out replies stay dense
            # (rare / not advertised).
            stream_ids: list[int] = []
            stream_plan = None
            quant_plan = None
            quant_ids: set[int] = set()
            if self.stream_chunk_bytes > 0 and not self.secure_agg:
                stream_ids = [
                    cid for cid in ids if cid in rnd.stream_replies
                ]
            if stream_ids:
                # Quantized replies (--reply-dtype): only clients whose
                # upload meta advertised the configured encoding get the
                # lossy plan; the rest share the base (self.compression)
                # plan. At most two payload encodes per round, each
                # shared across its cohort.
                quant_enc = wire.WIRE_DTYPE_ENCS[self.reply_dtype]
                if self.reply_dtype != "fp32":
                    quant_ids = {
                        cid
                        for cid in stream_ids
                        if quant_enc in rnd.reply_dtype_encs.get(cid, ())
                    }
                if quant_ids:
                    quant_plan = self._plan_reply_stream(
                        agg, compression=quant_enc
                    )
                if any(cid not in quant_ids for cid in stream_ids):
                    stream_plan = self._plan_reply_stream(agg)
            dense_targets = [c for c in reply_targets if c not in stream_ids]
            if not dense_targets:
                # All-streaming fleet: no dense blob to build — skipping
                # the encode saves a model-sized copy + CRC pass per
                # round in exactly the shape this PR optimizes for.
                replies = {}
            elif self.auth_key is None:
                # One shared reply blob, referenced by every client.
                shared = wire.encode(
                    agg, meta=reply_meta, compression=self.compression
                )
                replies = {cid: shared for cid in dense_targets}
            else:
                # Auth mode: each reply echoes that client's challenge nonce
                # with role=server, so it can't be replayed or reflected.
                # (Per-client encode costs one extra payload memcpy each.)
                replies = {
                    cid: self._encode_reply(agg, reply_meta, nonces.get(cid))
                    for cid in dense_targets
                }
            stream_jobs = {
                cid: (
                    self._encode_stream_reply_header(
                        quant_plan if cid in quant_ids else stream_plan,
                        reply_meta,
                        nonces.get(cid),
                    ),
                    bytes.fromhex(nonces[cid]) if cid in nonces else b"",
                    quant_plan if cid in quant_ids else stream_plan,
                )
                for cid in stream_ids
            }
            # Stale-but-resyncable DP clients: the reply is the catch-up
            # SEQUENCE of retained round deltas (applied in order to
            # their base) — their excluded uploads already cost them the
            # round's contribution; this puts them back on the fleet's
            # exact base for the next one.
            for cid, (sequence, n_rounds) in resync_payloads.items():
                replies[cid] = self._encode_reply(
                    sequence,
                    {
                        **reply_meta,
                        "dp_reply": "resync",
                        "dp_resync_rounds": n_rounds,
                    },
                    nonces.get(cid),
                )
                log.info(
                    f"[SERVER] client {cid} resynced with a catch-up "
                    f"sequence of {n_rounds} retained round delta(s)"
                )
            for cid in stale_resync:
                if cid not in resync_payloads:
                    # Unresyncable after all (shape drift mid-window):
                    # close now so the client fails fast instead of
                    # blocking on a reply that will never come.
                    c = all_conns.get(cid)
                    if c is not None:
                        c.close()
        except BaseException:
            # A failed round must not leave clients blocked in recv_frame
            # until their timeouts — drop every connection so they fail fast.
            for c in all_conns.values():
                c.close()
            self._finish_round(
                rnd, t_round_unix, t_round0, wait_s,
                time.monotonic() - t_agg0, 0.0, failed=True,
            )
            raise
        agg_s = time.monotonic() - t_agg0
        # The round's ACTUAL aggregation assignment (fold order at this
        # tier, each relay contributor expanded to the client ids its
        # partial folded) — what the crc contract replays over.
        self.last_assignment = {
            "round": rnd.round_no,
            "groups": [
                list(subtree_ids[i]) if i in subtree_ids else int(i)
                for i in ids
            ],
        }
        degraded = (
            missing_n > 0 and rnd.cohort is None and not self.secure_agg
        )
        if degraded:
            # Quorum semantics, one level up: the round COMPLETED over
            # the survivors. At a parent of relays the missing children
            # are whole subtrees — stamp the event, count it, and
            # preserve the evidence (subtree-failure flight bundle); at
            # a leaf tier they are stragglers shed at this aggregator's
            # local deadline. Known coarseness: a plain round's expected
            # count carries no per-child identity, so a MIXED tier (some
            # children relays, some direct leaves) attributes every
            # missing child to the dominant shape — subtrees whenever
            # any upload carried a contributor record. Keep tiers
            # homogeneous (the documented topology) for exact counts.
            tree_key = (
                "subtree_failures" if subtree_ids else "stragglers_shed"
            )
            with self._totals_lock:
                self.tree_totals[tree_key] += missing_n
                self.tree_totals["degraded_rounds"] += 1
            if subtree_ids:
                self._m_subtree_failures.inc(float(missing_n))
                log.warning(
                    f"[SERVER] round {rnd.round_no} completed DEGRADED: "
                    f"{missing_n} expected subtree(s) never uploaded "
                    f"within the deadline; folded the surviving "
                    f"contributors {ids} (mean renormalized over their "
                    "mass)"
                )
                recorder = obs_flight.get_global_recorder()
                if recorder is not None:
                    try:
                        recorder.maybe_dump(
                            "subtree-failure",
                            extra={
                                "round": rnd.round_no,
                                "trace": rnd.trace,
                                "expected": rnd.expected,
                                "missing_subtrees": missing_n,
                                "survivors": [int(i) for i in ids],
                            },
                        )
                    except OSError as e:
                        log.warning(
                            "[SERVER] subtree-failure postmortem dump "
                            f"failed (non-fatal): {e}"
                        )
            else:
                self._m_stragglers_shed.inc(float(missing_n))
                log.info(
                    f"[SERVER] round {rnd.round_no}: shed {missing_n} "
                    "straggler(s) at the local deadline; proceeding "
                    f"over {ids}"
                )
        if self.tracer is not None:
            extra = {}
            if degraded and subtree_ids:
                extra["missing_subtrees"] = missing_n
            elif degraded:
                extra["stragglers_shed"] = missing_n
            if adopted:
                extra["adopted"] = sorted(int(i) for i in adopted)
            if subtree_ids:
                extra["assignment"] = self.last_assignment["groups"]
            if self.reply_via is None:
                # Which strategy produced this round's global (+ its
                # hyperparams): the postmortem flight bundle / obs watch
                # answer to "what aggregation rule was live here".
                extra["strategy"] = self._strategy.name
                s_params = self._strategy.params()
                if s_params:
                    extra["strategy_params"] = {
                        k: s_params[k] for k in sorted(s_params)
                    }
            self.tracer.record(
                "agg",
                t_start=t_agg_unix,
                dur_s=agg_s,
                trace=rnd.trace,
                round=rnd.round_no,
                clients=len(models),
                # The round's CONTRIBUTOR set (post staleness exclusion):
                # the obs timeline's drop attribution — who was actually
                # aggregated vs who uploaded-but-was-excluded vs who
                # never arrived (faults/scenario.py consumes this).
                contributors=[int(i) for i in ids],
                **extra,
            )
        t_rep_unix = time.time()
        t_rep0 = time.monotonic()
        self._reply_all(replies, all_conns, stream_jobs)
        reply_s = time.monotonic() - t_rep0
        out_bytes = float(sum(len(b) for b in replies.values()))
        if stream_jobs:
            out_bytes += sum(
                len(hdr) + plan["payload_nbytes"]
                for hdr, _, plan in stream_jobs.values()
            )
            with self._totals_lock:
                self.stream_totals["stream_replies"] += len(stream_jobs)
            self._m_stream_replies.inc(float(len(stream_jobs)))
        self._m_bytes_out.inc(out_bytes)
        if self.tracer is not None:
            self.tracer.record(
                "wire-reply",
                t_start=t_rep_unix,
                dur_s=reply_s,
                trace=rnd.trace,
                round=rnd.round_no,
                replies=len(replies),
            )
        self._finish_round(
            rnd, t_round_unix, t_round0, wait_s, agg_s, reply_s
        )
        return agg

    def _finish_round(
        self,
        rnd: _Round,
        t_unix: float,
        t0: float,
        wait_s: float,
        agg_s: float,
        reply_s: float,
        *,
        failed: bool = False,
    ) -> None:
        """Close a round's observability: accumulate the wait/agg/reply
        phase seconds (process totals AND /metrics counters), fold the
        round's streaming stats into the cross-round totals (plus the
        ``wire-overlap`` span when any fold overlapped the wire), and
        emit the round span."""
        round_wall = time.monotonic() - t0
        for name, dur in (("wait", wait_s), ("agg", agg_s), ("reply", reply_s)):
            self.phase_seconds[name] += dur
            self._m_phase[name].inc(max(dur, 0.0))
        self._h_round.observe(max(round_wall, 0.0))
        # Device-memory watermark at the round's aggregation boundary
        # (obs/profile.py): meaningful on accelerator-backed server
        # hosts, a graceful no-op on the host-only numpy tier.
        note_memory("post-aggregate")
        if failed:
            self._m_round_failures.inc()
        if rnd.stream is not None:
            s = rnd.stream.stats()
            with self._totals_lock:
                tot = self.stream_totals
                tot["early_bytes"] += s["early_bytes"]
                tot["late_bytes"] += s["late_bytes"]
                tot["early_s"] += s["early_s"]
                tot["late_s"] += s["late_s"]
                tot["peak_agg_bytes"] = max(
                    tot["peak_agg_bytes"], s["peak_bytes"]
                )
                # Last ROUND's peak separately: a mixed campaign's first
                # (dense, pre-advert) round peaks at O(clients x model)
                # and would mask the streamed rounds' O(model +
                # in-flight) in the cross-round max.
                tot["last_round_peak_bytes"] = s["peak_bytes"]
                # Compiled-fold telemetry (ops/fold.py): which engine
                # folded and at what throughput — the source of the
                # fedtpu_server_fold_throughput_gbps gauge.
                tot["fold_engine"] = s["fold_engine"]
                tot["last_fold_throughput_gbps"] = s[
                    "fold_throughput_gbps"
                ]
            self._g_peak_agg.set(float(s["peak_bytes"]))
            if s["fold_s"] > 0.0:
                self._g_fold_throughput.set(
                    float(s["fold_throughput_gbps"])
                )
            if self.tracer is not None and s["early_s"] > 0.0:
                # Overlapped-vs-exposed wire attribution: how much fold
                # work ran DURING the wait phase (hidden behind other
                # clients' transfers) — the obs timeline's overlap row.
                wire_dtypes = sorted(set(rnd.wire_dtypes.values()))
                self.tracer.record(
                    "wire-overlap",
                    t_start=s["first_fold_unix"] or t_unix,
                    dur_s=s["early_s"],
                    trace=rnd.trace,
                    round=rnd.round_no,
                    folded_bytes=s["early_bytes"],
                    overlap_frac=round(s["overlap_frac"], 4),
                    peak_agg_bytes=s["peak_bytes"],
                    fold_engine=s["fold_engine"],
                    fold_throughput_gbps=round(
                        s["fold_throughput_gbps"], 3
                    ),
                    wire_dtypes=wire_dtypes or None,
                )
        if self.tracer is not None:
            self.tracer.record(
                "round",
                t_start=t_unix,
                dur_s=round_wall,
                trace=rnd.trace,
                round=rnd.round_no,
                failed=True if failed else None,
            )
        if failed:
            # Flight recorder (obs/flight.py): a failed round is exactly
            # the moment whose surrounding spans + metric state an
            # operator wants preserved. After the round span above so
            # the bundle's ring includes the failure itself. Rate-
            # limited; never fatal to the round path.
            recorder = obs_flight.get_global_recorder()
            if recorder is not None:
                try:
                    recorder.maybe_dump(
                        "round-failure",
                        extra={
                            "round": rnd.round_no,
                            "trace": rnd.trace,
                            "expected": rnd.expected,
                            "wall_s": round(round_wall, 3),
                        },
                    )
                except OSError as e:
                    log.warning(
                        f"[SERVER] postmortem dump failed (non-fatal): {e}"
                    )

    def _encode_reply(self, agg: dict, meta: dict, nonce: str | None) -> bytes:
        """One reply blob, auth-aware (echoes the client's nonce with
        role=server in auth mode)."""
        if self.auth_key is None:
            return wire.encode(agg, meta=meta, compression=self.compression)
        return wire.encode(
            agg,
            meta={**meta, "role": "server", "nonce": nonce},
            compression=self.compression,
            auth_key=self.auth_key,
        )

    def _plan_reply_stream(self, agg: dict, compression: str | None = None) -> dict:
        """Build the round's shared streamed-reply payload ONCE: the
        tensor plan plus the chunk payload list every advertised client's
        fan-out references. Per-client state (header meta, auth tags) is
        layered on in :meth:`_encode_stream_reply_header` and
        :meth:`_send_stream_reply` — a 256-client fan-out never holds
        more than one encoded copy of the model payload. ``compression``
        overrides the server's reply compression for the QUANTIZED reply
        plan (``--reply-dtype``): at most two plans exist per round — this
        one for capability-advertising clients, the base plan for the
        rest — each still shared across its cohort."""
        if compression is None:
            compression = self.compression
        flat = wire.flatten_lazy(agg)
        tensors, payload_nbytes = wire.plan_stream(flat, compression)
        chunks: list[bytes] = []
        buf = bytearray()
        for t in tensors:
            buf += wire.encode_stream_leaf(flat[t["key"]], t["enc"])
            while len(buf) >= self.stream_chunk_bytes:
                chunks.append(bytes(buf[: self.stream_chunk_bytes]))
                del buf[: self.stream_chunk_bytes]
        if buf:
            chunks.append(bytes(buf))
        return {
            "tensors": tensors,
            "chunks": chunks,
            "payload_nbytes": payload_nbytes,
        }

    def _encode_stream_reply_header(
        self, plan: dict, meta: dict, nonce: str | None
    ) -> bytes:
        """One client's STRH reply header (auth mode echoes its nonce
        with role=server, exactly like the dense reply's meta)."""
        if self.auth_key is not None:
            meta = {**meta, "role": "server", "nonce": nonce}
        return wire.encode_stream_header(
            plan["tensors"],
            meta=meta,
            chunk_bytes=self.stream_chunk_bytes,
            payload_nbytes=plan["payload_nbytes"],
            auth_key=self.auth_key,
            direction="down",
        )

    def _send_stream_reply(
        self,
        conn: socket.socket,
        header: bytes,
        plan: dict,
        nonce: bytes,
    ) -> None:
        """Ship one streamed reply: ACKed header, fire-and-forget chunk
        frames (the client reads them without interleaving ACK writes),
        ACKed trailer — the mirror of the upload direction's shape. Chunk
        envelopes (seq + per-connection tag under the reply-direction
        domain) are built per send, so the shared payload is never
        duplicated per client."""
        framing.send_frame(conn, header)
        for seq, chunk in enumerate(plan["chunks"]):
            framing.send_frame(
                conn,
                wire.encode_stream_chunk(
                    seq,
                    chunk,
                    auth_key=self.auth_key,
                    nonce=nonce,
                    direction="down",
                ),
                await_ack=False,
            )
        framing.send_frame(
            conn,
            wire.encode_stream_end(
                len(plan["chunks"]),
                auth_key=self.auth_key,
                nonce=nonce,
                direction="down",
            ),
        )

    def _reply_all(
        self,
        replies: dict[int, bytes],
        conns_map: dict[int, socket.socket],
        stream_jobs: dict[int, tuple[bytes, bytes, dict]] | None = None,
    ) -> None:
        """Parallel reply fan-out: send_frame blocks on the client's ACK,
        so a sequential loop would let one dead client stall every healthy
        one behind it for a full socket timeout. ``stream_jobs`` clients
        get the chunk-streamed shape instead of their ``replies`` blob;
        each job carries its own plan (base vs ``--reply-dtype`` quantized
        — the plan OBJECTS are still shared per cohort)."""
        stream_jobs = stream_jobs or {}

        def _reply(cid: int, conn: socket.socket) -> None:
            try:
                if cid in stream_jobs:
                    header, nonce, plan = stream_jobs[cid]
                    self._send_stream_reply(conn, header, plan, nonce)
                else:
                    framing.send_frame(conn, replies[cid])
            except (OSError, wire.WireError, ConnectionError) as e:
                log.info(f"[SERVER] reply to client {cid} failed: {e}")
            finally:
                conn.close()

        reply_threads = [
            threading.Thread(
                target=_reply, args=(cid, conns_map[cid]), daemon=True
            )
            for cid in {*replies, *stream_jobs}
        ]
        for t in reply_threads:
            t.start()
        for t in reply_threads:
            t.join(timeout=self.timeout)

    def comm_overlap_frac(self) -> float:
        """Bytes-weighted fraction of this server's aggregation input
        folded while the round's wire phase was still active (0.0 on a
        pure barrier run); tests/test_stream.py holds it above 0 streamed."""
        with self._totals_lock:
            early = self.stream_totals["early_bytes"]
            tot = early + self.stream_totals["late_bytes"]
        return early / tot if tot else 0.0

    def serve(self, rounds: int = 1) -> None:
        """Multi-round loop: one failed round (quorum missed, DP base
        mismatch, reveal dropout) must not kill the server for every
        remaining round — the reference hangs forever in this situation
        (server.py:124-132); here the round is logged and the next one
        proceeds, so retrying clients can still complete it."""
        for r in range(rounds):
            log.info(f"[SERVER] round {r + 1}/{rounds}")
            try:
                self.serve_round()
            except RuntimeError as e:
                log.info(f"[SERVER] round {r + 1} failed: {e}")
