"""Streaming chunk aggregation for one round (comm/server.py PR 5).

The barrier aggregation path materializes every client's full state dict
and only then computes the weighted mean — O(N·model) peak memory, and
all of the aggregation compute exposed after the last upload finishes.
This module is the round's incremental alternative: uploads register an
*intent* (tensor key set + sample count, from the stream header or a
dense frame), leaves are handed over one at a time as their bytes
arrive, and the moment every fold-set member's copy of a leaf is present
the leaf is **folded** into the running mean and freed. Peak memory
drops toward O(model + in-flight leaves), and the fold work overlaps the
slower clients' remaining wire transfer.

Bit-exactness contract (pinned by tests): the folded result equals
``comm.server.aggregate_flat`` — the barrier mean — BIT-EXACTLY. That
holds because the fold replays the identical fp32 arithmetic in the
identical order: per key, ``acc = zeros; acc += float32(w_i) * leaf_i``
over clients in ascending-id order, with weights normalized in float64
exactly as the barrier does. fp32 addition is non-associative, so the
ascending-id order per leaf is not a style choice — it is what keeps the
base crc every DP/resync test pins unchanged.

Consequences of folding early (documented trade-offs):

* The fold set must be FROZEN before the first fold (weights are
  normalized over it). It freezes when every expected client's intent
  has arrived — milliseconds into a healthy round. If a client never
  shows up, nothing folds and ``finalize`` degrades to the barrier mean
  over the survivors at round close (quorum semantics unchanged, no
  overlap).
* A client that dies (or re-uploads) AFTER folds began poisons the
  round: its already-folded leaves cannot be subtracted back out. The
  round fails with a clear reason and clients retry; the next round's
  freeze simply never includes the dead client.
* A streamed DP upload that exceeds its declared clip can only be
  re-clipped server-side while none of its leaves have folded; once
  folds consumed unscaled leaves the round fails closed instead of
  widening the mechanism's sensitivity (the barrier path re-clips and
  proceeds; honest clients — which already clip client-side — never see
  the difference).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Mapping

import numpy as np

from . import wire
from ..ops import fold as fold_ops


class StreamAggPoisoned(RuntimeError):
    """The running aggregate can no longer reach a correct mean (a folded
    contributor died, re-uploaded, or violated its clip)."""


class StreamAgg:
    """One round's incremental weighted-mean state.

    Thread-safety: one internal lock serializes every mutation; folds run
    under it, which also serializes the fp32 accumulation (required for
    the bit-exactness contract — two concurrent folds of one key would
    race the accumulator).

    ``eager=False`` disables freezing/folding entirely: every upload is
    held and ``finalize`` computes the barrier mean at close. That is the
    non-pipelined A/B arm tests/test_stream.py compares against.
    """

    def __init__(
        self,
        *,
        eager: bool = True,
        base: Mapping[str, np.ndarray] | None = None,
    ):
        self._lock = threading.Lock()
        self.eager = bool(eager)
        #: Last aggregate (sparse-delta base): a dense delta upload folds
        #: as ``base[key] + delta`` exactly like the barrier's absolute
        #: reconstruction.
        self.base = base
        #: cid -> {"keys": tuple, "n_samples": float, "delta": bool,
        #:         "dp_crc": int | None}
        self.intents: dict[int, dict] = {}
        self._pending: dict[str, dict[int, np.ndarray]] = {}
        self._acc: dict[str, np.ndarray] = {}
        self._folded: set[str] = set()
        self.fold_ids: list[int] | None = None
        self._weights: dict[int, np.float64] | None = None
        self.poisoned: str | None = None
        self._wait_over = False
        #: cids whose upload fully arrived: a fold only counts as
        #: "overlapped" while some member's bytes are still in flight.
        self._complete: set[int] = set()
        #: Per-client fold stats handed to the round's aggregation
        #: strategy at finalize (strategies/core.py ``client_stats``):
        #: cid -> {"weight", "bytes", "scale"}. An entry lives exactly
        #: as long as the client's intent — ``drop_client`` purges it
        #: unconditionally (even on the poisoned path) so a dropped
        #: client can never leak into the strategy's view of the round.
        self._strategy_stats: dict[int, dict[str, float]] = {}
        # accounting (the obs layer's wire-overlap span)
        self._cur_bytes = 0
        self.peak_bytes = 0
        self.early_bytes = 0
        self.late_bytes = 0
        self.early_s = 0.0
        self.late_s = 0.0
        self.first_fold_unix: float | None = None

    # ------------------------------------------------------------ intents
    def register(
        self,
        cid: int,
        *,
        keys: tuple,
        n_samples: float,
        delta: bool = False,
        dp_crc: int | None = None,
    ) -> None:
        with self._lock:
            self.intents[cid] = {
                "keys": tuple(keys),
                "n_samples": float(n_samples),
                "delta": bool(delta),
                "dp_crc": dp_crc,
            }
            self._strategy_stats[cid] = {
                "weight": float(n_samples),
                "bytes": 0.0,
                "scale": 1.0,
            }

    def admit(self, cid: int) -> bool:
        """Late-adopt a NEW contributor (a re-homed client, comm/server.py)
        into the round's fold. Before any fold ran, a frozen fold set is
        simply un-frozen — the next freeze re-normalizes the weights over
        the grown set, still the exact barrier mean. Once folds consumed
        the frozen weights no correct mean including ``cid`` exists any
        more: returns False and the caller refuses the adoption (the
        round's integrity beats the straggler's membership)."""
        with self._lock:
            if self.fold_ids is None or cid in self.fold_ids:
                return True
            if self._folded:
                return False
            self.fold_ids = None
            self._weights = None
            return True

    def drop_client(self, cid: int, *, poison: bool = True) -> bool:
        """Forget a client's unfolded state (mid-stream death, duplicate
        re-upload). Returns False when folds already consumed its leaves
        — poisoning the round when ``poison`` (a folded contributor DIED;
        no correct mean exists any more), or leaving it intact when not
        (a DUPLICATE upload is simply refused and the folded original
        stands). Before any fold, a frozen fold set containing ``cid`` is
        un-frozen again: nothing was consumed, so ``finalize`` can
        re-freeze over the survivors — the exact barrier semantics for a
        pre-aggregation death."""
        with self._lock:
            if self.fold_ids and cid in self.fold_ids:
                if self._folded:
                    if poison:
                        self.poisoned = (
                            f"client {cid} dropped its upload after "
                            f"{len(self._folded)} leaf folds already "
                            "consumed it"
                        )
                        # The round is dead either way, but the strategy
                        # view must not keep a ghost contributor: a
                        # poisoned-round retry reuses nothing, and the
                        # stats() invariant (strategy stats ⊆ intents)
                        # holds even on this failure path.
                        self.intents.pop(cid, None)
                        self._strategy_stats.pop(cid, None)
                        self._complete.discard(cid)
                    return False
                self.fold_ids = None
                self._weights = None
            self.intents.pop(cid, None)
            self._strategy_stats.pop(cid, None)
            self._complete.discard(cid)
            for leaves in self._pending.values():
                arr = leaves.pop(cid, None)
                if arr is not None:
                    self._cur_bytes -= arr.nbytes
            return True

    def mark_complete(self, cid: int) -> None:
        """The client's upload fully arrived (trailer verified / dense
        frame decoded): later folds no longer overlap ITS wire time."""
        with self._lock:
            self._complete.add(cid)

    def scale_client(self, cid: int, scale: float) -> bool:
        """Apply the DP re-clip scale to a client's pending leaves
        (``leaf * float32(scale)`` — byte-identical to the barrier's
        ``wire.clip_flat``). Returns False when folds already consumed
        unscaled leaves (caller fails the round)."""
        with self._lock:
            if self._folded and self.fold_ids and cid in self.fold_ids:
                self.poisoned = (
                    f"client {cid} exceeded its DP clip after folds "
                    "already consumed its unscaled leaves"
                )
                return False
            for leaves in self._pending.values():
                if cid in leaves:
                    leaves[cid] = np.asarray(
                        leaves[cid], np.float32
                    ) * np.float32(scale)
            if cid in self._strategy_stats:
                self._strategy_stats[cid]["scale"] *= float(scale)
            return True

    # ------------------------------------------------------------- leaves
    def add_leaf(self, cid: int, key: str, arr: np.ndarray) -> None:
        with self._lock:
            if key in self._folded:
                # A late leaf for an already-folded key can only belong
                # to a non-member (e.g. a stale DP client being drained);
                # a member's leaves were all present by definition.
                return
            prev = self._pending.setdefault(key, {}).get(cid)
            if prev is not None:
                # Re-supplied leaf (a dense retry completing a superseded
                # stream): replacement, not accumulation.
                self._cur_bytes -= prev.nbytes
            self._pending[key][cid] = arr
            self._cur_bytes += arr.nbytes
            if cid in self._strategy_stats:
                self._strategy_stats[cid]["bytes"] += float(arr.nbytes)
            self.peak_bytes = max(self.peak_bytes, self._cur_bytes)
            if self.fold_ids is not None:
                self._maybe_fold(key)

    def add_dense(self, cid: int, flat: Mapping[str, np.ndarray]) -> None:
        """A single-frame upload: all leaves at once (old-peer interop —
        dense and streamed clients mix freely in one fold)."""
        with self._lock:
            self._complete.add(cid)
            for key, arr in flat.items():
                if key in self._folded:
                    continue
                arr = np.asarray(arr)
                prev = self._pending.setdefault(key, {}).get(cid)
                if prev is not None:
                    self._cur_bytes -= prev.nbytes
                self._pending[key][cid] = arr
                self._cur_bytes += arr.nbytes
                if cid in self._strategy_stats:
                    self._strategy_stats[cid]["bytes"] += float(arr.nbytes)
            self.peak_bytes = max(self.peak_bytes, self._cur_bytes)
            if self.fold_ids is not None:
                for key in list(self._pending):
                    self._maybe_fold(key)

    # -------------------------------------------------------------- folds
    def freeze(self, ids: list[int], weights: list[float] | None) -> None:
        """Fix the fold set + normalized weights (weight math identical
        to ``aggregate_flat``), then fold every leaf already complete."""
        with self._lock:
            if self.poisoned:
                return
            ids = sorted(int(i) for i in ids)
            if self.fold_ids is not None:
                if ids == self.fold_ids:
                    return
                if self._folded:
                    # Folds already ran with the old set's weights; a
                    # different contributor set cannot reach a correct
                    # mean any more.
                    self.poisoned = (
                        f"fold set changed after {len(self._folded)} "
                        f"folds ({self.fold_ids} -> {ids})"
                    )
                    return
                # Frozen but nothing folded yet (a member died between
                # its intent and its first complete leaf): re-freeze
                # over the final set — still the exact barrier mean.
                self.fold_ids = None
                self._weights = None
            if weights is None:
                w = np.ones(len(ids), np.float64)
            else:
                w = np.asarray(weights, np.float64)
                if w.shape != (len(ids),) or w.sum() <= 0:
                    raise ValueError(f"bad weights {weights}")
            w = w / w.sum()
            self._weights = {cid: w[i] for i, cid in enumerate(ids)}
            self.fold_ids = ids
            for key in list(self._pending):
                self._maybe_fold(key)

    def _maybe_fold(self, key: str) -> None:
        """Caller holds the lock; folds ``key`` when every fold-set
        member's leaf is present."""
        if self.poisoned or key in self._folded:
            return
        leaves = self._pending.get(key)
        if leaves is None or any(c not in leaves for c in self.fold_ids):
            return
        # fedtpu: allow(determinism): first-fold wall-clock for the
        # wire-overlap span's t_start — observability only, the fold value
        # and order come from fold_ids
        t_unix = time.time()
        t0 = time.monotonic()
        try:
            # Batched fold: materialize the K leaves in ascending-id order
            # and hand them to the fold engine in ONE dispatch. Every
            # engine replays the identical per-element fp32 mul/add
            # sequence, so the result stays bit-exact with the barrier
            # mean regardless of which engine folded (pinned by the
            # shuffled-arrival property test).
            ordered: list[np.ndarray] = []
            for cid in self.fold_ids:
                arr = leaves[cid]
                if self.intents[cid].get("delta"):
                    # Barrier parity: absolute = base + float32(delta),
                    # validated against the base at upload time.
                    arr = self.base[key] + np.asarray(arr, np.float32)
                arr = np.asarray(arr, np.float32)
                if ordered and arr.shape != ordered[0].shape:
                    raise wire.WireError(f"shape mismatch for {key!r}")
                ordered.append(arr)
            acc = fold_ops.fold_ordered(
                ordered, [np.float32(self._weights[c]) for c in self.fold_ids]
            )
        except Exception as e:  # poison, don't kill the handler thread
            self.poisoned = f"fold of {key!r} failed: {e}"
            return
        self._acc[key] = acc
        freed = sum(a.nbytes for a in leaves.values())
        del self._pending[key]
        self._cur_bytes += acc.nbytes - freed
        self.peak_bytes = max(self.peak_bytes, self._cur_bytes)
        self._folded.add(key)
        dur = time.monotonic() - t0
        overlapped = not self._wait_over and any(
            c not in self._complete for c in self.fold_ids
        )
        if overlapped:
            if self.first_fold_unix is None:
                self.first_fold_unix = t_unix
            self.early_bytes += freed
            self.early_s += dur
        else:
            self.late_bytes += freed
            self.late_s += dur

    def mark_wait_end(self) -> None:
        """The round's wait phase is over: folds from here on are exposed
        aggregation time, not overlapped wire time."""
        with self._lock:
            self._wait_over = True

    # ----------------------------------------------------------- finalize
    def finalize(
        self, ids: list[int], weights: list[float] | None
    ) -> dict[str, np.ndarray]:
        """Fold whatever is left over the FINAL contributor set and
        return the mean. With no prior freeze (non-eager mode, or a
        straggler round that never completed its intents) this IS the
        barrier computation; with one, ``ids`` must match the frozen set
        — a divergence means folds used wrong weights, so fail loudly."""
        if self.poisoned:
            raise StreamAggPoisoned(self.poisoned)
        self.freeze(ids, weights)
        with self._lock:
            if self.poisoned:
                raise StreamAggPoisoned(self.poisoned)
            want = set(str(k) for i in self.fold_ids for k in self.intents[i]["keys"])
            for i in self.fold_ids:
                if set(self.intents[i]["keys"]) != want:
                    raise wire.WireError(
                        f"model {i} key set differs from the round's"
                    )
            missing = sorted(want - self._folded)
            for key in missing:
                leaves = self._pending.get(key, {})
                absent = [c for c in self.fold_ids if c not in leaves]
                if absent:
                    raise wire.WireError(
                        f"leaf {key!r} never arrived from clients {absent}"
                    )
                self._maybe_fold(key)
            if self.poisoned:
                raise StreamAggPoisoned(self.poisoned)
            return dict(sorted(self._acc.items()))

    # -------------------------------------------------------------- stats
    def client_stats(self) -> dict[int, dict[str, float]]:
        """Per-client fold stats for the round's aggregation strategy
        (snapshot copy: the strategy must see the round, not a live
        mutable view)."""
        with self._lock:
            return {
                cid: dict(self._strategy_stats[cid])
                for cid in sorted(self._strategy_stats)
            }

    def stats(self) -> dict[str, Any]:
        with self._lock:
            # Invariant (strategies/ PR): a dropped client's strategy
            # stats entry dies with its intent — a poisoned mid-round
            # drop must not leave a ghost contributor for the strategy.
            stale = sorted(set(self._strategy_stats) - set(self.intents))
            assert not stale, (
                f"strategy stats leak for dropped clients {stale}"
            )
            folded = self.early_bytes + self.late_bytes
            fold_s = self.early_s + self.late_s
            return {
                "peak_bytes": int(self.peak_bytes),
                "early_bytes": int(self.early_bytes),
                "late_bytes": int(self.late_bytes),
                "early_s": float(self.early_s),
                "late_s": float(self.late_s),
                "overlap_frac": (
                    self.early_bytes / folded if folded else 0.0
                ),
                "first_fold_unix": self.first_fold_unix,
                "fold_engine": fold_ops.engine_name(),
                "fold_s": float(fold_s),
                "fold_throughput_gbps": (
                    folded / fold_s / 1e9 if fold_s > 0 and folded else 0.0
                ),
            }
