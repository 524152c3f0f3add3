"""Bucketed jit cache: score variable-size batches through fixed shapes.

A scoring service sees every batch size from 1 (a lone probe) to the
coalescing cap. Jitting on the raw size would compile a fresh XLA
program per novel size — a multi-second stall mid-traffic, per size.
Instead batches are padded up to a small ladder of bucket shapes
(default 1/8/32/128) so the service runs at most ``len(buckets)``
compilations for its whole lifetime, all of them optionally paid at
startup (``warmup()``), and every request thereafter hits a warm path.

The probability math is exactly the eval path's (train/engine.py
``eval_counts``): ``softmax(model.apply(...))`` with deterministic
apply — scalar score ``[:, 1]`` for K = 2, ``1 - [:, 0]`` for K > 2
(the same STATIC head-width branch) — and pad rows built the way
``pad_split_to_batch`` builds them — which is what makes served
probabilities bit-for-bit equal to ``fedtpu predict``'s (pinned in
tests/test_serving.py). The full per-class softmax rides along so the
serving wire can carry K-class scores (serving/protocol.py
``class_probs``).

Sharded serving (``mesh=``): with an FSDP host mesh the engine holds
params sharded per-leaf AT REST (parallel/mesh.py ``fsdp_tree_shardings``
— per-chip static bytes ~1/N) and all-gathers the weights AT USE via a
separate per-dispatch jitted program (``fsdp_gather_program`` — see its
docstring for why the gather is NOT the train step's in-body constraint:
inlined collectives shift XLA's fusion and drift the probs by 1 ulp,
breaking the crc contract below), so full-size weights exist only
transiently during a forward and every bucket program compiles the SAME
collective-free module the replicated engine runs — served probabilities
from a sharded replica are bit-identical to a replicated one's
(tests/test_serving_fsdp.py). ``swap`` re-places onto the SAME
shape-deterministic layout (``fsdp_spec`` is a pure function of
(shape, n_shards)), so a rolling hot-reload reuses every warm bucket
program — the ledger's 0-recompile guarantee holds across reloads.
The shard-layout derivation is inside the ``fedtpu check`` determinism
scope: the layout must replay identically on every process, or a
restore-scatter and a reply-leaf sink would disagree about where bytes
live.

Compile counting: the Python body of a jitted function runs once per
traced shape — so a trace hook inside ``_probs`` IS a compile hook, not
a call counter. That discipline is now the repo-wide
:class:`~..obs.profile.CompileLedger` (this module pioneered it as a
local dict); each engine holds a PRIVATE ledger under the
``serving.probs`` site so ``compile_counts`` stays per-engine while the
``fedtpu_xla_*`` /metrics families aggregate process-wide.
``compile_counts`` maps (batch, seq) to trace count; the e2e test
storms mixed sizes and asserts every value == 1, and ``warmup()`` marks
the site warm so any later novel shape is flagged as a recompile.
"""

from __future__ import annotations

import threading
from typing import Any

import numpy as np

from ..config import ModelConfig
from ..models import build_classifier
from ..obs.profile import CompileLedger, maybe_step_profiler, profile_stride
from ..utils.logging import get_logger

log = get_logger()

DEFAULT_BUCKETS = (1, 8, 32, 128)


class ScoreEngine:
    """Pad-to-bucket scoring over one jitted program per (bucket, seq).

    Thread contract: ``score`` is called by the single scorer thread;
    ``swap`` may be called from the watcher/scorer; the params reference
    is swapped atomically under a lock (scoring holds whichever params it
    read at dispatch — a reload never tears a batch)."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        params: Any,
        *,
        pad_id: int = 0,
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        round_id: int = 0,
        mesh: Any = None,
    ):
        import jax

        if not buckets or any(b < 1 for b in buckets):
            raise ValueError(f"buckets {buckets} must be positive")
        self.model_cfg = model_cfg
        self.pad_id = int(pad_id)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.seq_len = int(model_cfg.max_len)
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"]) if mesh is not None else 1
        # Private compile ledger (obs/profile.py): per-engine counts —
        # two engines in one process must not mix their compile-count
        # assertions — while the metric families it increments are the
        # shared process-wide fedtpu_xla_* ones.
        self.ledger = CompileLedger()
        note_compile = self.ledger.hook("serving.probs")
        # Score-path step attribution: armed only when profiling is on
        # process-wide (--profile-stride / ObsConfig.profile_stride).
        self.step_profiler = maybe_step_profiler("score")
        self._lock = threading.Lock()
        self._params = self._place(params)
        self._round_id = int(round_id)
        # Gather-at-use as its OWN jitted program (parallel/mesh.py
        # fsdp_gather_program): executed per dispatch, output dropped
        # with the forward — full-size weights still never exist at
        # rest — but the bucket programs below compile over replicated
        # inputs, collective-free. An in-body constraint gather (the
        # train step's form) splices the all-gathers into the bucket
        # module and XLA's fusion around them drifts the probs by 1 ulp
        # vs the replicated engine, which the serving crc contract
        # forbids. The gather program gets its own ledger site so a
        # swap-induced retrace of IT is flagged like a bucket retrace.
        if mesh is not None:
            from ..parallel.mesh import fsdp_gather_program

            self._gather_prog = fsdp_gather_program(
                self._params,
                mesh,
                note=self.ledger.hook("serving.gather"),
            )
        else:
            self._gather_prog = None
        model = build_classifier(model_cfg)

        def _probs(p, input_ids, attention_mask):
            # Trace-time hook: this Python body runs exactly once per
            # (batch, seq) shape — each execution of the compiled program
            # skips it. The ledger note is the compile counter.
            note_compile((input_ids.shape[0], input_ids.shape[1]))
            logits = model.apply(
                {"params": p}, input_ids, attention_mask, True
            )
            class_probs = jax.nn.softmax(logits, axis=-1)
            # STATIC head-width branch, mirroring eval_counts: K = 2
            # keeps the binary scalar verbatim (bit-identical to the
            # pre-K-class serving path); K > 2 scores P(any attack).
            if int(logits.shape[-1]) == 2:
                score = class_probs[:, 1]
            else:
                score = 1.0 - class_probs[:, 0]
            return score, class_probs

        self._probs = self.ledger.timed("serving.probs", jax.jit(_probs))

    def _place(self, params: Any) -> Any:
        """Device placement honoring the engine's layout: replicated for
        a plain engine, per-leaf ``fsdp_spec`` shardings for a sharded
        one. Shape-deterministic, so every swap lands the new weights on
        the exact layout the warm programs were compiled for."""
        import jax

        if self.mesh is None:
            return jax.device_put(params)
        from ..parallel.mesh import fsdp_tree_shardings

        return jax.device_put(
            params, fsdp_tree_shardings(params, self.mesh)
        )

    @property
    def compile_counts(self) -> dict[tuple[int, int], int]:
        """(batch, seq) -> trace count, straight off the ledger (the
        pre-ledger dict's exact shape; stats() and the compile-count-
        asserted tests read it unchanged)."""
        return self.ledger.compile_counts("serving.probs")

    # ------------------------------------------------------------ versioning
    @property
    def round_id(self) -> int:
        return self._round_id

    def swap(self, params: Any, *, round_id: int) -> None:
        """Adopt a new checkpoint's params (same architecture — shapes are
        unchanged, so the compiled programs are reused as-is; a changed
        architecture needs a new engine, serving/reload.py handles that
        distinction). On a sharded engine the new params land on the SAME
        per-leaf shard layout the warm programs were compiled against
        (``fsdp_spec`` is shape-deterministic), so a rolling reload never
        retraces a bucket — the ledger flags it if one ever does."""
        new = self._place(params)
        with self._lock:
            self._params = new
            self._round_id = int(round_id)

    def snapshot(self) -> tuple[Any, int]:
        with self._lock:
            return self._params, self._round_id

    # --------------------------------------------------------------- scoring
    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits ``n`` (callers cap n at max bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} exceeds the largest bucket {self.buckets[-1]}"
        )

    def warmup(self) -> None:
        """Pay every bucket's compilation before traffic arrives, then
        mark the site warm: any later novel shape is a flagged recompile
        (obs/profile.py — the bucket ladder makes one impossible unless
        the padding discipline breaks)."""
        for b in self.buckets:
            self.score(
                np.full((b, self.seq_len), self.pad_id, np.int32),
                np.zeros((b, self.seq_len), np.int32),
            )
        # Freeze every site — the bucket ladder AND (sharded engines)
        # the gather program, whose retrace after a swap would be just
        # as much a served-latency cliff as a bucket retrace.
        self.ledger.mark_warm()
        log.info(
            f"[SERVE] warmed {len(self.buckets)} bucket programs "
            f"(batch in {self.buckets}, seq {self.seq_len})"
        )

    def score(
        self, input_ids: np.ndarray, attention_mask: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, int, int]:
        """Score ``[n, seq]`` rows -> (float32 probs [n], per-class
        softmax [n, K], bucket, round).

        Pads up to the bucket with PAD rows exactly as
        ``pad_split_to_batch`` does for eval (pad_id ids, zero mask) and
        slices the pad rows back off — per-row results are independent of
        sibling rows, so the padded program returns the same bits the
        eval pipeline computes."""
        n = int(input_ids.shape[0])
        bucket = self.bucket_for(n)
        if input_ids.shape[1] != self.seq_len:
            raise ValueError(
                f"rows have seq {input_ids.shape[1]}, engine expects "
                f"{self.seq_len}"
            )
        # Strided step attribution (obs/profile.py): a sampled dispatch
        # splits host pad-prep / dispatch / device-execute; unsampled
        # dispatches (and profiling off) run the bare path. Re-checked
        # lazily (one lock-free int read when off) because the CLI
        # installs the stride after the engine is built.
        prof = self.step_profiler
        if prof is None and profile_stride() > 0:
            prof = self.step_profiler = maybe_step_profiler("score")
        sampled = prof.tick() if prof is not None else False
        t0 = prof.clock() if sampled else 0.0
        if n < bucket:
            pad_ids = np.full(
                (bucket - n, self.seq_len), self.pad_id, np.int32
            )
            pad_mask = np.zeros((bucket - n, self.seq_len), np.int32)
            input_ids = np.concatenate([input_ids, pad_ids])
            attention_mask = np.concatenate([attention_mask, pad_mask])
        params, round_id = self.snapshot()
        if self._gather_prog is not None:
            # Gather AT USE: reconstruct full-size weights for this
            # dispatch only — ``params`` here is a local that dies with
            # the call, so the gathered tree is freed after the forward.
            params = self._gather_prog(params)
        ids = np.ascontiguousarray(input_ids, np.int32)
        mask = np.ascontiguousarray(attention_mask, np.int32)
        if sampled:
            prof.note_host(prof.clock() - t0)
            t1 = prof.clock()
            probs, class_probs = self._probs(params, ids, mask)
            prof.note_dispatch(prof.clock() - t1)
            prof.fence(probs)
        else:
            probs, class_probs = self._probs(params, ids, mask)
        return (
            np.asarray(probs)[:n],
            np.asarray(class_probs)[:n],
            bucket,
            round_id,
        )
