"""Device performance plane: compile ledger, step attribution, watermarks.

The fleet health plane (obs/slo.py + obs/fleet.py) watches processes;
nothing watched the *device* layer — a silent XLA recompile storm, a
host-sync stall, or device-memory creep was invisible until it surfaced
as a worse MFU headline with no attribution. The reference's entire
profiling story is timestamped prints plus tqdm rates (SURVEY.md §5).
This module is the device-side judgment layer, four pieces:

* :class:`CompileLedger` — the serving tier's trace-hook discipline
  (serving/engine.py pioneered it: the Python body of a jitted function
  runs once per traced shape, so a counter inside the body IS a compile
  hook) generalized repo-wide. Every jitted program registers a trace
  hook under a **site** name; the ledger records compiles per
  (site, shape-signature) with trace wall seconds, exports
  ``fedtpu_xla_compiles_total`` / ``fedtpu_xla_recompiles_total`` /
  ``fedtpu_xla_trace_seconds`` on /metrics, emits an ``xla-compile``
  span into the closed vocabulary, and — after :meth:`mark_warm` —
  flags any NEW signature at a known site as a **recompile** event that
  can trip the PR-10 flight recorder (``xla-recompile`` bundles).
* :class:`StepProfiler` — deterministically-strided fenced step timers:
  every Nth step is split into host batch-prep / dispatch /
  device-execute with ``jax.block_until_ready`` fences, observed into
  ``fedtpu_train_step_seconds`` / ``fedtpu_score_step_seconds``
  histograms and stamped as attrs on the existing train-phase spans so
  the PR-4 timeline can render a device-vs-host row. Stride 0 (the
  default) is the zero-overhead path: one attribute check per step,
  no fences, no timer reads, no metric registration.
* **Memory watermarks** — :func:`note_memory` snapshots
  ``device.memory_stats()`` at phase boundaries (post-restore,
  post-first-step, post-round, post-aggregate) into peak-bytes gauges,
  degrading gracefully to "unavailable" on backends that return None
  (the CPU tier-1 lane).
* **Cost-analysis cross-check** — :func:`xla_cost_flops` pulls
  ``compiled.cost_analysis()`` FLOPs for a jitted program so the
  analytic ``train_step_flops`` behind the MFU headline can be pinned
  against what XLA actually built (:data:`FLOPS_RATIO_TOLERANCE`).

``run_profile_session`` drives all four end-to-end (the
implementation behind ``fedtpu obs profile``).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Iterator, Mapping

from .metrics import MetricsRegistry, default_registry
from .trace import annotate

#: XLA-vs-analytic FLOPs ratio bounds tests/test_profile.py pins (see the
#: README "Device profiling" section). XLA's cost model counts the same
#: 2·M·N·K per matmul the analytic model does, but additionally counts
#: elementwise/softmax/optimizer FLOPs the analytic model deliberately
#: excludes, while fusion can eliminate work the analytic model keeps —
#: so the ratio hovers near 1 and [0.5, 2.0] flags a real divergence
#: (wrong model config, a broken backward path, a cost model reading a
#: different program) without flaking on backend differences.
FLOPS_RATIO_TOLERANCE = (0.5, 2.0)

#: Trace/compile wall-time histogram edges: compiles run 10 ms (tiny
#: CPU programs) to minutes (BERT-large on a cold TPU).
TRACE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0, 300.0)

#: Step-phase histogram edges: 100 µs host prep to multi-second steps.
STEP_BUCKETS = (
    1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
)

#: StepProfiler site -> /metrics histogram family (one literal per
#: family, registered from this module only — the obs-metric-once
#: contract). The "wire" site covers the TCP tier's pack/unpack hot
#: loops (comm/client.py streamed-upload leaf encode + streamed-reply
#: leaf decode) — the PR-12 device-plane residual.
_STEP_FAMILIES = {
    "train": "fedtpu_train_step_seconds",
    "score": "fedtpu_score_step_seconds",
    "wire": "fedtpu_wire_step_seconds",
}

#: Per-site phase vocabulary: the train/score sites split a step into
#: host/dispatch/device; the wire site times one leaf's encode or
#: decode as a single "wire" phase (direction comes from the span the
#: attrs land on: wire-upload = pack, wire-reply = unpack).
_SITE_PHASES = {
    "wire": ("wire",),
}


# ------------------------------------------------------------ compile ledger
class _Site:
    """Per-site ledger state (guarded by the owning ledger's lock)."""

    __slots__ = (
        "name", "sigs", "trace_s", "warm", "timed", "fresh", "gen",
        "inflight",
    )

    def __init__(self, name: str):
        self.name = name
        self.sigs: dict[Any, int] = {}  # signature -> trace count
        self.trace_s: dict[Any, float] = {}  # signature -> wall seconds
        self.warm = False
        self.timed = False  # a timed() wrapper owns span emission
        self.fresh: list[tuple[Any, bool]] = []  # (sig, recompile) in-flight
        self.gen = 0  # bumps per note — the timed wrapper's cheap check
        self.inflight = 0  # wrapper calls currently executing


class CompileLedger:
    """Compiles per (site, shape-signature), with recompile flagging.

    Two touch points per jitted program:

    * ``note = ledger.hook("tier.step")`` returns the trace-time
      callable; the jitted body calls ``note(signature)`` — it executes
      once per traced shape and never at dispatch time, so the hot path
      pays nothing.
    * ``fn = ledger.timed("tier.step", jax.jit(body))`` wraps the
      jitted callable so the wall seconds of any call during which a
      trace fired are attributed to that compile (trace+compile happen
      inside the first dispatch). The wrapper costs two monotonic reads,
      one plain int compare and one ``dispatch/<site>`` profiler
      annotation per call; it exposes the jitted original as
      ``__wrapped__`` (``xla_cost_flops`` needs ``lower``).
      ``ledger.jit("tier.step", body, **jit_kwargs)`` is the pair in one
      call, with the program named after the site.

    ``mark_warm()`` freezes the signature set: a NEW signature at a
    warm site afterwards is a *recompile* — counted, logged, listed in
    :meth:`recompiles`, and offered to the installed flight recorder
    (``maybe_dump("xla-recompile")``, rate-limited by the recorder).
    This is serving/engine.py's compile-count-asserted discipline made
    repo-wide.

    Thread-safe; the default process-wide instance is
    :func:`default_ledger` (serving engines hold private instances so
    per-engine ``compile_counts`` stay per-engine while the /metrics
    families — get-or-create on the shared registry — stay process
    totals).
    """

    def __init__(self, *, registry: MetricsRegistry | None = None):
        self._lock = threading.Lock()
        self._sites: dict[str, _Site] = {}
        self._reg = registry or default_registry()
        self._events: list[dict] = []  # recompile events, oldest first

    # ------------------------------------------------------------- plumbing
    def _site(self, name: str) -> _Site:
        site = self._sites.get(name)
        if site is None:
            site = self._sites.setdefault(name, _Site(str(name)))
        return site

    def _metrics(self, site: str):
        return (
            self._reg.counter(
                "fedtpu_xla_compiles_total",
                help="XLA traces/compiles per jitted site",
                labels={"site": site},
            ),
            self._reg.counter(
                "fedtpu_xla_recompiles_total",
                help="new shape signatures traced at a warm site",
                labels={"site": site},
            ),
            self._reg.histogram(
                "fedtpu_xla_trace_seconds",
                help="wall seconds of calls that traced+compiled",
                labels={"site": site},
                buckets=TRACE_BUCKETS,
            ),
        )

    # ------------------------------------------------------------ recording
    def hook(self, site: str) -> Callable[[Any], None]:
        """The trace-time callable for ``site`` — call it inside the
        jitted body with a hashable shape signature."""
        name = str(site)

        def note(signature: Any) -> None:
            self.note(name, signature)

        return note

    def note(self, site: str, signature: Any) -> None:
        """Record one trace of ``signature`` at ``site`` (called from
        inside a traced body — i.e. exactly once per compilation)."""
        emit_span = False
        recompile = False
        with self._lock:
            s = self._site(site)
            count = s.sigs.get(signature, 0) + 1
            s.sigs[signature] = count
            s.gen += 1
            recompile = s.warm and count == 1
            if recompile:
                self._events.append(
                    {
                        "site": site,
                        "signature": signature,
                        "ts": time.time(),
                    }
                )
            # Defer span/time attribution to the timed wrapper ONLY
            # when one is actually in flight: a trace fired outside it
            # (xla_cost_flops lowering the unwrapped jit, a direct AOT
            # path) would otherwise sit stale in `fresh` and corrupt
            # the NEXT attributed compile's wall-second share.
            deferred = s.timed and s.inflight > 0
            if deferred:
                s.fresh.append((signature, recompile))
            emit_span = not deferred
        compiles, recompiles, _hist = self._metrics(site)
        compiles.inc()
        if recompile:
            recompiles.inc()
            self._flag_recompile(site, signature)
        if emit_span:
            # Untimed site: the span still lands (dur unknowable from
            # trace time alone); a timed() wrapper emits it instead,
            # with the measured wall seconds.
            self._emit_span(site, signature, 0.0, recompile)

    def _flag_recompile(self, site: str, signature: Any) -> None:
        from ..utils.logging import get_logger

        get_logger().warning(
            f"[XLA] recompile at warm site {site!r}: new shape "
            f"signature {signature!r} — a shape leak on a hot path "
            "(bucket the input, or mark_warm later)"
        )
        # Flight recorder (obs/flight.py): a recompile storm mid-traffic
        # is exactly the moment whose surrounding spans an operator
        # wants preserved. maybe_dump rate-limits per reason; a dump
        # failure must never break the training/serving path.
        from .flight import get_global_recorder

        recorder = get_global_recorder()
        if recorder is not None:
            try:
                recorder.maybe_dump(
                    "xla-recompile",
                    extra={"site": site, "signature": repr(signature)},
                )
            except OSError:
                pass

    def _emit_span(
        self, site: str, signature: Any, dur_s: float, recompile: bool
    ) -> None:
        from .trace import get_global_tracer

        tracer = get_global_tracer()
        if tracer is None:
            return
        tracer.record(
            "xla-compile",
            t_start=time.time() - dur_s,
            dur_s=dur_s,
            site=site,
            signature=repr(signature),
            recompile=True if recompile else None,
        )

    def jit(self, site: str, fn: Callable, **jit_kwargs: Any) -> Callable:
        """``jax.jit(fn, **jit_kwargs)`` as the program ``jit_<site>``
        (``.`` as ``_``: ``fed.packed_step`` -> ``jit_fed_packed_step``),
        wrapped by :meth:`timed`. ``fn`` is renamed in place (pass the
        site's own closure), so a profile's ``XLA Modules`` line and every
        op under it carry the ledger's site, whichever variant of the body
        (plain, FedProx, FSDP) was built."""
        import jax

        fn.__name__ = fn.__qualname__ = str(site).replace(".", "_")
        return self.timed(site, jax.jit(fn, **jit_kwargs))

    def timed(self, site: str, fn: Callable) -> Callable:
        """Wrap a jitted callable: wall seconds of any call during which
        ``site`` traced are attributed as that compile's trace time. Every
        call is also the profiler annotation ``dispatch/<site>``
        (obs/trace.py ``annotate``): one program launch, host side."""
        name = str(site)
        label = f"dispatch/{name}"
        with self._lock:
            self._site(name).timed = True

        def wrapper(*args, **kwargs):
            s = self._sites[name]
            gen0 = s.gen
            # Plain GIL-atomic counter (no lock on the hot path): note()
            # only defers to the wrapper while a call is in flight.
            s.inflight += 1
            t0 = time.monotonic()
            try:
                with annotate(label):
                    out = fn(*args, **kwargs)
            finally:
                s.inflight -= 1
            if s.gen != gen0:  # a trace fired during this call
                self._attribute(s, time.monotonic() - t0)
            return out

        wrapper.__wrapped__ = fn
        return wrapper

    def _attribute(self, s: _Site, dt: float) -> None:
        with self._lock:
            fresh, s.fresh = s.fresh, []
        if not fresh:
            return
        share = dt / len(fresh)
        _c, _r, hist = self._metrics(s.name)
        with self._lock:
            for sig, _rec in fresh:
                s.trace_s[sig] = s.trace_s.get(sig, 0.0) + share
        for sig, rec in fresh:
            hist.observe(share)
            self._emit_span(s.name, sig, share, rec)

    # ------------------------------------------------------------- lifecycle
    def mark_warm(self, site: str | None = None) -> None:
        """Freeze the signature set (all sites, or one): any new
        signature afterwards is flagged as a recompile. Call after the
        warmup phase — the serving engine does it from ``warmup()``."""
        with self._lock:
            targets = (
                [self._site(site)] if site is not None
                else list(self._sites.values())
            )
            for s in targets:
                s.warm = True

    # ------------------------------------------------------------- reporting
    def compile_counts(self, site: str) -> dict[Any, int]:
        """signature -> trace count for one site (the serving engine's
        ``compile_counts`` contract rides this verbatim)."""
        with self._lock:
            s = self._sites.get(site)
            return dict(s.sigs) if s is not None else {}

    def recompiles(self, site: str | None = None) -> list[dict]:
        """Flagged recompile events, oldest first — exactly one per new
        signature at a warm site."""
        with self._lock:
            return [
                dict(e)
                for e in self._events
                if site is None or e["site"] == site
            ]

    def report(self) -> dict:
        """``{site: {compiles, signatures, trace_s, warm}}`` + events."""
        with self._lock:
            sites = {
                name: {
                    "compiles": sum(s.sigs.values()),
                    "signatures": len(s.sigs),
                    "trace_s": round(sum(s.trace_s.values()), 4),
                    "warm": s.warm,
                }
                for name, s in sorted(self._sites.items())
            }
            return {
                "sites": sites,
                "compile_count": sum(
                    s["compiles"] for s in sites.values()
                ),
                "recompiles": [dict(e) for e in self._events],
            }


_LEDGER_LOCK = threading.Lock()
_LEDGER: CompileLedger | None = None


def default_ledger() -> CompileLedger:
    """The process-wide ledger every jitted tier notes into (the
    default-registry pattern: no plumbing to share one /metrics view)."""
    global _LEDGER
    with _LEDGER_LOCK:
        if _LEDGER is None:
            _LEDGER = CompileLedger()
        return _LEDGER


# --------------------------------------------------------- step attribution
def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


class StepProfiler:
    """Deterministically-strided fenced step timers.

    ``tick()`` advances the step counter and answers "is this step
    sampled" (step k is sampled iff ``k % stride == 0`` — a plain
    counter stride, no RNG, so reruns sample identically and the
    `fedtpu check` determinism discipline is untouched). On sampled
    steps the caller brackets the three phases:

    * ``note_host(dt)`` — input-pipeline work (batch gather/pad),
    * ``note_dispatch(dt)`` — the jitted call's Python return time,
    * ``fence(value)`` — ``jax.block_until_ready`` + the wait recorded
      as device-execute time (``drain(value)`` first empties the async
      queue so the sampled step measures itself, not its backlog).

    Unsampled steps — and every step at stride 0, the default — pay one
    attribute read. Stride 0 additionally registers nothing on the
    metrics registry.
    """

    PHASES = ("host", "dispatch", "device")

    def __init__(
        self,
        stride: int,
        *,
        site: str = "train",
        registry: MetricsRegistry | None = None,
        max_samples: int = 4096,
    ):
        self.stride = int(stride)
        self.enabled = self.stride > 0
        self.site = str(site)
        # Per-site phase vocabulary (the wire site has one phase; the
        # step sites keep the host/dispatch/device split).
        self.phases: tuple[str, ...] = _SITE_PHASES.get(self.site, self.PHASES)
        self._n = 0
        self._lock = threading.Lock()
        self._samples: dict[str, list[float]] = {p: [] for p in self.phases}
        self._max_samples = int(max_samples)
        self._hists = None
        if self.enabled:
            family = _STEP_FAMILIES.get(self.site)
            if family is not None:
                reg = registry or default_registry()
                self._hists = {
                    p: reg.histogram(
                        family,
                        help="sampled step seconds by phase",
                        labels={"phase": p},
                        buckets=STEP_BUCKETS,
                    )
                    for p in self.phases
                }

    # ------------------------------------------------------------- sampling
    def tick(self) -> bool:
        """Advance the step counter; True when THIS step is sampled."""
        if not self.enabled:
            return False
        n = self._n
        self._n = n + 1
        return n % self.stride == 0

    def clock(self) -> float:
        return time.monotonic()

    def drain(self, value: Any) -> None:
        """Fence the async dispatch queue BEFORE timing a sampled step,
        so the device-execute measurement is this step's own work and
        not the backlog of the unsampled steps before it."""
        if value is not None:
            import jax

            jax.block_until_ready(value)

    def _note(self, phase: str, dt: float) -> None:
        with self._lock:
            vals = self._samples[phase]
            if len(vals) < self._max_samples:
                vals.append(float(dt))
        if self._hists is not None:
            self._hists[phase].observe(float(dt))

    def note(self, phase: str, dt: float) -> None:
        """Record one sampled duration for a named phase — the generic
        entry for sites whose phases aren't the host/dispatch/device
        split (the wire pack/unpack loops note ``"wire"``)."""
        if phase not in self._samples:
            raise ValueError(
                f"unknown phase {phase!r} for site {self.site!r} "
                f"(have {self.phases})"
            )
        self._note(phase, dt)

    def note_host(self, dt: float) -> None:
        self._note("host", dt)

    def note_dispatch(self, dt: float) -> None:
        self._note("dispatch", dt)

    def fence(self, value: Any) -> None:
        """Block until ``value`` is ready; the wait is device time."""
        import jax

        t0 = time.monotonic()
        jax.block_until_ready(value)
        self._note("device", time.monotonic() - t0)

    # ------------------------------------------------------------ reporting
    def begin_window(self) -> None:
        """Start a fresh reporting window (one fit/round): the sample
        lists are CLEARED, so summary/span_attrs always describe the
        current window and a long-lived daemon can never fill the
        sample bound once and silently stop reporting (the histograms
        above carry the cumulative record)."""
        with self._lock:
            for p in self.phases:
                self._samples[p].clear()

    def _phase_stats(self, vals: list[float]) -> dict | None:
        if not vals:
            return None
        v = sorted(vals)
        return {
            "n": len(v),
            "p50": _percentile(v, 0.50),
            "p95": _percentile(v, 0.95),
        }

    def summary(self) -> dict:
        """{phase: {n, p50, p95}} in seconds over the current window
        (empty when no samples)."""
        with self._lock:
            out = {}
            for p in self.phases:
                st = self._phase_stats(self._samples[p])
                if st is not None:
                    out[p] = st
            return out

    def span_attrs(self) -> dict:
        """Flat span attrs (milliseconds) for stamping on the existing
        train-phase spans — the timeline's device-vs-host row."""
        s = self.summary()
        out: dict[str, Any] = {}
        for p, st in s.items():
            out[f"step_{p}_ms_p50"] = round(st["p50"] * 1e3, 3)
            out[f"step_{p}_ms_p95"] = round(st["p95"] * 1e3, 3)
        if s:
            out["step_sampled"] = max(st["n"] for st in s.values())
        return out


_STRIDE_LOCK = threading.Lock()
_PROFILE_STRIDE = 0


def set_profile_stride(stride: int) -> None:
    """Install the process-wide step-profiling stride (0 = off, the
    default). The CLI calls this from ``--profile-stride`` /
    ObsConfig.profile_stride BEFORE trainers/engines are built — they
    read it once at construction."""
    global _PROFILE_STRIDE
    with _STRIDE_LOCK:
        _PROFILE_STRIDE = max(0, int(stride))


def profile_stride() -> int:
    # Lock-free read (a GIL-atomic int load): the scoring hot path asks
    # per call and must not pay a lock acquire for "off".
    return _PROFILE_STRIDE


def maybe_step_profiler(site: str) -> StepProfiler | None:
    """A StepProfiler when profiling is armed process-wide, else None —
    the construction-time hook trainers and engines call. None keeps
    the hot loops on the literal pre-profiling code path."""
    stride = profile_stride()
    if stride <= 0:
        return None
    return StepProfiler(stride, site=site)


# ---------------------------------------------------------- memory watermarks
_MEM_LOCK = threading.Lock()
_MEM_REPORT: dict[str, dict] = {}


def device_memory_stats(device: Any = None) -> dict | None:
    """``device.memory_stats()`` with every backend quirk absorbed:
    returns a plain dict, or None when the backend has no stats (CPU),
    returns None, or raises — the graceful-"unavailable" contract the
    CPU tier-1 lane depends on. Never IMPORTS jax: a host-only daemon
    (the TCP aggregation server) that calls :func:`note_memory` at a
    phase boundary must not pay a backend init for an unavailable
    answer — no jax in ``sys.modules`` means no device work happened
    in this process, so "unavailable" is already correct."""
    try:
        if device is None:
            import sys

            jax = sys.modules.get("jax")
            if jax is None:
                return None
            device = jax.local_devices()[0]
        stats_fn = getattr(device, "memory_stats", None)
        if stats_fn is None:
            return None
        stats = stats_fn()
    except Exception:
        return None
    if not stats:
        return None
    return dict(stats)


def note_memory(
    phase: str,
    *,
    device: Any = None,
    registry: MetricsRegistry | None = None,
) -> dict | None:
    """Snapshot device memory at a phase boundary (post-restore /
    post-first-step / post-round / post-aggregate). Returns the
    snapshot, or None when the backend exposes no stats — the phase is
    still recorded as unavailable so ``memory_report`` shows it was
    visited."""
    stats = device_memory_stats(device)
    phase = str(phase)
    if stats is None:
        with _MEM_LOCK:
            _MEM_REPORT.setdefault(phase, {"available": False})
        return None
    in_use = float(stats.get("bytes_in_use", 0.0))
    peak = float(stats.get("peak_bytes_in_use", in_use))
    snap = {
        "available": True,
        "bytes_in_use": in_use,
        "peak_bytes": peak,
        "ts": time.time(),
    }
    with _MEM_LOCK:
        prev = _MEM_REPORT.get(phase)
        if prev is not None and prev.get("available"):
            # Watermark semantics: keep the high-water peak across
            # repeated visits (every round hits post-round).
            snap["peak_bytes"] = max(peak, prev["peak_bytes"])
        _MEM_REPORT[phase] = snap
    reg = registry or default_registry()
    reg.gauge(
        "fedtpu_device_bytes_in_use",
        help="device bytes in use at the last phase-boundary snapshot",
        labels={"phase": phase},
    ).set(in_use)
    reg.gauge(
        "fedtpu_device_peak_bytes",
        help="high-water device bytes across phase-boundary snapshots",
        labels={"phase": phase},
    ).set(snap["peak_bytes"])
    return snap


def memory_report() -> dict[str, dict]:
    """phase -> last snapshot (``{"available": False}`` for phases
    visited on stats-less backends)."""
    with _MEM_LOCK:
        return {k: dict(v) for k, v in _MEM_REPORT.items()}


def peak_device_bytes() -> float:
    """The process high-water mark over every recorded phase (0.0 when
    no backend stats were ever available)."""
    with _MEM_LOCK:
        return max(
            (
                v["peak_bytes"]
                for v in _MEM_REPORT.values()
                if v.get("available")
            ),
            default=0.0,
        )


# ------------------------------------------------------ cost-analysis check
def xla_cost_flops(fn: Callable, *args: Any, **kwargs: Any) -> float | None:
    """FLOPs of the program XLA actually built for ``fn(*args)``, via
    ``lowered.compile().cost_analysis()`` — or None when the callable
    is not lowerable or the backend exposes no cost model. ``fn`` may
    be a :meth:`CompileLedger.timed` wrapper (unwrapped here)."""
    fn = getattr(fn, "__wrapped__", fn)
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        cost = lower(*args, **kwargs).compile().cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, Mapping):
        return None
    flops = cost.get("flops")
    try:
        flops = float(flops)
    except (TypeError, ValueError):
        return None
    return flops if flops > 0.0 else None


def flops_ratio_ok(ratio: float | None) -> bool:
    """None (no cost model on this backend) is not a failure; a number
    outside :data:`FLOPS_RATIO_TOLERANCE` is."""
    if ratio is None:
        return True
    lo, hi = FLOPS_RATIO_TOLERANCE
    return lo <= ratio <= hi


# ------------------------------------------------------------- full session
def run_profile_session(
    model_cfg=None,
    train_cfg=None,
    *,
    steps: int = 8,
    batch_size: int = 16,
    stride: int = 1,
    warmup: int = 2,
    capture_dir: str | None = None,
    serving: bool = True,
    seed: int = 0,
) -> dict:
    """One end-to-end pass over the device performance plane: train
    ``steps`` real engine steps with the step profiler armed, snapshot
    memory at the phase boundaries, cross-check analytic vs XLA FLOPs,
    and storm the bucketed serving path asserting zero recompiles.
    The implementation behind ``fedtpu obs profile``;
    ``capture_dir`` wraps ``jax.profiler``
    around the profiled steps (utils/profiling.trace)."""
    import jax
    import numpy as np

    from ..config import ModelConfig, TrainConfig
    from ..train.engine import Trainer
    from ..utils.profiling import trace, train_step_flops

    model_cfg = model_cfg or ModelConfig()
    train_cfg = train_cfg or TrainConfig()
    ledger = default_ledger()
    before = ledger.report()
    events_before = len(before["recompiles"])

    trainer = Trainer(model_cfg, train_cfg)
    # The session drives its own manual step loop below (tick/drain/
    # fence directly) rather than trainer.fit — the fit-loop
    # integration has its own tests.
    prof = StepProfiler(stride, site="train")
    rng = np.random.default_rng(seed)
    L = model_cfg.max_len
    # The batch stays HOST-side: each sampled step times its device_put
    # as the host batch-prep phase (what an input pipeline pays per
    # step), so the session reports all three phases like the fit loops.
    batch = {
        "input_ids": rng.integers(
            0, model_cfg.vocab_size, (batch_size, L)
        ).astype(np.int32),
        "attention_mask": np.ones((batch_size, L), np.int32),
        "labels": rng.integers(0, 2, batch_size).astype(np.int32),
    }
    state = trainer.init_state(seed=seed)
    loss = None
    # Warmup FIRST, through the timed wrapper, so the compile's wall
    # seconds are attributed to the ledger (the cost-analysis lowering
    # below then rides the already-populated trace cache).
    for _ in range(max(1, warmup)):
        state, loss = trainer.train_step(state, batch)
    jax.block_until_ready(loss)
    note_memory("post-first-step")
    # XLA's own FLOPs for the step just compiled (lower+compile never
    # executes, and a donated-buffer state is still lowerable — only
    # avals are read). Before mark_warm: a backend that re-traces here
    # must count a compile, not flag a recompile.
    flops_xla = xla_cost_flops(trainer.train_step, state, batch)
    flops_analytic = train_step_flops(model_cfg, batch_size)
    ratio = (
        flops_xla / flops_analytic
        if flops_xla is not None and flops_analytic > 0
        else None
    )
    # Warm ONLY the site this session just exercised: a blanket
    # mark_warm would freeze sibling sites with zero or partial
    # signature sets and misflag their next legitimate first compile
    # (e.g. a caller tracing a different batch size right after this
    # session) as a shape leak.
    ledger.mark_warm("engine.train_step")

    with trace(capture_dir):
        for _ in range(max(1, steps)):
            if prof.tick():
                prof.drain(loss)
                t_h = prof.clock()
                placed = {k: jax.device_put(v) for k, v in batch.items()}
                prof.note_host(prof.clock() - t_h)
                t_d = prof.clock()
                state, loss = trainer.train_step(state, placed)
                prof.note_dispatch(prof.clock() - t_d)
                prof.fence(loss)
            else:
                state, loss = trainer.train_step(state, batch)
    jax.block_until_ready(loss)
    note_memory("post-round")

    serving_report = None
    if serving:
        serving_report = _serving_bucket_storm(seed=seed)

    after = ledger.report()
    sites = {}
    for name, rec in after["sites"].items():
        prev = before["sites"].get(name)
        compiles = rec["compiles"] - (prev["compiles"] if prev else 0)
        if compiles > 0:
            sites[name] = {
                "compiles": compiles,
                "signatures": rec["signatures"],
                "trace_s": round(
                    rec["trace_s"] - (prev["trace_s"] if prev else 0.0), 4
                ),
            }
    recompiles = after["recompiles"][events_before:]
    report = {
        "sites": sites,
        "compile_count": sum(s["compiles"] for s in sites.values()),
        "recompiles": recompiles,
        "step": prof.summary(),
        "stride": stride,
        "memory": memory_report(),
        "peak_device_bytes": peak_device_bytes(),
        "flops_analytic": flops_analytic,
        "flops_xla": flops_xla,
        "flops_ratio": round(ratio, 4) if ratio is not None else None,
        "flops_ratio_ok": flops_ratio_ok(ratio),
        "flops_tolerance": list(FLOPS_RATIO_TOLERANCE),
        "capture_dir": capture_dir,
    }
    if serving_report is not None:
        report["serving"] = serving_report
    return report


def _serving_bucket_storm(*, seed: int = 0) -> dict:
    """Warm a tiny bucketed ScoreEngine, then storm mixed batch sizes:
    the bucket ladder must absorb every size into an already-compiled
    shape — recompiles asserted 0 (the compile-count discipline the
    serving tests pin, exercised live)."""
    import jax
    import numpy as np

    from ..config import ModelConfig
    from ..models import build_classifier, init_params
    from ..serving.engine import ScoreEngine

    cfg = ModelConfig.tiny()
    eng = ScoreEngine(
        cfg,
        init_params(build_classifier(cfg), cfg, jax.random.key(seed)),
        buckets=(1, 4),
    )
    eng.warmup()  # pays both bucket compiles, then marks the site warm
    rng = np.random.default_rng(seed)
    L = cfg.max_len
    for n in (1, 2, 3, 4, 1, 4, 2):
        ids = rng.integers(0, cfg.vocab_size, (n, L)).astype(np.int32)
        mask = np.ones((n, L), np.int32)
        eng.score(ids, mask)
    counts = eng.compile_counts
    return {
        "compiles": sum(counts.values()),
        "signatures": len(counts),
        "recompiles": len(eng.ledger.recompiles()),
        "buckets": list(eng.buckets),
    }


def render_profile_report(report: dict) -> str:
    """The ``fedtpu obs profile`` human rendering of a session report."""
    out: list[str] = []
    out.append("compile ledger (this session):")
    sites = report.get("sites") or {}
    if sites:
        out.append(
            f"  {'site':<24} {'compiles':>9} {'signatures':>11} "
            f"{'trace_s':>9}"
        )
        for name, s in sorted(sites.items()):
            out.append(
                f"  {name:<24} {s['compiles']:>9} {s['signatures']:>11} "
                f"{s['trace_s']:>9.3f}"
            )
    else:
        out.append("  (no compiles — every program was already warm)")
    rec = report.get("recompiles") or []
    if rec:
        out.append(f"recompiles: {len(rec)} FLAGGED")
        for e in rec:
            out.append(f"  {e['site']}  signature {e['signature']!r}")
    else:
        out.append("recompiles: none")
    step = report.get("step") or {}
    if step:
        out.append(f"step time (stride {report.get('stride')}, sampled):")
        for phase in StepProfiler.PHASES:
            st = step.get(phase)
            if st:
                out.append(
                    f"  {phase:<9} p50 {st['p50'] * 1e3:8.2f}ms  "
                    f"p95 {st['p95'] * 1e3:8.2f}ms  ({st['n']} samples)"
                )
    mem = report.get("memory") or {}
    out.append("memory watermarks:")
    if mem:
        for phase, snap in mem.items():
            if snap.get("available"):
                out.append(
                    f"  {phase:<16} {snap['bytes_in_use'] / 1e6:9.1f} MB "
                    f"in use, peak {snap['peak_bytes'] / 1e6:9.1f} MB"
                )
            else:
                out.append(
                    f"  {phase:<16} unavailable (backend exposes no "
                    "memory_stats)"
                )
    else:
        out.append("  (no snapshots)")
    lo, hi = report.get("flops_tolerance", FLOPS_RATIO_TOLERANCE)
    ratio = report.get("flops_ratio")
    out.append(
        "flops cross-check: analytic "
        f"{report.get('flops_analytic', 0.0):.3g}, xla "
        + (
            f"{report['flops_xla']:.3g}, ratio {ratio}"
            f" (tolerance {lo}-{hi}"
            + (", OK)" if report.get("flops_ratio_ok") else ", BROKEN)")
            if report.get("flops_xla") is not None
            else "unavailable (no cost model on this backend)"
        )
    )
    srv = report.get("serving")
    if srv:
        out.append(
            f"serving bucketed path: {srv['compiles']} compiles over "
            f"buckets {srv['buckets']}, {srv['recompiles']} recompiles"
            + (" (OK)" if srv["recompiles"] == 0 else " (BROKEN)")
        )
    if report.get("capture_dir"):
        out.append(
            f"jax.profiler capture: {report['capture_dir']} "
            "(view with xprof/tensorboard)"
        )
    return "\n".join(out) + "\n"


def profiled_step_iter(
    profiler: "StepProfiler | None", batches: Iterator
) -> Iterator[tuple[Any, bool]]:
    """Yield ``(batch, sampled)`` pairs, timing host batch-prep on the
    sampled steps — the shared loop shim for the engine and federated
    fit loops (profiling off = the bare iterator, zero overhead)."""
    it = iter(batches)
    if profiler is None or not profiler.enabled:
        for batch in it:
            yield batch, False
        return
    while True:
        sampled = profiler.tick()
        t0 = profiler.clock() if sampled else 0.0
        try:
            batch = next(it)
        except StopIteration:
            return
        if sampled:
            profiler.note_host(profiler.clock() - t0)
        yield batch, sampled
