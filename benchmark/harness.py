"""What every driver shares: the run's context, the benchmark's own spans,
the compile meter, seeded weights and data, and the comparison with the
plain reference.

From the program a run takes only the system under test (its public entry
points) and its counters; clocks, spans and arithmetic are the benchmark's.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import time
from typing import Any

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PKG = (
    "detecting_cyber_attacks_with_distilled_large_language_models"
    "_in_distributed_networks_tpu"
)


def pkg(module: str):
    """A module of the program under test."""
    return importlib.import_module(f"{PKG}.{module}")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ spans
class Recorder:
    """The benchmark's own spans, on the host's monotonic clock. Each span
    is also a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so a
    traced run finds the same spans on the profiler's clock, beside the
    device's operations. Kept in memory; nothing is written while a window
    runs."""

    def __init__(self) -> None:
        self.spans: list[dict] = []
        self.phase = "setup"
        self.data: dict[str, Any] = {}  # what readers read besides spans

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax

        rec = {"name": name, "phase": self.phase, **attrs}
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            rec["t0"] = time.perf_counter()
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()
                self.spans.append(rec)

    def select(self, name: str, phase: str = "window") -> list[dict]:
        return [s for s in self.spans if s["name"] == name and s["phase"] == phase]

    def seconds(self, name: str, phase: str = "window") -> np.ndarray:
        return np.array([s["t1"] - s["t0"] for s in self.select(name, phase)], np.float64)

    def total(self, name: str, key: str, phase: str = "window") -> float:
        return float(sum(s.get(key, 0) for s in self.select(name, phase)))

    def counters(self, name: str, phase: str = "window") -> dict[str, float]:
        """Every numeric attribute of the spans ``name``, summed: what a
        driver counted on them (``rows``, ``steps``, ...)."""
        out: dict[str, float] = {}
        for s in self.select(name, phase):
            for key, x in s.items():
                if key not in ("t0", "t1") and isinstance(x, (int, float)) and not isinstance(x, bool):
                    out[key] = out.get(key, 0.0) + x
        return out


#: Untraced whole rounds a window holds at the least, however short.
MIN_ROUNDS = 4


def total_rate(rec: "Recorder", name: str, counter: str, phase: str = "window") -> float:
    """``counter`` summed over the spans ``name`` per second inside them:
    total over total, so that a stalled or periodically slow call shows."""
    return rec.total(name, counter, phase) / float(rec.seconds(name, phase).sum())


def say_rounds(ctx: "Context", parts: tuple) -> None:
    """Per-round detail on an earlier line: each part's seconds in every
    untraced round of the window, and the totals a median would hide."""
    if ctx.rehearsal:
        return
    for part in parts:
        secs = ctx.rec.seconds(part)
        if len(secs):
            ctx.say(
                f"  {part}: median {np.median(secs):.4f} s, mean {secs.mean():.4f} s, max "
                f"{secs.max():.4f} s over {len(secs)} span(s): "
                + " ".join(f"{x:.3f}" for x in secs)
            )


def run_rounds(ctx: "Context", one_round, *, warm_rounds: int, **warm_kwargs) -> list[dict]:
    """What the training drivers share: ``warm_rounds`` whole rounds of
    set-up (``one_round(r, **warm_kwargs)``), then the window: in a traced
    run the profiled rounds first (slower under the profiler, and left out
    of the host metrics), then whole rounds until the window ends. Returns
    the window's round records."""
    with ctx.rec.span("warm_rounds"):
        for r in range(warm_rounds):
            warm = one_round(r, **warm_kwargs)
    ctx.say(f"{warm_rounds} warm-up round(s) done: loss {warm['loss_mean']:.4f}")
    trace_rounds = int(ctx.cell.get("trace", {}).get("rounds", 1)) if ctx.trace else 0
    rounds: list[dict] = []
    t0 = ctx.begin_window()
    deadline = t0 + ctx.seconds
    r = warm_rounds
    if trace_rounds:
        with ctx.profiler():
            with ctx.rec.span("traced"):
                for _ in range(trace_rounds):
                    rec = one_round(r)
                    rec["traced"] = True
                    rounds.append(rec)
                    r += 1
    while time.perf_counter() < deadline or len(rounds) - trace_rounds < MIN_ROUNDS:
        rounds.append(one_round(r))
        r += 1
    ctx.end_window(t0)
    retag_traced(ctx.rec, rounds)
    return rounds


def round_results(ctx: "Context", rounds: list[dict], parts: tuple) -> dict:
    """The training cells' result: rounds attempted and failed (a
    non-finite loss fails), the rows through ``fit`` over the seconds inside
    it (total over total) and the median round, with every part's seconds
    on an earlier line."""
    failed = sum(1 for x in rounds if not x["losses_finite"])
    if not ctx.compare("rounds_nonfinite", failed, 0):
        ctx.fail(f"{failed} round(s) had a non-finite loss")
    say_rounds(ctx, parts)
    return {
        "attempted": len(rounds),
        "failed": failed,
        "end_to_end": {
            "train_samples_per_s": total_rate(ctx.rec, "fit", "rows"),
            "round_s": float(np.median(ctx.rec.seconds("round"))),
        },
    }


def retag_traced(rec: Recorder, rounds: list[dict]) -> None:
    """Spans of the rounds that ran under the profiler leave the window's
    statistics (tracing slows the host) and keep their own phase."""
    traced = [x for x in rounds if x.get("traced")]
    for span in rec.spans:
        if span["phase"] == "window" and any(
            x["t0"] <= span["t0"] and span["t1"] <= x["t1"] for x in traced
        ):
            span["phase"] = "traced"


class CompileMeter:
    """Totals of JAX's own monitoring events, per phase of the run: seconds
    inside backend compilation (a persistent-cache retrieval counts as its,
    short, compile), compilations, and persistent-cache hits and misses.
    The benchmark's copy of ``chip_smoke.py::CompileMeter``."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self, recorder: Recorder) -> None:
        import jax

        self.recorder = recorder
        self.by_phase: dict[str, dict[str, float]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _bump(self, key: str, by: float) -> None:
        phase = self.by_phase.setdefault(self.recorder.phase, {})
        phase[key] = phase.get(key, 0.0) + by

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._bump("backend_compile_s", duration)
            self._bump("compiles", 1)

    def _event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self._bump(key, 1)

    def get(self, phase: str, key: str) -> float:
        return float(self.by_phase.get(phase, {}).get(key, 0.0))


class GcWatch:
    """Pauses of the interpreter's garbage collector in this process, per
    phase of the run: a full collection over the heap the model stack
    builds stops every thread (the server's readers, the fit loop) for tens
    of milliseconds. An observer only: it changes nothing the collector
    does."""

    def __init__(self, recorder: Recorder) -> None:
        import gc

        self.recorder = recorder
        self.pauses: list[tuple[str, int, float]] = []  # phase, generation, seconds
        self._t0 = 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(
                (self.recorder.phase, int(info["generation"]), time.perf_counter() - self._t0)
            )

    def report(self, phase: str = "window") -> str:
        full = [s for p, g, s in self.pauses if p == phase and g == 2]
        rest = [s for p, g, s in self.pauses if p == phase and g < 2]
        return (
            f"garbage collector in the {phase}: {len(full)} full collection(s), "
            f"{sum(full) * 1e3:.1f} ms in all, longest {max(full, default=0.0) * 1e3:.1f} ms; "
            f"{len(rest)} young collection(s), {sum(rest) * 1e3:.1f} ms in all"
        )


# ---------------------------------------------------------------- context
@dataclasses.dataclass
class Context:
    """One run: the cell's data files, the arguments, the clocks."""

    workload: str
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    chips: int
    config: dict  # benchmark/configs/<config>.json
    traffic: dict  # benchmark/traffic/<mix>.json
    cell: dict  # benchmark/cells/<cell>.json
    t_start: float  # perf_counter at process start
    workdir: str  # under TMPDIR; removed when the run ends
    rec: Recorder
    meter: CompileMeter
    devices: list
    gc_watch: GcWatch | None = None
    setup_s: float | None = None
    window_s: float | None = None
    trace_path: str | None = None
    memory: tuple = (0, {})  # peak bytes on the fullest chip, every chip's stats
    problems: list = dataclasses.field(default_factory=list)
    compared: dict = dataclasses.field(default_factory=dict)  # name -> [number, limit]

    def compare(self, name: str, value: float, limit: float, *, least: bool = False) -> bool:
        """One number that decides ``correct``, kept beside its limit for
        the result line; ``least``: the limit is a floor, not a ceiling.
        Returns whether it holds (a NaN never does)."""
        self.compared[name] = [float(value), float(limit)]
        return bool(value >= limit) if least else bool(value <= limit)

    def fail(self, problem: str) -> None:
        """A check that decides ``correct`` did not hold. The run goes on
        and reports what it measured, with ``correct`` false."""
        self.problems.append(problem)
        self.say(f"CHECK FAILED: {problem}")

    def say(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    def num(self, x: float, unit: str = "") -> str:
        """A rehearsal runs on the CPU: its timings are not device numbers
        and are printed under no name."""
        return "withheld" if self.rehearsal else f"{x:.4f}{unit}"

    @property
    def family(self):
        """The module ``benchmark/families/<family>.py`` that the
        configuration names: everything the yardstick knows of the model's
        architecture."""
        from . import families

        return families.load(self.config)

    @property
    def model(self) -> dict:
        """The model as it is run; a rehearsal swaps in the family's tiny
        model."""
        model = self.config["model"]
        return self.family.tiny(model) if self.rehearsal else model

    def peaks(self) -> dict:
        """The published peaks of the device; a device that is not in
        benchmark/peaks.json is an error. A rehearsal (which prints no
        value) walks the readers with the v5e's row."""
        from . import flops

        return flops.load_peaks("TPU v5 lite" if self.rehearsal else self.devices[0].device_kind)

    def model_config(self):
        """The program's configuration object of the model as it is run."""
        return self.family.model_config(self.model)

    def scaled(self, key: str, tiny: int) -> int:
        """A traffic size; a rehearsal takes the small stand-in."""
        return tiny if self.rehearsal else int(self.traffic[key])

    def begin_window(self, at: float | None = None) -> float:
        """Set-up ends here (or at the time ``at``, a moment away):
        everything is loaded, compiled and warm."""
        now = time.perf_counter() if at is None else at
        self.setup_s = now - self.t_start
        self.rec.phase = "window"
        return now

    def end_window(self, t0: float) -> None:
        """The window is over: what follows (the comparison with the
        reference) is the benchmark's own work and is in no number, the
        memory peak included, which is read here."""
        self.window_s = time.perf_counter() - t0
        self.rec.phase = "after"
        self.memory = memory_peak_bytes(self.devices)

    @contextlib.contextmanager
    def profiler(self):
        """Trace what runs inside into a directory under the run's work
        directory (outside the checkout); ``trace_path`` names the
        ``.xplane.pb`` afterwards. Python-level tracing stays off: it slows
        the host it measures."""
        import glob

        import jax

        out = os.path.join(self.workdir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
            self.trace_path = found[0] if found else None


# ------------------------------------------------------- weights and data
def init_params_on_device(family, model_cfg, seed: int, prng_impl: str):
    """The model's weights, random from the seed, made on the device in one
    jitted call in the type they are trained and served in."""
    import jax

    return jax.jit(lambda k: family.init_params(model_cfg, k))(
        jax.random.key(seed, impl=prng_impl)
    )


def tokenised_flows(ctx: Context, n: int, seed: int, tok):
    """``n`` seeded flows rendered, tokenised once by the program's
    tokenizer, as (texts, TokenizedSplit). The time inside
    ``batch_encode`` is a span (``tokenise``)."""
    from . import flows

    with ctx.rec.span("make_flows"):
        texts, labels = flows.make_flows(n, seed)
    with ctx.rec.span("tokenise", flows=n):
        enc = tok.batch_encode(texts, max_len=ctx.model["max_len"])
    split = pkg("data.pipeline").TokenizedSplit(
        enc["input_ids"], enc["attention_mask"], labels.astype(np.int32)
    )
    return texts, split


# ------------------------------------------------------------ correctness
#: What is compared, against which plain reference and within which limits
#: is the family's (``benchmark/families/<family>.py``: ``program``,
#: ``reference``, ``TOLERANCES`` with their evidence, ``logit_scale``).


def _rel_l2(err: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per sequence: |err| / |ref| over tokens and dimensions."""
    axes = tuple(range(1, err.ndim))
    return np.sqrt((err**2).sum(axes)) / np.maximum(np.sqrt((ref**2).sum(axes)), 1e-30)


def compare_hidden(got: np.ndarray, want: np.ndarray, mask: np.ndarray) -> dict:
    """``got`` against ``want`` (``[B, L, D]``) over each sequence's real
    tokens: the worst relative L2 error, and how well the comparison binds
    the inputs: the least ratio of a sequence's distance to the nearest
    OTHER sequence's reference over its own error. Over 2, every ``got`` is
    nearer to its own reference than to any other's."""
    w = mask[..., None].astype(np.float64)
    got, want = got.astype(np.float64) * w, want.astype(np.float64) * w
    err = _rel_l2(got - want, want)
    apart = np.array([
        min(_rel_l2(want[j][None] - want[i][None], want[i][None])[0] for j in range(len(want)) if j != i)
        for i in range(len(want))
    ])
    return {
        "hidden_rel_err": float(err.max()), "nearest_other": float(apart.min()),
        "binding": float((apart / np.maximum(err, 1e-30)).min()),
    }


def check_model(
    ctx: Context, params, split, *, what: str, key: str, bind: bool, n: int = 16
) -> dict:
    """The program's model (the family's ``program``: the program's own
    classes, jitted, as its eval path calls them) on the weights ``params``
    against the family's plain float32 ``reference`` on ``n`` seeded
    sequences, within the family's ``TOLERANCES``: the last hidden states
    within ``hidden_rel``, the logits within ``logit_rel`` of the logit
    scale and, with ``bind``, every sequence bound to its input. ``key``
    names the numbers compared in the result line."""
    import jax

    family = ctx.family
    tol = family.TOLERANCES
    rng = np.random.default_rng(ctx.seed + 1009)
    idx = rng.choice(len(split), size=min(n, len(split)), replace=False)
    ids, mask = split.input_ids[idx], split.attention_mask[idx]
    program = jax.jit(family.program(ctx.model_config()))
    hidden, logits = (np.asarray(x, np.float32) for x in program(params, ids, mask))
    want_hidden, want = (
        np.asarray(x, np.float32) for x in family.reference(params, ids, mask, ctx.model)
    )
    if not (np.isfinite(hidden).all() and np.isfinite(logits).all()):
        ctx.fail(f"{what}: non-finite hidden states or logits")
        return {}
    out = compare_hidden(hidden, want_hidden, mask)
    out["logit_rel_err"] = float(np.abs(logits - want).max()) / family.logit_scale(params, want)
    out.update(tolerance_rel=tol["hidden_rel"], sequences=int(len(idx)))
    bound = f"limit {tol['binding']:g}" if bind else "no limit on trained weights"
    ctx.say(
        f"correct/{what}: program vs float32 reference on {len(idx)} sequences: last hidden "
        f"states differ by at most {100 * out['hidden_rel_err']:.3f}% (relative L2 over a "
        f"sequence's tokens; limit {100 * tol['hidden_rel']:g}%); the nearest other sequence "
        f"lies {100 * out['nearest_other']:.2f}% away, at least {out['binding']:.1f} x the "
        f"error ({bound}); logits differ by at most {100 * out['logit_rel_err']:.3f}% of "
        f"the logit scale (limit {100 * tol['logit_rel']:g}%)"
    )
    if not ctx.compare(f"{key}.hidden_rel", out["hidden_rel_err"], tol["hidden_rel"]):
        ctx.fail(f"{what}: hidden states differ from the reference by {out['hidden_rel_err']:.4f} relative")
    if bind and not ctx.compare(f"{key}.binding", out["binding"], tol["binding"], least=True):
        ctx.fail(f"{what}: the comparison cannot bind the inputs (nearest other sequence at {out['binding']:.2f} x the error)")
    if not ctx.compare(f"{key}.logit_rel", out["logit_rel_err"], tol["logit_rel"]):
        ctx.fail(f"{what}: logits differ from the reference by {out['logit_rel_err']:.4f} of the logit scale")
    return out


def check_trained(ctx: Context, params, split, *, what: str) -> dict:
    """The comparison for a training cell, on two sets of weights: the
    arithmetic on ``params``, which the window ended with, and the
    arithmetic and the binding on the weights the run started from, made
    again from the seed. The binding cannot be asked of trained weights: a
    BERT-large a few hundred steps from a random start has collapsed, its
    reference's hidden states for different flows lie 0.00-0.07% apart (my
    chip runs, PR 22; held-out accuracy 50%), and no comparison of outputs
    binds the inputs of a model that ignores them. The program is the same
    on either weights, and random ones separate flows by 28-60%."""
    out = check_model(ctx, params, split, what=f"{what}, trained", key="trained", bind=False)
    fresh = init_params_on_device(
        ctx.family, ctx.model_config(), ctx.seed, pkg("config").TrainConfig().prng_impl
    )
    out["seed_weights"] = check_model(
        ctx, fresh, split, what=f"{what}, the seed's weights", key="seed", bind=True
    )
    return out


#: On the v5e the allocator counts live arrays (``bytes_in_use``) and the
#: scratch space of the running program (``bytes_reserved``) apart, and
#: both come off ``bytes_limit``: after a BERT-large step at batch 64 it
#: read 4.75 GB in use + 9.91 GB reserved, where the compiler's own
#: ``memory_analysis`` says 4.01 GB of arguments + 10.04 GB of temporaries
#: (my chip run and CPU compile, PR 22). The peak a chip held is their sum.
PEAK_KEYS = ("peak_bytes_in_use", "peak_bytes_reserved")


def memory_peak_bytes(devices) -> tuple[int, dict]:
    """Peak bytes held on the fullest chip (live arrays + program scratch),
    and every chip's stats."""
    per = {}
    for d in devices:
        stats = d.memory_stats() or {}
        per[int(d.id)] = {k: int(v) for k, v in stats.items() if "bytes" in k}
    peak = max((sum(s.get(k, 0) for k in PEAK_KEYS) for s in per.values()), default=0)
    return int(peak), per
