"""Cross-tier observability: tracing, metrics, timelines, fleet health.

Seven pieces (see each module's docstring):

* :mod:`.trace` — round-scoped trace contexts with span ids propagated
  across the TCP wire protocols via an optional meta field; every
  process appends spans to a unified events-JSONL. Beside it, the
  profiler-clock plane: ``annotate(name)`` marks the trainers' phases,
  batches and launches as ``fedtpu:<name>`` events (vocabulary
  ``ANNOTATIONS``) on the ``/host:CPU`` plane of a ``jax.profiler``
  trace (``--profile-dir``, ``fedtpu obs profile --capture``), on the
  clock of the device's operations; with no session it writes nothing.
  And the device plane's names: ``SCOPES``, the ``jax.named_scope``s the
  models, the expert layer and the train steps open around their parts,
  which every compiled instruction carries in its ``op_name`` path.
* :mod:`.metrics` — in-process counters/gauges/histograms exposed over a
  stdlib-HTTP ``/metrics`` endpoint in Prometheus text format, plus the
  machine-readable ``/metrics.json`` twin.
* :mod:`.timeline` — the ``fedtpu obs`` merge/analysis layer: per-round
  timeline tables and Chrome trace-event export.
* :mod:`.slo` — declarative SLOs evaluated as multi-window burn rates
  over metric-snapshot deltas, with fire/clear alert state machines.
* :mod:`.fleet` — the scrape hub behind ``fedtpu obs health|watch``:
  poll every daemon, merge into fleet snapshots, judge the SLOs.
* :mod:`.flight` — the failure flight recorder: bounded in-memory rings
  dumped as postmortem bundles on round failure / eject storm / SLO page.
* :mod:`.profile` — the device performance plane: XLA compile ledger
  (per-site compile/recompile accounting; ``ledger.jit(site, fn)`` names
  the program ``jit_<site>`` so a trace's modules and ops carry the
  site, and every launch is a ``dispatch/<site>`` annotation), strided fenced step-time
  attribution, device-memory watermarks, and the analytic-vs-XLA FLOPs
  cross-check behind ``fedtpu obs profile``.
* :mod:`.sentinel` — the sentinel watch daemon behind ``fedtpu obs
  sentinel``: known-truth canary probes through the live serving chain,
  continuous journal-tailing supervised drift between gates, and a
  long-horizon retention ring with pinned-baseline regression verdicts.
"""

from .flight import (  # noqa: F401
    FlightRecorder,
    get_global_recorder,
    list_bundles,
    load_bundle,
    set_global_recorder,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsServer,
    default_registry,
    maybe_start_metrics_server,
)
from .profile import (  # noqa: F401
    FLOPS_RATIO_TOLERANCE,
    CompileLedger,
    StepProfiler,
    default_ledger,
    device_memory_stats,
    memory_report,
    note_memory,
    profile_stride,
    render_profile_report,
    run_profile_session,
    set_profile_stride,
    xla_cost_flops,
)
from .slo import (  # noqa: F401
    SLO,
    AlertManager,
    default_slos,
    slos_from_spec,
)
from .fleet import (  # noqa: F401
    ScrapeHub,
    Target,
    health_verdict,
    parse_target,
)
from .sentinel import (  # noqa: F401
    CanaryFlow,
    CanaryProber,
    JournalTail,
    RetentionRing,
    Sentinel,
    load_canary_flows,
)
from .timeline import (  # noqa: F401
    chrome_trace,
    export_chrome_trace,
    group_rounds,
    load_spans,
    round_breakdown,
    round_summaries,
    tail_spans,
    timeline_table,
)
from .trace import (  # noqa: F401
    ANNOTATIONS,
    SCHEMA,
    SCOPES,
    SPAN_NAMES,
    TRACE_META_KEY,
    Tracer,
    annotate,
    annotate_iter,
    get_global_tracer,
    get_run_id,
    maybe_span,
    new_trace_id,
    set_global_tracer,
)
