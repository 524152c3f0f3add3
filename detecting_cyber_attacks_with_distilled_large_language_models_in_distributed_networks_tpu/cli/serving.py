"""fedtpu infer-serve — the online scoring service (serving/).

The deployment step after ``predict``: instead of a one-shot CSV pass,
stand up a TCP detector that answers live flow queries through the
dynamic micro-batcher, picks up new federated checkpoints between
batches, and sheds load explicitly when over capacity. ``serve`` remains
the FL *aggregation* server; this is the *inference* server the ROADMAP
north star ("serves heavy traffic") was missing.
"""

from __future__ import annotations

import time

from ..utils.logging import get_logger
from .common import _obs_setup, _resolve_with_pretrained

log = get_logger()


def _parse_buckets(spec: str) -> tuple[int, ...]:
    try:
        buckets = tuple(sorted({int(b) for b in spec.split(",") if b.strip()}))
    except ValueError:
        raise SystemExit(
            f"--buckets {spec!r}: want a comma-separated int list, e.g. "
            "1,8,32,128"
        ) from None
    if not buckets or buckets[0] < 1:
        raise SystemExit(f"--buckets {spec!r}: bucket sizes must be >= 1")
    return buckets


def build_infer_server(args):
    """Everything ``infer-serve`` does before it listens: resolve the
    config, restore the weights, build engine, batcher and watcher.
    Returns the un-started :class:`ScoringServer` and the one-line
    description of the deployment; the caller starts it, serves, and
    closes it (cmd_infer_serve until interrupted; chip_smoke.py for a
    few requests)."""
    from ..data.datasets import get_dataset
    from ..serving import (
        CheckpointWatcher,
        MicroBatcher,
        RegistryWatcher,
        ScoreEngine,
        ScoringServer,
    )
    from ..serving.reload import checkpoint_restorer

    tok, cfg, pretrained = _resolve_with_pretrained(args)
    buckets = _parse_buckets(args.buckets)
    # Sharded scorer (--data-parallel N --fsdp): params live split
    # per-leaf across this host's chips and every bucket program
    # all-gathers them at use — serving a model bigger than one chip.
    # The mesh is built BEFORE the restore so checkpoint leaves scatter
    # straight onto their shards (never one full-size copy per chip).
    mesh = None
    n_dp = int(getattr(args, "data_parallel", None) or 0)
    if getattr(args, "fsdp", None):
        if n_dp < 2:
            raise SystemExit(
                "--fsdp shards the model over the serving mesh: pass "
                "--data-parallel N with N >= 2"
            )
        from ..parallel.mesh import make_host_mesh

        mesh = make_host_mesh(n_dp)
    elif n_dp > 1:
        raise SystemExit(
            "infer-serve uses --data-parallel only for --fsdp sharding "
            "(replicated data-parallel serving is the fleet tier: "
            "`fedtpu fleet --replicas N`)"
        )
    if args.max_queue < buckets[-1]:
        # Validate BEFORE the (slow) checkpoint restore, and as an
        # operator-facing message like every other flag check here.
        raise SystemExit(
            f"--max-queue {args.max_queue} is smaller than the largest "
            f"bucket {buckets[-1]}: the queue could never fill one batch"
        )
    auth_key = None
    if getattr(args, "auth", False):
        from .comm import _auth_key

        auth_key = _auth_key()
        if auth_key is None:
            raise SystemExit(
                "--auth needs the shared secret in the FEDTPU_SECRET env "
                "var (same value on server and every scoring client)"
            )
    registry_dir = getattr(args, "registry_dir", None)
    if registry_dir and cfg.checkpoint_dir:
        raise SystemExit(
            "--registry-dir and --checkpoint-dir are two different reload "
            "sources (eval-gated pointer vs raw latest step); pass one"
        )
    if not registry_dir and not cfg.checkpoint_dir and pretrained is None:
        raise SystemExit(
            "infer-serve needs trained weights: pass --registry-dir (serve "
            "the control plane's PROMOTED artifact, hot-swapped on "
            "promotion), --checkpoint-dir (a local or federated training "
            "checkpoint; hot reload of the latest step) or --hf-dir (a "
            "fine-tuned classifier checkpoint)"
        )
    watcher = None
    if registry_dir:
        from ..registry import ModelRegistry

        # Pointer-following deployment: the initial load AND every swap
        # come from the registry's serving pointer — this process can only
        # ever score with an artifact the eval gate promoted.
        registry = ModelRegistry(registry_dir)
        info = registry.serving_info()
        if info is None:
            raise SystemExit(
                f"registry {registry_dir} has no serving artifact yet — "
                "run `fedtpu controller` (or `fedtpu registry promote`) "
                "to promote one first"
            )
        manifest = registry.manifest(info["artifact"])
        model_cfg = cfg.model
        if manifest.get("model_config"):
            from ..config import ModelConfig

            model_cfg = ModelConfig(**manifest["model_config"])
        if model_cfg.vocab_size != len(tok.vocab):
            raise SystemExit(
                f"serving artifact's model vocab ({model_cfg.vocab_size}) "
                f"!= tokenizer vocab ({len(tok.vocab)}); pass the matching "
                "--hf-dir / vocab"
            )
        params = registry.load_params(info["artifact"])
        round_id = int(manifest.get("round", 0))
        watcher = RegistryWatcher(
            registry, poll_interval_s=args.reload_poll
        )
        watcher.prime(info["artifact"])
        log.info(
            f"[SERVE] serving promoted artifact {info['artifact']} "
            f"(round {round_id}) from registry {registry_dir}"
        )
    elif cfg.checkpoint_dir:
        from ..serving.reload import latest_finalized_step

        # One restore path for the initial load AND every hot reload —
        # the round-id derivation (meta "round", step fallback) must not
        # exist twice and drift.
        restore = checkpoint_restorer(cfg, tok, mesh=mesh)
        step = latest_finalized_step(cfg.checkpoint_dir)
        model_cfg, params, round_id = restore(step)
        watcher = CheckpointWatcher(
            cfg.checkpoint_dir, restore, poll_interval_s=args.reload_poll
        )
        # Prime with the step just restored (never a fresh directory
        # scan): a round finalized between restore and server start must
        # count as NEW on the first poll, not be marked already-seen.
        watcher.prime(step)
    else:
        model_cfg, params, round_id = cfg.model, pretrained, 0
    engine = ScoreEngine(
        model_cfg,
        params,
        pad_id=tok.pad_id,
        buckets=buckets,
        round_id=round_id,
        mesh=mesh,
    )
    if mesh is not None:
        log.info(
            f"[SERVE] sharded scorer: params split over {n_dp} chips "
            "(gathered at use inside each warm bucket program)"
        )
    batcher = MicroBatcher(
        max_batch=buckets[-1],
        max_queue=args.max_queue,
        gather_window_s=args.max_wait_ms / 1e3,
    )
    tracer, _metrics = _obs_setup(
        args, proc="serve", cfg=cfg, metrics_host=args.host
    )
    server = ScoringServer(
        engine,
        tok,
        host=args.host,
        port=args.port,
        spec=get_dataset(cfg.data.dataset),
        threshold=args.threshold,
        batcher=batcher,
        watcher=watcher,
        default_deadline_s=(
            args.default_deadline_ms / 1e3
            if args.default_deadline_ms is not None
            else None
        ),
        metrics_jsonl=getattr(args, "metrics_jsonl", None),
        scored_jsonl=getattr(args, "scored_jsonl", None),
        auth_key=auth_key,
        # The drift contract: serving-score histograms and the promoted
        # artifact's eval reference must bin identically (ControlConfig).
        score_bins=cfg.control.score_bins,
        tracer=tracer,
        # serve-batch span sampling for high-rate streams: --trace-sample
        # overrides the config's obs.trace_sample (both default 1.0).
        trace_sample=(
            args.trace_sample
            if getattr(args, "trace_sample", None) is not None
            else cfg.obs.trace_sample
        ),
    )
    reload_src = (
        "registry pointer"
        if registry_dir
        else ("checkpoint dir" if cfg.checkpoint_dir else "off")
    )
    return server, (
        f"scoring {cfg.data.dataset} flows on "
        f"{args.host}:{server.port} (model round {engine.round_id}; "
        f"hot reload: {reload_src}; auth "
        f"{'on' if auth_key else 'off — open port'})"
    )


def cmd_infer_serve(args) -> int:
    server, banner = build_infer_server(args)
    with server:
        log.info(f"[SERVE] {banner}")
        try:
            while True:
                time.sleep(60.0)
                s = server.stats()
                log.info(
                    f"[SERVE] {s['scored']} flows served "
                    f"({s['flows_per_sec']:.1f}/s), p50 {s['p50_ms']:.2f} ms "
                    f"p99 {s['p99_ms']:.2f} ms, round {s['round']}, "
                    f"rejects {s['rejects']}"
                )
        except KeyboardInterrupt:
            log.info("[SERVE] interrupted; draining")
    return 0
