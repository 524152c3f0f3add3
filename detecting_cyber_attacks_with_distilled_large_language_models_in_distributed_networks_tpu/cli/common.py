"""Shared CLI plumbing: config resolution, pretrained/tokenizer
resolution, data loading, and per-client report writing (split out of the
original monolithic cli module; see package docstring in .parser)."""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
from typing import Any

import numpy as np

from ..config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
)
from ..utils.logging import get_logger, phase

log = get_logger()


# ------------------------------------------------------------- observability
def _obs_setup(
    args,
    *,
    proc: str,
    cfg: ExperimentConfig | None = None,
    install_global: bool = True,
    metrics_host: str = "127.0.0.1",
):
    """One call per CLI process: build this process's span Tracer (from
    --trace-jsonl, falling back to the config's obs.trace_jsonl), install
    it as the global tracer (the mesh-tier trainers' fallback hook), and
    start the /metrics endpoint when --metrics-port (or obs.metrics_port)
    asks for one. Returns ``(tracer | None, metrics_server | None)``.

    ``install_global=False`` (the TCP client): the round loop measures
    its own client-local phase through FederatedClient.note_local_phase,
    so the inner trainer's fallback hook must stay disarmed — a
    --seq-parallel client's embedded fedseq trainer would otherwise emit
    a SECOND client-local span per round and double the timeline's
    compute attribution.

    ``--flight-dir`` (or obs.flight_dir) additionally installs the
    process failure flight recorder (obs/flight.py): the daemon keeps a
    bounded ring of recent spans and dumps a postmortem bundle there on
    round failure / replica eject storm / SLO page."""
    from ..obs import (
        FlightRecorder,
        Tracer,
        maybe_start_metrics_server,
        set_global_recorder,
        set_global_tracer,
    )
    from ..obs.trace import set_run_id

    obs_cfg = cfg.obs if cfg is not None else None
    if obs_cfg is not None and obs_cfg.run_id:
        # Pin BEFORE the first span/metrics record: every stream this
        # process writes then carries the configured run identity.
        set_run_id(obs_cfg.run_id)
    trace_path = getattr(args, "trace_jsonl", None) or (
        obs_cfg.trace_jsonl if obs_cfg else None
    )
    tracer = None
    if trace_path:
        tracer = Tracer(trace_path, proc=proc)
        log.info(f"[OBS] {proc}: appending spans to {trace_path}")
    # Unconditional: an invocation WITHOUT tracing must clear any tracer
    # a previous in-process invocation installed (tests drive several CLI
    # commands per process; a stale global tracer would keep appending to
    # a dead path).
    set_global_tracer(tracer if install_global else None)
    flight_dir = getattr(args, "flight_dir", None) or (
        obs_cfg.flight_dir if obs_cfg else None
    )
    recorder = None
    if flight_dir:
        recorder = FlightRecorder(
            flight_dir,
            proc=proc,
            ring=obs_cfg.flight_ring if obs_cfg else 256,
            # The bundle's config section: what this process was
            # actually running with — the first thing a postmortem
            # reader checks against their expectations.
            config={
                "proc": proc,
                **({"experiment": cfg.to_dict()} if cfg is not None else {}),
            },
            tracer=tracer,
        )
        log.info(
            f"[OBS] {proc}: flight recorder armed, postmortem bundles "
            f"-> {flight_dir}"
        )
    # Same unconditional rule as the tracer: clear a previous in-process
    # invocation's recorder when this one doesn't ask for one.
    set_global_recorder(recorder)
    # Device performance plane (obs/profile.py): install the step-
    # profiling stride process-wide — unconditional, like the tracer,
    # so a previous in-process invocation's stride never leaks into a
    # run that didn't ask for profiling. Trainers/engines built before
    # this call re-check the stride at fit time.
    from ..obs.profile import set_profile_stride

    stride = getattr(args, "profile_stride", None)
    if stride is None and obs_cfg is not None:
        stride = obs_cfg.profile_stride
    set_profile_stride(stride or 0)
    if stride:
        log.info(
            f"[OBS] {proc}: step profiling armed (every {stride}th step "
            "fenced into host/dispatch/device)"
        )
    port = getattr(args, "metrics_port", None) or (
        obs_cfg.metrics_port if obs_cfg else 0
    )
    # The endpoint is unauthenticated: bind no wider than the tier
    # itself (server commands pass their own --host; everything else
    # stays loopback).
    server = maybe_start_metrics_server(port, host=metrics_host)
    if server is not None:
        log.info(
            f"[OBS] {proc}: Prometheus /metrics on "
            f"{metrics_host}:{server.port}"
        )
    return tracer, server


# ------------------------------------------------------------------ config
def _preset_model(preset: str, vocab_size: int):
    # One registry (models/presets.py) behind every entrypoint's
    # --preset; adding a scale point is a registry entry, not an
    # if-chain edit here.
    from ..models.presets import model_preset

    try:
        return model_preset(preset, vocab_size=vocab_size)
    except ValueError as e:
        raise SystemExit(f"--preset: {e}") from None


def _resolve_mesh(args, cfg: ExperimentConfig, n: int) -> MeshConfig:
    """Mesh axes from flags: ``is None`` checks (an explicit 0 must reach
    MeshConfig's own validation, not silently fall back to the config
    default), and validation errors surface as operator messages."""
    dp = getattr(args, "data_parallel", None)
    sp = getattr(args, "seq_parallel", None)
    fsdp = getattr(args, "fsdp", None)
    try:
        return MeshConfig(
            clients=n,
            data=cfg.mesh.data if dp is None else dp,
            seq=cfg.mesh.seq if sp is None else sp,
            # store_true default is False; the config file wins unless
            # the flag was actually given.
            fsdp=cfg.mesh.fsdp or bool(fsdp),
        )
    except ValueError as e:
        raise SystemExit(str(e)) from None


def resolve_config(args: argparse.Namespace, *, vocab_size: int) -> ExperimentConfig:
    """defaults <- --config file <- flags."""
    if getattr(args, "config", None):
        with open(args.config) as f:
            cfg = ExperimentConfig.from_dict(json.load(f))
    else:
        from ..models.presets import PRESET_DATA

        preset = getattr(args, "preset", "tiny")
        model = _preset_model(preset, vocab_size)
        cfg = ExperimentConfig(
            model=model,
            data=DataConfig(max_len=model.max_len, **PRESET_DATA.get(preset, {})),
        )

    model_kw: dict[str, Any] = {}
    if getattr(args, "max_len", None):
        model_kw.update(max_len=args.max_len)
    if getattr(args, "gelu", None):
        model_kw.update(gelu=args.gelu)
    if getattr(args, "attention_impl", None):
        model_kw.update(attention_impl=args.attention_impl)
    if getattr(args, "attention_dropout", None) is not None:
        # Explicit 0 must reach the config (ring requires it).
        model_kw.update(attention_dropout=args.attention_dropout)
    if getattr(args, "remat", None) is not None:
        # Tri-state: --remat / --no-remat / absent (config wins).
        model_kw.update(remat=args.remat)
    try:
        new_model = cfg.model.replace(**model_kw) if model_kw else cfg.model
    except ValueError as e:
        # Operator error (e.g. --attention-impl ring with the default
        # attention_dropout): surface the config validation message, not
        # a traceback.
        raise SystemExit(str(e)) from None

    # model and data must change together: ExperimentConfig.__post_init__
    # checks data.max_len == model.max_len on every replace.
    data_kw: dict[str, Any] = {"max_len": new_model.max_len}
    if getattr(args, "dataset", None):
        data_kw.update(dataset=args.dataset)
    if getattr(args, "batch_size", None):
        data_kw.update(batch_size=args.batch_size, eval_batch_size=args.batch_size)
    if getattr(args, "data_fraction", None):
        data_kw.update(data_fraction=args.data_fraction)
    if getattr(args, "partition", None):
        data_kw.update(partition=args.partition)
    if getattr(args, "dirichlet_alpha", None) is not None:
        # Explicit 0 must reach DataConfig's own validation, not silently
        # fall back to the default.
        data_kw.update(dirichlet_alpha=args.dirichlet_alpha)
    cfg = dataclasses.replace(
        cfg, model=new_model, data=dataclasses.replace(cfg.data, **data_kw)
    )

    train_kw: dict[str, Any] = {}
    if getattr(args, "epochs", None):
        train_kw.update(epochs_per_round=args.epochs)
    if getattr(args, "learning_rate", None):
        train_kw.update(learning_rate=args.learning_rate)
    if getattr(args, "warmup_steps", None) is not None:
        train_kw.update(warmup_steps=args.warmup_steps)
    if getattr(args, "seed", None) is not None:
        train_kw.update(seed=args.seed)
    if getattr(args, "prox_mu", None) is not None:
        # The TCP client's local phase reads TrainConfig.prox_mu (the
        # engine's FedProx step); the mesh tier reads FedConfig.prox_mu
        # (resolved below). One flag feeds whichever tier runs.
        train_kw.update(prox_mu=args.prox_mu)
    if train_kw:
        cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, **train_kw))

    if hasattr(args, "num_clients"):
        n = args.num_clients or cfg.fed.num_clients
        participation = (
            cfg.fed.participation
            if getattr(args, "participation", None) is None
            else args.participation
        )
        # --participation implies the survivor floor can't exceed the
        # sampling rate; clamp ONLY the untouched default floor so an
        # explicitly configured floor still collides loudly in FedConfig
        # validation instead of being silently weakened.
        min_frac = cfg.fed.min_client_fraction
        if participation < min_frac and min_frac == FedConfig().min_client_fraction:
            min_frac = participation
        cfg = dataclasses.replace(
            cfg,
            fed=dataclasses.replace(
                cfg.fed,
                num_clients=n,
                rounds=getattr(args, "rounds", None) or cfg.fed.rounds,
                weighted=(
                    True
                    if getattr(args, "weighted", False)
                    else False
                    if getattr(args, "unweighted", False)
                    else cfg.fed.weighted
                ),
                prox_mu=(
                    cfg.fed.prox_mu
                    if getattr(args, "prox_mu", None) is None
                    else args.prox_mu
                ),
                participation=participation,
                participation_mode=(
                    getattr(args, "participation_mode", None)
                    or cfg.fed.participation_mode
                ),
                min_client_fraction=min_frac,
                dp_clip=(
                    cfg.fed.dp_clip
                    if getattr(args, "dp_clip", None) is None
                    else args.dp_clip
                ),
                dp_noise_multiplier=(
                    cfg.fed.dp_noise_multiplier
                    if getattr(args, "dp_noise_multiplier", None) is None
                    else args.dp_noise_multiplier
                ),
                server_opt=getattr(args, "server_opt", None) or cfg.fed.server_opt,
                server_lr=(
                    cfg.fed.server_lr
                    if getattr(args, "server_lr", None) is None
                    else args.server_lr
                ),
                server_momentum=(
                    cfg.fed.server_momentum
                    if getattr(args, "server_momentum", None) is None
                    else args.server_momentum
                ),
                personalize_epochs=(
                    cfg.fed.personalize_epochs
                    if getattr(args, "personalize_epochs", None) is None
                    else args.personalize_epochs
                ),
                personalize_scope=(
                    getattr(args, "personalize_scope", None)
                    or cfg.fed.personalize_scope
                ),
            ),
            mesh=_resolve_mesh(args, cfg, n),
        )
    if getattr(args, "output_dir", None):
        cfg = dataclasses.replace(cfg, output_dir=args.output_dir)
    if getattr(args, "checkpoint_dir", None):
        cfg = dataclasses.replace(cfg, checkpoint_dir=args.checkpoint_dir)
    return cfg


# --------------------------------------------------------------- pretrained
def _resolve_with_pretrained(args, *, load_weights: bool = True):
    """(tokenizer, resolved config, initial params or None).

    ``load_weights=False`` skips the (full) HF/.pth weight load while still
    resolving tokenizer + architecture from ``--hf-dir`` — for callers
    whose weights come from elsewhere (e.g. distill --teacher-checkpoint).

    With ``--hf-dir`` (the reference's required ``./distilbert-base-uncased``
    directory, client1.py:357,360-361): vocab from its ``vocab.txt``,
    architecture from its ``config.json``, initial encoder weights from its
    checkpoint (fresh head, as at reference client1.py:58). Without it:
    the domain tokenizer and random init.
    """
    hf_dir = getattr(args, "hf_dir", None)
    if getattr(args, "pth", None) and not hf_dir:
        raise SystemExit(
            "--pth needs --hf-dir alongside it: the .pth holds only weights; "
            "the tokenizer and architecture come from the HF checkpoint dir "
            "(the reference requires the same directory, client1.py:357)"
        )
    if not hf_dir:
        from ..data import default_tokenizer

        tok = default_tokenizer()
        return tok, resolve_config(args, vocab_size=len(tok.vocab)), None

    from ..data import WordPieceTokenizer
    from ..models.hf_convert import config_from_hf_dir, load_hf_dir

    tok = WordPieceTokenizer.from_vocab_file(os.path.join(hf_dir, "vocab.txt"))
    # Resolve WITHOUT --max-len: the preset model this produces is discarded
    # below, and validating the flag against its (irrelevant) position table
    # would reject lengths the checkpoint actually supports.
    args_sans_len = copy.copy(args)
    args_sans_len.max_len = None
    cfg = resolve_config(args_sans_len, vocab_size=len(tok.vocab))
    # Architecture comes from the checkpoint; every non-architecture knob
    # (dtypes, dropouts, attention impl, head size) carries over from the
    # resolved config so --config files keep working under --hf-dir.
    # Sequence length defaults to min(128, the checkpoint's position table)
    # — the reference's 128 (client1.py:27) — unless --max-len says else.
    m = cfg.model
    overrides: dict[str, Any] = dict(
        dropout=m.dropout,
        attention_dropout=m.attention_dropout,
        head_dropout=m.head_dropout,
        n_classes=m.n_classes,
        compute_dtype=m.compute_dtype,
        param_dtype=m.param_dtype,
        attention_impl=m.attention_impl,
        ring_axis=m.ring_axis,
        remat=m.remat,
        fused_qkv=m.fused_qkv,
    )
    # Activation precedence: --gelu flag > --config file's model section >
    # the checkpoint's declared activation (config.json) > library default.
    # The config file only wins when it actually SAYS gelu — a file saved
    # before the field existed must not inject today's library default over
    # the checkpoint's declared activation (same legacy rule as
    # ExperimentConfig.from_checkpoint_dict).
    if getattr(args, "gelu", None):
        overrides["gelu"] = args.gelu
    elif getattr(args, "config", None):
        with open(args.config) as f:
            if "gelu" in json.load(f).get("model", {}):
                overrides["gelu"] = m.gelu
    if getattr(args, "max_len", None):
        overrides["max_len"] = args.max_len
    model_cfg = config_from_hf_dir(hf_dir, **overrides)
    if len(tok.vocab) != model_cfg.vocab_size:
        raise SystemExit(
            f"--hf-dir vocab.txt has {len(tok.vocab)} entries but config.json "
            f"says vocab_size={model_cfg.vocab_size}"
        )
    cfg = dataclasses.replace(
        cfg,
        model=model_cfg,
        data=dataclasses.replace(cfg.data, max_len=model_cfg.max_len),
    )
    if not load_weights:
        return tok, cfg, None
    if getattr(args, "pth", None):
        # The reference's own trained artifact: --hf-dir supplies the
        # tokenizer + architecture (exactly as the reference requires that
        # directory, client1.py:56,357), the .pth supplies the weights —
        # mirroring its DDoSClassifier(path) + load_state_dict flow
        # (client1.py:374-377).
        from ..models.hf_convert import load_reference_pth

        with phase(f"loading reference .pth {args.pth}", tag="MODEL"):
            try:
                params = load_reference_pth(args.pth, model_cfg)
            except Exception as e:
                # KeyError = architecture mismatch vs --hf-dir's config.json,
                # FileNotFoundError = bad path, ValueError = headless dict —
                # all operator errors, none deserving a raw traceback.
                raise SystemExit(
                    f"--pth {args.pth}: {type(e).__name__}: {e} — expected "
                    "the reference's DDoSClassifier state dict matching "
                    "--hf-dir's architecture (client1.py:53-58,388)"
                ) from None
        return tok, cfg, params
    with phase(f"loading HF checkpoint {hf_dir}", tag="MODEL"):
        params, _ = load_hf_dir(
            hf_dir, cfg=model_cfg, head_rng=np.random.default_rng(cfg.train.seed)
        )
    return tok, cfg, params


# -------------------------------------------------------------------- data
def _load_client_splits(args, cfg: ExperimentConfig, num_clients: int):
    """CSV / mixed corpus / synthetic -> per-client text splits (host-side
    pandas/numpy only; tokenization is a separate phase so multi-host
    processes can tokenize just their own clients)."""
    from ..data import (
        load_flow_csv,
        load_mixed_corpus,
        make_all_client_splits,
        make_all_client_splits_from_corpus,
        make_synthetic,
        parse_source_arg,
    )

    # Partition manifest (data/partition.py): the non-IID schemes record
    # each client's label histogram next to the run outputs, on BOTH
    # deployment tiers (every tier's loader funnels through here).
    manifest_path = (
        os.path.join(cfg.output_dir, "partition_manifest.json")
        if cfg.data.partition != "sample" and cfg.output_dir
        else None
    )
    if getattr(args, "source", None):
        if getattr(args, "csv", None):
            raise SystemExit("--csv and --source are mutually exclusive")
        # --dataset pins the schema for unprefixed --source entries; entries
        # without either fall back to schema auto-detection.
        default_name = getattr(args, "dataset", None)
        entries = [
            (name or default_name, path)
            for name, path in map(parse_source_arg, args.source)
        ]
        with phase(f"loading {len(entries)}-source mixed corpus", tag="DATA"):
            corpus = load_mixed_corpus(entries)
        with phase("partition/split", tag="DATA"):
            return make_all_client_splits_from_corpus(
                corpus, num_clients, cfg.data, manifest_path=manifest_path
            )
    if getattr(args, "csv", None):
        with phase(f"loading {args.csv}", tag="DATA"):
            df = load_flow_csv(args.csv)
    else:
        n = getattr(args, "synthetic", None) or 2400
        with phase(f"generating {n} synthetic {cfg.data.dataset} flows", tag="DATA"):
            df = make_synthetic(cfg.data.dataset, n, seed=cfg.data.seed_base)
    with phase("partition/split", tag="DATA"):
        return make_all_client_splits(
            df, num_clients, cfg.data, manifest_path=manifest_path
        )


def _load_clients(args, cfg: ExperimentConfig, tok, num_clients: int):
    """Full path: text splits -> tokenized static-shape arrays, all clients."""
    from ..data import tokenize_client

    if getattr(args, "stream", False):
        if not getattr(args, "csv", None):
            raise SystemExit("--stream needs --csv (chunked two-pass reader)")
        from ..data import stream_client_tokens

        with phase(f"streaming {args.csv}", tag="DATA"):
            return stream_client_tokens(
                args.csv, cfg.data, num_clients, tok, max_len=cfg.model.max_len
            )
    splits = _load_client_splits(args, cfg, num_clients)
    if cfg.data.window_flows:
        from ..data import window_client

        splits = [window_client(s, cfg.data.window_flows) for s in splits]
    with phase("tokenize", tag="DATA"):
        return [tokenize_client(s, tok, max_len=cfg.model.max_len) for s in splits]


# --------------------------------------------------------------- reporting
def _write_reports(
    client_id: int,
    local: dict,
    aggregated: dict | None,
    output_dir: str,
) -> None:
    """The reference's per-client artifact set: one-row metrics CSVs named
    ``client{N}_{local,aggregated}_metrics.csv`` (client1.py:386,401) and the
    plot set under ``client{N}_plots/`` (client1.py:153-225)."""
    from .. import reporting

    os.makedirs(output_dir, exist_ok=True)
    reporting.save_metrics(
        local, os.path.join(output_dir, f"client{client_id}_local_metrics.csv")
    )
    if aggregated is not None:
        reporting.save_metrics(
            aggregated,
            os.path.join(output_dir, f"client{client_id}_aggregated_metrics.csv"),
        )
    written = reporting.plot_evaluation(
        local,
        aggregated,
        os.path.join(output_dir, f"client{client_id}_plots"),
        client_id=client_id,
    )
    log.info(
        f"[CLIENT {client_id}] wrote metrics CSVs and {len(written)} plots "
        f"under {output_dir}"
    )
