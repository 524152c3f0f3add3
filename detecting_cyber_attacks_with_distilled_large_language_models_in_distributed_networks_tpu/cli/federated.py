"""fedtpu federated — N clients on one TPU mesh: SPMD local epochs +
pmean FedAvg, multi-round, checkpoint/resume (the TPU-native deployment)."""

from __future__ import annotations

import os
from typing import Any, NamedTuple

import numpy as np

from ..utils.logging import get_logger, phase
from .common import (
    _load_client_splits,
    _obs_setup,
    _resolve_with_pretrained,
    _write_reports,
)

log = get_logger()


class FederatedRun(NamedTuple):
    """What one ``fedtpu federated`` launch built and ended with — for a
    caller that checks the run itself (chip_smoke.py), not its exit code."""

    trainer: Any
    state: Any  # the final FedState, as checkpointed
    round_losses: list  # per round run this launch: [E, C] epoch-mean losses


def cmd_federated(args) -> int:
    run_federated(args)
    return 0


def run_federated(args) -> FederatedRun:
    import jax

    from ..data import stack_clients_ragged, tokenize_client
    from ..train.federated import FederatedTrainer

    # Multi-host bootstrap must precede the first backend touch
    # (jax.devices()/process_count()); config resolution and data loading
    # are backend-free so their order doesn't matter.
    mesh = None
    local_sl = None
    # multihost.initialize owns ALL the configuration logic (flag/env
    # resolution, single-process no-op, TPU-pod autodetect); the CLI only
    # converts its failures into actionable messages.
    from ..parallel.multihost import initialize

    try:
        initialize(
            getattr(args, "coordinator", None),
            getattr(args, "num_processes", None),
            getattr(args, "process_id", None),
        )
    except Exception as e:
        raise SystemExit(
            f"multi-host bootstrap failed: {e}\n"
            "Pass --coordinator HOST:PORT --num-processes N --process-id I "
            "together (every process the same coordinator), or none of them "
            "on a platform where jax.distributed autodetects."
        )

    # Fail fast on an unfittable data axis — knowable from argv + device
    # count alone, before any (potentially large) HF checkpoint load.
    # Client-axis fitting itself lives in FederatedTrainer (replica
    # stacking), serving library callers too.
    if (
        jax.process_count() == 1
        and getattr(args, "data_parallel", None)
        and args.data_parallel > len(jax.devices())
    ):
        raise SystemExit(
            f"--data-parallel {args.data_parallel} exceeds the "
            f"{len(jax.devices())} available devices"
        )

    tok, cfg, pretrained = _resolve_with_pretrained(args)
    C = cfg.fed.num_clients
    if jax.process_count() > 1:
        from ..parallel.multihost import (
            local_client_slice,
            make_global_mesh,
            make_global_seq_mesh,
        )

        if C != cfg.mesh.clients:
            raise SystemExit(
                f"multi-host runs need one mesh row per client "
                f"(num_clients={C}, mesh.clients={cfg.mesh.clients})"
            )
        if cfg.mesh.seq > 1:
            # --seq-parallel multi-host: clients over DCN, each client's
            # seq ring (and data psum) inside one host's ICI domain.
            mesh = make_global_seq_mesh(
                cfg.mesh.clients, cfg.mesh.data, cfg.mesh.seq
            )
        else:
            mesh = make_global_mesh(
                cfg.mesh.clients, cfg.mesh.data, axis_names=cfg.mesh.axis_names
            )
        local_sl = local_client_slice(mesh)
        log.info(
            f"[FED] process {jax.process_index()}/{jax.process_count()} owns "
            f"clients [{local_sl.start}, {local_sl.stop})"
        )

    if getattr(args, "stream", False):
        if not getattr(args, "csv", None):
            raise SystemExit("--stream needs --csv (chunked two-pass reader)")
        from ..data import stream_client_tokens_for

        # Works multi-host: every process computes the identical global
        # plan (same label scan), materializes tokens only for ITS clients,
        # and learns every client's split sizes for the stacked shapes and
        # FedAvg weights.
        stream_ids = (
            list(range(C))
            if local_sl is None
            else list(range(local_sl.start, local_sl.stop))
        )
        with phase(f"streaming {args.csv} for clients {stream_ids}", tag="DATA"):
            clients, sizes = stream_client_tokens_for(
                args.csv, cfg.data, C, tok, stream_ids, max_len=cfg.model.max_len
            )
        train_sizes = [s["train"] for s in sizes]
        eval_rows_global = max(s["test"] for s in sizes)
        val_rows_global = max(s["val"] for s in sizes)
    else:
        # Partitioning runs over the full fleet on every host (it must be
        # globally consistent); tokenization — the host-side hot loop — runs
        # only for this process's clients. Global row counts for the stacked
        # train/eval feeds come from the (cheap) split lengths, so every host
        # agrees on batch counts without seeing other hosts' token arrays.
        splits = _load_client_splits(args, cfg, C)
        local_ids = (
            range(C) if local_sl is None else range(local_sl.start, local_sl.stop)
        )
        with phase(f"tokenize clients {list(local_ids)}", tag="DATA"):
            clients = [
                tokenize_client(splits[c], tok, max_len=cfg.model.max_len)
                for c in local_ids
            ]
        eval_rows_global = max(len(s.test) for s in splits)
        val_rows_global = max(len(s.val) for s in splits)
        train_sizes = [len(s.train) for s in splits]
    # Ragged stack to the GLOBAL fleet-max row count: no client's rows are
    # truncated (the reference's N independent processes each train on all
    # their own samples), and every host agrees on the stacked shape.
    stacked_train = stack_clients_ragged(
        [c.train for c in clients],
        pad_id=tok.pad_id,
        target_rows=max(train_sizes),
    )
    if cfg.mesh.seq > 1:
        # --seq-parallel N: the 3-axis clients x data x seq composition
        # (ring attention per client) behind the identical trainer surface
        # — eval, reports, checkpointing, DP all flow through unchanged.
        from ..train.seqfed import FedSeqTrainer

        trainer = FedSeqTrainer(cfg, pad_id=tok.pad_id, mesh=mesh)
    else:
        trainer = FederatedTrainer(cfg, pad_id=tok.pad_id, mesh=mesh)
    # Obs spans for the mesh tier: per-round client-local / agg phase
    # timers land on this process's events-JSONL (no wire here — the
    # round boundary is a collective, so one proc covers the fleet).
    trainer.tracer, _metrics = _obs_setup(args, proc="fed", cfg=cfg)

    ckpt = None
    start_round = 0
    state = trainer.init_state(params=pretrained)
    if cfg.checkpoint_dir:
        # Works multi-host too: every process participates in save/restore
        # (orbax coordinates through the jax.distributed runtime; the state
        # template carries the global shardings).
        from ..train.checkpoint import Checkpointer, maybe_warm_start

        restored, step = maybe_warm_start(cfg.checkpoint_dir, state)
        if restored is not None:
            state, start_round = restored, int(step)
            log.info(f"[FED] resumed from round {start_round}")
            # Checkpoints are written BEFORE the per-round optimizer reset
            # (cmd loop below); apply the reset a continuous run would have
            # done so the resumed trajectory matches it exactly.
            if start_round < cfg.fed.rounds and cfg.fed.reset_optimizer_each_round:
                state = trainer.reset_optimizer(state)
        ckpt = Checkpointer(cfg.checkpoint_dir)

    # FedAvg weights are the GLOBAL per-client sample counts (known from the
    # cheap split phase on every host, reference semantics: weight by data).
    # weighted=None (the default) auto-weights; --unweighted forces the
    # reference's literal uniform mean.
    weights = (
        np.array(train_sizes, np.float64) if cfg.fed.resolve_weighted() else None
    )
    # Under a uniform mean (--unweighted, or DP's forced uniform), zero-row
    # clients would average their never-trained round-start params in with
    # full 1/C weight; mask them out as permanently dropped clients (same
    # rule as FederatedTrainer.run). train_sizes is global, so every host
    # builds the identical mask.
    base_mask = None
    if weights is None:
        empty = np.asarray(train_sizes) == 0
        if empty.any():
            base_mask = (~empty).astype(np.float64)
            log.warning(
                f"[FED] clients {np.flatnonzero(empty).tolist()} have zero "
                "train rows; excluding them from the uniform mean"
            )
    from ..utils.profiling import trace

    prepared = trainer.prepare_eval(
        [c.test for c in clients], target_rows=eval_rows_global
    )
    # Validation metrics every phase, like the reference (it evaluates val
    # AND test at each of local/aggregated, client1.py:383-385,398-400).
    prepared_val = trainer.prepare_eval(
        [c.val for c in clients], target_rows=val_rows_global
    )
    history = []
    round_losses = []
    with trace(getattr(args, "profile_dir", None)):
        for r in range(start_round, cfg.fed.rounds):
            anchor = trainer.round_anchor(state)
            with phase(f"round {r + 1}/{cfg.fed.rounds}", tag="FED"):
                state, losses = trainer.fit_local(
                    state, stacked_train, epoch_offset=r * cfg.train.epochs_per_round
                )
                round_losses.append(losses)
                local_val = trainer.evaluate_clients(
                    state.params, prepared=prepared_val
                )
                local = trainer.evaluate_clients(state.params, prepared=prepared)
                # Shared sampling/gating/aggregation (incl. the Poisson
                # empty-cohort no-op round, train/federated.py).
                state = trainer.round_aggregate(
                    state,
                    round_index=r,
                    weights=weights,
                    base_mask=base_mask,
                    anchor=anchor,
                )
                aggregated_val = trainer.evaluate_clients(
                    state.params, prepared=prepared_val
                )
                aggregated = trainer.evaluate_clients(state.params, prepared=prepared)
            history.append((r, local, aggregated))
            for c in range(C):
                log.info(
                    f"[FED] round {r + 1} client {c}: local val/test acc "
                    f"{local_val[c]['Accuracy']:.4f}/{local[c]['Accuracy']:.4f}"
                    f" -> aggregated "
                    f"{aggregated_val[c]['Accuracy']:.4f}/"
                    f"{aggregated[c]['Accuracy']:.4f}"
                )
            if getattr(args, "metrics_jsonl", None) and jax.process_index() == 0:
                from ..reporting import append_metrics_jsonl

                for c in range(C):
                    for phase_name, split_name, m in (
                        ("local", "val", local_val[c]),
                        ("local", "test", local[c]),
                        ("aggregated", "val", aggregated_val[c]),
                        ("aggregated", "test", aggregated[c]),
                    ):
                        append_metrics_jsonl(
                            args.metrics_jsonl,
                            {
                                "round": r + 1,
                                "client": c,
                                "phase": phase_name,
                                "split": split_name,
                                **m,
                            },
                        )
            if ckpt is not None:
                ckpt.save(
                    r + 1,
                    state,
                    meta={
                        "round": r + 1,
                        "kind": "federated",
                        "config": cfg.to_dict(),
                    },
                )
            if getattr(args, "registry_dir", None) and jax.process_index() == 0:
                # Registry-aware checkpointing: every finished round also
                # becomes an immutable CANDIDATE artifact with its
                # fleet-mean validation metrics (model-selection data —
                # never test), so `fedtpu registry promote` / the control
                # plane can gate what serves without touching raw orbax
                # steps. Replica 0 is the global model (FedAvg replicates
                # its output across the clients axis).
                from ..registry import ModelRegistry

                params0 = jax.tree.map(
                    lambda x: np.asarray(x)[0], trainer._host(state.params)
                )
                fleet_val = {
                    k: float(np.mean([m[k] for m in aggregated_val]))
                    for k in ("Accuracy", "Loss", "Precision", "Recall", "F1-Score")
                    if all(k in m for m in aggregated_val)
                }
                ModelRegistry(args.registry_dir).add(
                    params0,
                    round_index=r + 1,
                    metrics=fleet_val,
                    model_config=cfg.model,
                    extra={"tier": "mesh", "clients": C},
                )
            if r + 1 < cfg.fed.rounds and cfg.fed.reset_optimizer_each_round:
                state = trainer.reset_optimizer(state)
    if ckpt is not None:
        ckpt.wait()
        ckpt.close()

    if cfg.fed.dp_clip > 0.0 and cfg.fed.dp_noise_multiplier > 0.0:
        from ..parallel.dp import dp_epsilon_both

        # Only the rounds executed THIS launch are known to have run under
        # this DP config; a resumed checkpoint's earlier rounds may have
        # been trained without noise, so the guarantee must not cover them.
        dp_rounds = cfg.fed.rounds - start_round
        # participation < 1: the subsampled-Gaussian accountant credits
        # privacy amplification (parallel/dp.py::sgm_rdp). Under the
        # Poisson sampler (the default with DP on) q is the exact
        # Bernoulli rate; under the fixed sampler it is the EFFECTIVE
        # cohort_size/C approximation.
        q, q_exact = cfg.fed.dp_sampling_rate()
        eps_zeroed, eps_replace = dp_epsilon_both(
            dp_rounds, cfg.fed.dp_noise_multiplier, 1e-5, sampling_rate=q
        )
        caveat = (
            ""
            if start_round == 0
            else (
                f" — covers rounds {start_round + 1}..{cfg.fed.rounds} only; "
                f"the {start_round} resumed round(s) carry whatever DP "
                "config they were run with"
            )
        )
        # Both adjacency bounds, every run: the zeroed-contribution figure
        # alone reads ~4x stronger than the same noise under the stricter
        # replace-one adjacency (parallel/dp.py module docstring).
        if q >= 1.0:
            sampling_note = ""
        elif q_exact:
            sampling_note = f"; Poisson sampling q={q:.3g} (accountant exact)"
        else:
            sampling_note = (
                f"; fixed-size cohort accounted as Poisson sampling "
                f"q={q:.3g} (approximation — use "
                f"--participation-mode poisson for an exact bound)"
            )
        log.info(
            f"[DP] client-level guarantee for {dp_rounds} round(s): "
            f"({eps_zeroed:.3g}, 1e-05)-DP under zeroed-contribution "
            f"adjacency; ({eps_replace:.3g}, 1e-05)-DP under replace-one "
            f"adjacency (clip {cfg.fed.dp_clip}, "
            f"noise x{cfg.fed.dp_noise_multiplier}{sampling_note})"
            f"{caveat}"
        )

    # Final reporting with probs for ROC/PR curves. Under multi-host,
    # evaluate_clients gathers every client's probs/labels process-major
    # (device replication + host allgather), so process 0 writes the FULL
    # artifact set — ROC/PR included — for all clients.
    final_local = history[-1][1] if history else None
    multihost = jax.process_count() > 1
    final_agg = trainer.evaluate_clients(
        state.params, prepared=prepared, collect_probs=True
    )
    final_pers = None
    if cfg.fed.personalize_epochs > 0:
        # FedAvg + local fine-tuning: each client adapts the aggregate on
        # its own shard (scope 'head' = FedPer) — evaluated as a third
        # phase; the aggregate itself (already evaluated above) is NOT
        # touched, so the standard artifact set stays comparable.
        with phase(
            f"personalization ({cfg.fed.personalize_epochs} epoch(s), "
            f"scope {cfg.fed.personalize_scope})",
            tag="FED",
        ):
            pstate, _ = trainer.personalize(state, stacked_train)
            final_pers = trainer.evaluate_clients(
                pstate.params, prepared=prepared
            )
        for c in range(C):
            log.info(
                f"[FED] client {c}: aggregated test acc "
                f"{final_agg[c]['Accuracy']:.4f} -> personalized "
                f"{final_pers[c]['Accuracy']:.4f}"
            )
        if getattr(args, "metrics_jsonl", None) and jax.process_index() == 0:
            from ..reporting import append_metrics_jsonl

            for c in range(C):
                append_metrics_jsonl(
                    args.metrics_jsonl,
                    {
                        "round": cfg.fed.rounds,
                        "client": c,
                        "phase": "personalized",
                        "split": "test",
                        **final_pers[c],
                    },
                )
    if not multihost or jax.process_index() == 0:
        if final_local is None:
            # No round trained this launch (e.g. relaunching a completed
            # checkpointed run): there ARE no local-model metrics — write
            # aggregated artifacts only rather than mislabeling.
            log.info(
                "[FED] all rounds already complete; writing aggregated "
                "reports only"
            )
            _save_phase_csvs(final_agg, "aggregated", cfg.output_dir)
        else:
            for c in range(C):
                _write_reports(c, final_local[c], final_agg[c], cfg.output_dir)
        if final_pers is not None:
            _save_phase_csvs(final_pers, "personalized", cfg.output_dir)
    return FederatedRun(trainer, state, round_losses)


def _save_phase_csvs(metrics: list, phase_name: str, out_dir: str) -> None:
    """One `client{c}_{phase}_metrics.csv` per client (reference schema)."""
    from .. import reporting

    os.makedirs(out_dir, exist_ok=True)
    for c, m in enumerate(metrics):
        reporting.save_metrics(
            m, os.path.join(out_dir, f"client{c}_{phase_name}_metrics.csv")
        )
