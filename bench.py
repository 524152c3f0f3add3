"""Headline benchmark: local-training throughput on the flagship model.

Measures the jitted train step on the full DistilBERT-base DDoS classifier
(66 M params; seq 128, Adam 2e-5 — reference client1.py:27,379-380) and
reports samples/sec against the reference's recorded CPU throughput of
~2.5 batch/s = 40 samples/s (client1_terminal_output.txt:7,9,11;
BASELINE.md), plus MFU against the local chip's peak (north star: ≥40%,
BASELINE.json). Batch defaults to the TPU sweet spot (BENCH_BATCH=16 for
the reference's exact configuration). Round-3 measured sweep on the v5e
chip (MFU): bs32 48.9, bs48 55.1, **bs64 57.6-58.4**, bs96 56.3, bs128
54.1, bs192 48.5, bs256 48.7 — hence the bs64 default.

Secondary modes via BENCH_MODE:
    train  (default)  DistilBERT train step
    bert              BERT-base scale-up train step (BASELINE.json config 4)
    eval              DistilBERT eval step vs the reference's ~10 batch/s
                      recorded eval throughput (BASELINE.md)
    fedavg            on-device FedAvg of a stacked 2-client DistilBERT
                      param tree vs the reference's 0.36 s host aggregation
                      (server_terminal_output.txt:14-15)
    flash             long-context flash-attention grad step vs the XLA
                      dot path at L=8192 (BENCH_SEQ overrides)
    ring              ring-schedule blockwise attention grad step (the
                      per-chunk math of parallel/ring_attention.py, single
                      chip, chunked K/V + online-softmax merge) vs the XLA
                      dot path at L=8192 (BENCH_SEQ / BENCH_RING_CHUNKS)
    fed2              the federated 2-axis product step (client replicas
                      on one chip) — the path fit_local actually executes
                      there (client-packing fast path when eligible)
    fedseq            the 3-axis (clients x data x seq) fedseq train step,
                      single chip — the --seq-parallel product path's
                      measured MFU (packed path when eligible)
    serve             the online scoring service (serving/): in-process
                      TCP server + closed-loop load generator; reports
                      flows/s and p50/p95/p99 latency (BENCH_SERVE_*
                      knobs: CONCURRENCY, REQUESTS, BUCKETS, WINDOW_MS)
    clientdp          the multi-chip TCP client's local phase: MeshTrainer
                      at --data-parallel N vs the single-device engine on
                      the same host (BENCH_DATA_PARALLEL, default 2);
                      vs_baseline IS the N-vs-1 speedup. Hosts with one
                      accelerator capture it from a virtual-CPU subprocess
    controller        the control plane's unattended round -> eval-gate ->
                      promote loop on a dryrun fleet (control/ + registry/):
                      rounds/hour, promotion latency (round end -> serving
                      pointer swap), and a machine-parsed gate_rejections
                      field (BENCH_CTRL_* knobs: ROUNDS, CLIENTS, PARAM_MB)
    scenario          the `fedtpu scenario` persona x partition matrix run
                      small: live loopback rounds with wire-level fault
                      injection; scenario_rounds_ok_frac asserted 1.0
    fleet             fleet-scale rounds (comm/relay.py): a live loopback
                      depth-2 fold tree — BENCH_FLEET_CLIENTS clients
                      (default 64) behind BENCH_FLEET_RELAYS relays behind
                      one weighted root, streamed both ways; headline
                      fleet_rounds_per_hour + relay_peak_agg_bytes, root
                      aggregate crc-pinned vs the aggregate_tree replay;
                      plus the chaos arm — one relay killed mid-round
                      (seeded dead-relay fault), clients re-home, the
                      root completes a degraded round crc-exact vs the
                      recorded actual assignment (fleet_rehomes_total,
                      fleet_subtree_failures, fleet_degraded_rounds_ok)
    router            the serving replica fleet (router/): live loopback
                      A/B of one scorer replica vs BENCH_ROUTER_REPLICAS
                      (default 3) behind the thin router, with a registry
                      promotion fired MID-LOAD so the rolling hot-reload
                      runs under traffic; headline router_qps_sustained +
                      router_p99_ms (vs the pinned BENCH_ROUTER_SLO_MS)
                      + router_rolling_reload_dropped asserted == 0
    profile           the device performance plane (obs/profile.py): one
                      run_profile_session over the flagship train step —
                      compile ledger + recompile flags, fenced host/
                      dispatch/device step split, memory watermarks,
                      analytic-vs-XLA FLOPs cross-check (pinned inside
                      FLOPS_RATIO_TOLERANCE), and the bucketed serving
                      path's zero-recompile storm (asserted 0, exit 3);
                      headline profile_compile_count / profile_recompiles
                      / profile_step_device_ms_p50 /
                      profile_peak_device_bytes
    shadow            the shadow evaluation plane (shadow/): a live
                      loopback disagreement-gated promotion — router
                      under closed-loop load with the traffic mirror
                      armed, an agreeing candidate promoted through the
                      gate on >= N mirrored pairs and a regressed one
                      rejected with the verdict on the registry event;
                      headline shadow_pairs_total / shadow_gate_verdicts
                      / shadow_added_p99_ms (asserted ~0 vs the
                      mirror-off arm), zero live drops asserted (exit 3)
    obs               the fleet health plane (obs/slo+fleet+flight): a
                      live loopback round campaign under the scrape hub
                      — a slow round FIRES the round-duration burn
                      alert, a quorum-missed round dumps a postmortem
                      bundle, healthy rounds CLEAR the alert; headline
                      slo_alerts_fired / obs_scrape_lag_ms /
                      postmortem_bundles (fired+cleared+bundle >= 1
                      asserted, exit 3)
    strategy          the server aggregation strategy sweep (strategies/):
                      `fedtpu scenario` run with --train on the Dirichlet
                      alpha=0.1 + lazy-persona cell, fedavg baseline vs
                      BENCH_STRAT_SPECS candidates (default fedprox +
                      fedopt:adam + headboost); headline
                      strategy_noniid_acc_lift (best candidate's final
                      accuracy minus fedavg's, asserted >= the pinned
                      STRATEGY_LIFT_FLOOR) and strategy_crc_exact (every
                      successful round's transformed aggregate bit-exact
                      vs the strategy replay over the clean survivor
                      mean, asserted 1.0), exit 3 on miss
    fsdp              the FSDP client mesh (train/client_mesh.py
                      FsdpMeshTrainer): shard-at-rest vs replicated A/B
                      on the same host mesh at equal global batch
                      (BENCH_FSDP_SHARDS, default 2); headline
                      fsdp_peak_param_opt_bytes_ratio (asserted <= 0.6
                      on >= 2 devices, "unavailable"-graceful),
                      fsdp_step_time_ratio (asserted <= 1.15x), and
                      fsdp_crc_exact (the wire-exchange gather
                      round-trip, asserted bit-exact); single-device
                      hosts capture it from a virtual-CPU subprocess

Every record is one JSON line of the shape
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
The default mode prints the secondary records FIRST — the two federated
product steps (VERDICT r4 #2: the driver bench must capture the federated
MFU, not just the dense proxy), the multi-chip client A/B, and the
online-serving throughput/latency record — and the dense headline LAST;
tail parsers keep reading the same headline metric, and the headline now
carries ``fed2_mfu``/``fedseq_mfu`` as machine-parsed fields with a
``BENCH_MFU_FLOOR`` (default 0.50) regression gate that exits 3 when a
federated product step breaks it. The headline also carries the fedseq
MFU-residual decomposition (``fedseq_residual_*``: hash-dropout vs
ring-merge vs degenerate-ring shares of the fed2-vs-fedseq step gap,
measured by no-dropout and merge micro A/Bs; BENCH_FEDSEQ_DECOMP=0
skips) and the round engine's measured ``comm_phase_{wait,agg,reply}_s``
breakdown from the controller fleet — ASSERTED present (exit 3 when the
phase accounting breaks). BENCH_SECONDARY=0 restores the single-line
output; every other mode prints exactly one line.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

# Keep the noisy platform banner off stdout (the JSON line must be parseable).
os.environ.setdefault("JAX_LOGGING_LEVEL", "ERROR")

import jax  # noqa: E402

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (  # noqa: E402
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (  # noqa: E402
    Trainer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.utils.compile_cache import (  # noqa: E402
    place_compile_cache,
)

REFERENCE_TRAIN_SAMPLES_PER_SEC = 40.0  # ~2.5 batch/s * bs 16 (BASELINE.md)
REFERENCE_EVAL_SAMPLES_PER_SEC = 160.0  # ~10 batch/s * bs 16 (BASELINE.md)
REFERENCE_FEDAVG_SECONDS = 0.36  # server_terminal_output.txt:14-15


def _sync(x) -> None:
    """Host readback of one scalar as the timing fence: it cannot return
    before the whole dependency chain has run, on any backend.
    ``jax.block_until_ready`` is an equally good fence on the TPU v5e under
    the installed JAX — chip_smoke.py times one matmul both ways on every
    run and prints the two against the peak (PERF.md has the last
    reading) — so the two fences in this repo (this one, and
    obs/profile.py's block_until_ready) measure the same thing."""
    np.asarray(jax.tree.leaves(x)[0]).ravel()[0]


def _emit(record: dict) -> None:
    print(json.dumps(record))


def _batch(model_cfg: ModelConfig, batch_size: int) -> dict:
    rng = np.random.default_rng(0)
    L = model_cfg.max_len
    return {
        k: jax.device_put(v)
        for k, v in {
            "input_ids": rng.integers(
                0, model_cfg.vocab_size, (batch_size, L)
            ).astype(np.int32),
            "attention_mask": np.ones((batch_size, L), np.int32),
            "labels": rng.integers(0, 2, batch_size).astype(np.int32),
        }.items()
    }


def bench_train(
    model_cfg: ModelConfig, name: str, extra: dict | None = None
) -> dict:
    # Default batch 64: the reference trains at bs=16 (client1.py:370) but
    # per-client batch is a free TPU knob (SURVEY.md §7c) — 64 is this
    # chip's measured MFU sweet spot (round-3 sweep in the module
    # docstring); vs_baseline compares samples/sec, which is
    # batch-size-fair. BENCH_BATCH=16 reproduces the reference
    # configuration exactly.
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "100"))
    # >=1: warmup 0 would leave `loss` unbound and time the compile.
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "10")))

    # TrainConfig defaults are the production path (incl. prng_impl="rbg"
    # dropout keys); BENCH_PRNG=threefry2x32 measures the costlier impl.
    # BENCH_FUSED_QKV=1 measures the apply-time Q/K/V fusion.
    if os.environ.get("BENCH_FUSED_QKV", "0").lower() not in ("", "0", "false"):
        model_cfg = model_cfg.replace(fused_qkv=True)
    train_cfg = TrainConfig(prng_impl=os.environ.get("BENCH_PRNG", "rbg"))
    trainer = Trainer(model_cfg, train_cfg)
    state = trainer.init_state(seed=0)
    batch = _batch(model_cfg, batch_size)

    for _ in range(warmup):
        state, loss = trainer.train_step(state, batch)
    _sync(loss)

    # Best-of-R timing windows: the minimum window approximates clean
    # hardware time. The capture-to-capture spread on the current
    # installation is not measured yet (ROADMAP Queue 1 item 1).
    # BENCH_REPEATS=1 restores the old single-window behavior.
    repeats = max(1, int(os.environ.get("BENCH_REPEATS", "3")))
    dt = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = trainer.train_step(state, batch)
        _sync(loss)
        window = time.perf_counter() - t0
        dt = window if dt is None else min(dt, window)

    samples_per_sec = batch_size * steps / dt

    # MFU accounting (utils/profiling.py): analytic step FLOPs over the
    # chip's peak — the BASELINE.json north-star metric (≥40% on DistilBERT).
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.utils.profiling import (
        device_peak_flops,
        mfu,
        train_step_flops,
    )

    flops = train_step_flops(model_cfg, batch_size)
    util = mfu(flops, dt / steps, peak_flops_per_device=device_peak_flops())
    record = {
        "metric": f"train_samples_per_sec_{name}_bs{batch_size}",
        "value": round(samples_per_sec, 2),
        "unit": "samples/sec",
        "vs_baseline": round(samples_per_sec / REFERENCE_TRAIN_SAMPLES_PER_SEC, 2),
        "device": jax.devices()[0].device_kind,
        "tflops_per_sec": round(flops * steps / dt / 1e12, 2),
    }
    if name != "distilbert":
        # The only recorded baseline is the reference's DistilBERT CPU run;
        # for other encoders the ratio is cross-model (understates the win).
        record["baseline_note"] = "vs reference DistilBERT CPU 40 samples/s"
    if util is not None:
        record["mfu"] = round(util, 4)
    if extra:
        # Machine-parsed companions on the HEADLINE record (the last line
        # the driver's tail parser reads): the federated product-step MFUs
        # ride here so BENCH_*.json `parsed` carries dense, fed2, and
        # fedseq MFU as fields, not tail text (VERDICT r5 weak #7).
        record.update(extra)
    _emit(record)
    return record


def bench_eval() -> None:
    batch_size = int(os.environ.get("BENCH_BATCH", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "100"))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "10")))
    model_cfg = ModelConfig()
    trainer = Trainer(model_cfg, TrainConfig())
    state = trainer.init_state(seed=0)
    batch = _batch(model_cfg, batch_size)
    valid = jax.device_put(np.ones(batch_size, np.int32))

    for _ in range(warmup):
        counts, *_ = trainer.eval_step(state.params, batch, valid)
    _sync(counts)
    t0 = time.perf_counter()
    for _ in range(steps):
        counts, *_ = trainer.eval_step(state.params, batch, valid)
    _sync(counts)
    dt = time.perf_counter() - t0
    sps = batch_size * steps / dt
    _emit(
        {
            "metric": f"eval_samples_per_sec_distilbert_bs{batch_size}",
            "value": round(sps, 2),
            "unit": "samples/sec",
            "vs_baseline": round(sps / REFERENCE_EVAL_SAMPLES_PER_SEC, 2),
            "device": jax.devices()[0].device_kind,
        }
    )


def bench_fedavg() -> None:
    """On-device mean of a stacked 2-client DistilBERT param tree — the
    round boundary the reference spends 0.36 s + two ~245 MB socket
    transfers on (server.py:67-79)."""
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.distilbert import (
        DDoSClassifier,
        init_params,
    )

    steps = int(os.environ.get("BENCH_STEPS", "50"))
    model_cfg = ModelConfig()
    params = init_params(
        DDoSClassifier(model_cfg), model_cfg, jax.random.key(0, impl="rbg")
    )
    stacked = jax.tree.map(lambda x: jnp.stack([x, x * 1.5]), params)

    @jax.jit
    def agg(t):
        return jax.tree.map(lambda x: x.mean(axis=0), t)

    out = agg(stacked)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = agg(stacked)
    _sync(out)
    dt = (time.perf_counter() - t0) / steps
    _emit(
        {
            "metric": "fedavg_seconds_distilbert_2clients",
            "value": round(dt, 6),
            "unit": "seconds",
            # Higher is better: reference seconds over ours.
            "vs_baseline": round(REFERENCE_FEDAVG_SECONDS / dt, 2),
            "device": jax.devices()[0].device_kind,
        }
    )


def bench_flash() -> None:
    """Long-context flash attention fwd+bwd vs the XLA dot path at L=8192
    (B=1, H=12, D=64 — the PARITY.md record's configuration)."""
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_bias,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
        flash_attention,
    )

    B, H, L, D = 1, 12, int(os.environ.get("BENCH_SEQ", "8192")), 64
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(rng.normal(size=(B, H, L, D)).astype(np.float32)).astype(
            jnp.bfloat16
        )
        for _ in range(3)
    )
    bias = make_attention_bias(jax.device_put(np.ones((B, L), np.int32)))

    def time_grad(fn):
        # Grad over ALL of (q, k, v): differentiating q alone would let XLA
        # dead-code-eliminate the dK/dV backward work, timing only part of
        # the gradient step.
        g = jax.jit(
            jax.grad(
                lambda qkv: fn(*qkv, bias).astype(jnp.float32).sum()
            )
        )
        out = g((q, k, v))
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = g((q, k, v))
        _sync(out)
        return (time.perf_counter() - t0) / steps

    flash_s = time_grad(flash_attention)
    dot_s = time_grad(dot_product_attention)
    _emit(
        {
            "metric": f"flash_attn_grad_ms_L{L}",
            "value": round(flash_s * 1e3, 2),
            "unit": "ms",
            # Higher is better: the XLA dot path's time over the kernel's.
            "vs_baseline": round(dot_s / flash_s, 2),
            "baseline_note": f"vs XLA dot-attention grad {dot_s * 1e3:.1f} ms",
            "device": jax.devices()[0].device_kind,
        }
    )


def bench_ring() -> None:
    """Ring-attention per-chunk math on one chip: the ring schedule's
    chunked K/V + online-softmax merge (parallel/ring_attention.py
    ``blockwise_attention_local`` — numerically the n-device ring minus
    the ppermute hops) fwd+bwd vs the XLA dot path at long L. This is the
    --seq-parallel path's compute kernel; the transport it omits rides
    ICI on real multi-chip."""
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_bias,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.ring_attention import (
        blockwise_attention_local,
    )

    B, H, L, D = 1, 12, int(os.environ.get("BENCH_SEQ", "8192")), 64
    n_chunks = int(os.environ.get("BENCH_RING_CHUNKS", "8"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(rng.normal(size=(B, H, L, D)).astype(np.float32)).astype(
            jnp.bfloat16
        )
        for _ in range(3)
    )
    bias = make_attention_bias(jax.device_put(np.ones((B, L), np.int32)))

    def time_grad(fn):
        g = jax.jit(
            jax.grad(lambda qkv: fn(*qkv, bias).astype(jnp.float32).sum())
        )
        out = g((q, k, v))
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = g((q, k, v))
        _sync(out)
        return (time.perf_counter() - t0) / steps

    ring_s = time_grad(
        lambda q, k, v, b: blockwise_attention_local(
            q, k, v, b, n_chunks=n_chunks
        )
    )
    dot_s = time_grad(dot_product_attention)
    _emit(
        {
            "metric": f"ring_attn_grad_ms_L{L}_c{n_chunks}",
            "value": round(ring_s * 1e3, 2),
            "unit": "ms",
            # Higher is better: the XLA dot path's time over the ring math's.
            "vs_baseline": round(dot_s / ring_s, 2),
            "baseline_note": f"vs XLA dot-attention grad {dot_s * 1e3:.1f} ms",
            "device": jax.devices()[0].device_kind,
        }
    )


def _time_product_step(trainer, model_cfg, n_clients, batch_size, steps, warmup):
    """Time one lockstep federated step the way fit_local executes it on
    this mesh: the client-packing fast path (per-client jitted steps,
    single-device mesh) when eligible, else the stacked vmapped step.
    Returns (seconds/step, path name)."""
    state = trainer.init_state(seed=0)
    rng = np.random.default_rng(0)
    L = model_cfg.max_len
    host_batch = {
        "input_ids": rng.integers(
            0, model_cfg.vocab_size, (n_clients, batch_size, L)
        ).astype(np.int32),
        "attention_mask": np.ones((n_clients, batch_size, L), np.int32),
        "labels": rng.integers(0, 2, (n_clients, batch_size)).astype(np.int32),
    }
    repeats = max(1, int(os.environ.get("BENCH_REPEATS", "3")))
    if trainer._packed_eligible():
        step_fn = trainer._build_packed_step()
        cstates = trainer._unstack_cstates(state)
        cbatches = [
            {k: jax.device_put(v[c]) for k, v in host_batch.items()}
            for c in range(n_clients)
        ]

        def run_once():
            last = None
            for c in range(n_clients):
                cstates[c], last = step_fn(cstates[c], cbatches[c])
            return last

        path = "packed"
    else:
        batch = trainer._feed(host_batch)
        fed_state = [state]

        def run_once():
            fed_state[0], losses = trainer.train_step(fed_state[0], batch)
            return losses

        path = "stacked"
    for _ in range(warmup):
        out = run_once()
    _sync(out)
    dt = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = run_once()
        _sync(out)
        window = time.perf_counter() - t0
        dt = window if dt is None else min(dt, window)
    return dt / steps, path


def bench_fed2() -> dict:
    """The federated 2-axis product step on one chip: FederatedTrainer's
    vmapped dense train step over stacked client replicas (mesh 1x1, C=2
    replicas on the chip — the program the driver's dryrun_multichip runs
    sharded over clients x data). Reports samples/sec across all clients
    plus MFU; the gap to the single-client headline is the price of the
    federated product step itself."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ExperimentConfig,
        FedConfig,
        MeshConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.federated import (
        FederatedTrainer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.utils.profiling import (
        device_peak_flops,
        mfu,
        train_step_flops,
    )

    n_clients = int(os.environ.get("BENCH_CLIENTS", "2"))
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))  # per client
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    cfg = ExperimentConfig(
        fed=FedConfig(num_clients=n_clients),
        mesh=MeshConfig(clients=1, data=1),
    )
    trainer = FederatedTrainer(cfg)
    dt, path = _time_product_step(
        trainer, cfg.model, n_clients, batch_size, steps, warmup
    )
    total = n_clients * batch_size
    sps = total / dt
    flops = train_step_flops(cfg.model, total)
    util = mfu(flops, dt, peak_flops_per_device=device_peak_flops())
    record = {
        "metric": f"fed2_samples_per_sec_c{n_clients}_bs{batch_size}",
        "value": round(sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(sps / REFERENCE_TRAIN_SAMPLES_PER_SEC, 2),
        "device": jax.devices()[0].device_kind,
        "tflops_per_sec": round(flops / dt / 1e12, 2),
        "step_seconds": round(dt, 6),
        "path": path,
    }
    if util is not None:
        record["mfu"] = round(util, 4)
    _emit(record)
    return record


def bench_fedseq() -> dict:
    """The --seq-parallel product path on one chip: FedSeqTrainer's 3-axis
    (clients x data x seq) jitted train step over stacked client replicas
    (mesh 1x1x1, C=2 replicas on the chip, ring path with a degenerate
    1-hop ring — the same program the driver's dryrun_multichip(8) runs
    sharded). Reports samples/sec across all clients plus MFU."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ExperimentConfig,
        FedConfig,
        MeshConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.seqfed import (
        FedSeqTrainer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.utils.profiling import (
        device_peak_flops,
        mfu,
        train_step_flops,
    )

    n_clients = int(os.environ.get("BENCH_CLIENTS", "2"))
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))  # per client
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    # >=1: warmup 0 would leave the timed output unbound and time the compile.
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    cfg = ExperimentConfig(
        fed=FedConfig(num_clients=n_clients),
        mesh=MeshConfig(clients=1, data=1, seq=1),
    )
    trainer = FedSeqTrainer(cfg)
    dt, path = _time_product_step(
        trainer, trainer.cfg.model, n_clients, batch_size, steps, warmup
    )
    total = n_clients * batch_size
    sps = total / dt
    flops = train_step_flops(trainer.cfg.model, total)
    util = mfu(flops, dt, peak_flops_per_device=device_peak_flops())
    record = {
        "metric": f"fedseq_samples_per_sec_c{n_clients}_bs{batch_size}",
        "value": round(sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(sps / REFERENCE_TRAIN_SAMPLES_PER_SEC, 2),
        "device": jax.devices()[0].device_kind,
        "tflops_per_sec": round(flops / dt / 1e12, 2),
        "step_seconds": round(dt, 6),
        "path": path,
    }
    if util is not None:
        record["mfu"] = round(util, 4)
    _emit(record)
    return record


def bench_fedseq_residual(
    rec_fed2: dict | None, rec_fedseq: dict | None
) -> dict | None:
    """Fedseq MFU residual decomposition (ROADMAP: "fedseq 56.0% vs fed2
    58.54% — the 2.5-point residual has no decomposition"). Measured A/Bs
    isolate where each fedseq step's extra time goes:

    * **hash-dropout**: rerun BOTH product steps with every dropout rate
      zeroed; the dropout cost difference ((fedseq - fedseq_nd) -
      (fed2 - fed2_nd)) is what the ring path's global-coordinate hash
      masks cost over the dense path's PRNG masks.
    * **ring-merge arithmetic**: micro A/B at the model's attention shape
      — blockwise_attention_local(n_chunks=1) (the online-softmax merge
      formulation with NO ring schedule) vs the XLA dot path — scaled by
      layers and clients.
    * **degenerate-ring overhead**: the remainder of the no-dropout gap —
      shard_map/1-hop-schedule cost that is neither merge math nor
      dropout.

    The parts are emitted as machine-parsed fields on this record AND as
    ``fedseq_residual_*`` companions on the headline record, so the
    driver pins the residual (and any fix) per round."""
    if not rec_fed2 or not rec_fedseq:
        return None
    if "step_seconds" not in rec_fed2 or "step_seconds" not in rec_fedseq:
        return None
    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ExperimentConfig,
        FedConfig,
        MeshConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_bias,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.ring_attention import (
        blockwise_attention_local,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.federated import (
        FederatedTrainer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.seqfed import (
        FedSeqTrainer,
    )

    n_clients = int(os.environ.get("BENCH_CLIENTS", "2"))
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_DECOMP_STEPS", "20"))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    fs_dt = float(rec_fedseq["step_seconds"])
    f2_dt = float(rec_fed2["step_seconds"])
    gap_s = fs_dt - f2_dt

    def _nd(cfg: ExperimentConfig) -> ExperimentConfig:
        return ExperimentConfig(
            fed=cfg.fed,
            mesh=cfg.mesh,
            model=cfg.model.replace(
                dropout=0.0, attention_dropout=0.0, head_dropout=0.0
            ),
        )

    cfg2 = ExperimentConfig(
        fed=FedConfig(num_clients=n_clients), mesh=MeshConfig(clients=1, data=1)
    )
    cfg3 = ExperimentConfig(
        fed=FedConfig(num_clients=n_clients),
        mesh=MeshConfig(clients=1, data=1, seq=1),
    )
    f2_nd_dt, _ = _time_product_step(
        FederatedTrainer(_nd(cfg2)), cfg2.model, n_clients, batch_size,
        steps, warmup,
    )
    tr3 = FedSeqTrainer(_nd(cfg3))
    fs_nd_dt, _ = _time_product_step(
        tr3, tr3.cfg.model, n_clients, batch_size, steps, warmup,
    )
    ring_total_s = fs_nd_dt - f2_nd_dt
    hash_dropout_s = (fs_dt - fs_nd_dt) - (f2_dt - f2_nd_dt)

    # Ring-merge micro A/B at the per-client attention shape: the
    # blockwise (online-softmax) formulation at n_chunks=1 runs the merge
    # arithmetic with zero ring schedule — its delta over the XLA dot
    # path, scaled by layers x clients, estimates the merge share of the
    # no-dropout gap; the rest is degenerate-ring/shard_map overhead.
    model = cfg2.model
    B, H, L, D = batch_size, model.n_heads, model.max_len, model.head_dim
    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(rng.normal(size=(B, H, L, D)).astype(np.float32)).astype(
            jnp.bfloat16
        )
        for _ in range(3)
    )
    bias = make_attention_bias(jax.device_put(np.ones((B, L), np.int32)))

    def _grad_time(fn):
        g = jax.jit(
            jax.grad(lambda qkv: fn(*qkv, bias).astype(jnp.float32).sum())
        )
        out = g((q, k, v))
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = g((q, k, v))
        _sync(out)
        return (time.perf_counter() - t0) / steps

    merge_attn_s = _grad_time(
        lambda q, k, v, b: blockwise_attention_local(q, k, v, b, n_chunks=1)
    )
    dot_attn_s = _grad_time(dot_product_attention)
    # The micro estimate is extrapolated (layers x clients, separate jit)
    # and can exceed a small/noisy gap; clamp BEFORE emitting so the
    # machine-parsed parts always satisfy
    # hash_dropout + ring_merge + degenerate_ring == gap exactly (a
    # negative degenerate_ring then honestly reads as measurement noise,
    # never as inconsistent bookkeeping).
    ring_merge_s = min(
        max(merge_attn_s - dot_attn_s, 0.0) * model.n_layers * n_clients,
        max(gap_s, 0.0),
    )
    degenerate_ring_s = gap_s - hash_dropout_s - ring_merge_s
    record = {
        "metric": f"fedseq_mfu_residual_c{n_clients}_bs{batch_size}",
        "value": round(gap_s * 1e3, 3),
        "unit": "ms/step",
        # Higher is better: fedseq step time as a fraction of fed2's
        # (1.0 = residual fully closed).
        "vs_baseline": round(f2_dt / fs_dt, 4) if fs_dt > 0 else None,
        "baseline_note": "fed2 product step time over fedseq's "
        "(no-dropout A/B + merge micro-A/B decomposition attached)",
        "fed2_step_ms": round(f2_dt * 1e3, 3),
        "fedseq_step_ms": round(fs_dt * 1e3, 3),
        "fed2_nodrop_step_ms": round(f2_nd_dt * 1e3, 3),
        "fedseq_nodrop_step_ms": round(fs_nd_dt * 1e3, 3),
        "hash_dropout_ms": round(hash_dropout_s * 1e3, 3),
        "ring_total_ms": round(ring_total_s * 1e3, 3),
        "ring_merge_ms": round(ring_merge_s * 1e3, 3),
        "degenerate_ring_ms": round(degenerate_ring_s * 1e3, 3),
        "device": jax.devices()[0].device_kind,
    }
    if rec_fed2.get("mfu") is not None and rec_fedseq.get("mfu") is not None:
        record["mfu_gap_points"] = round(
            (rec_fed2["mfu"] - rec_fedseq["mfu"]) * 100, 2
        )
    _emit(record)
    return record


def bench_serving() -> None:
    """Online scoring throughput/latency on the flagship model: stand up
    the real TCP service (serving/ScoringServer — dynamic micro-batcher,
    bucketed warm jit paths) in-process and drive it with the closed-loop
    load generator tests use. The record carries flows/s as the headline
    value plus client-observed p50/p95/p99 ms and the mean coalesced
    batch size. The nearest recorded reference number is its offline eval
    throughput (~160 samples/s on CPU, BASELINE.md) — the reference has
    no online serving at all, so vs_baseline understates the capability
    gap (it compares against a batch pipeline with no network, no
    per-request tokenization, and no latency bound)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
        make_synthetic,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
        get_dataset,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        MicroBatcher,
        ScoreEngine,
        ScoringServer,
        run_load,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        Trainer,
    )

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.serving import (
        _parse_buckets,
    )

    concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY", "16"))
    requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "1024"))
    # The CLI's parser, not a bare int split: it sorts and dedups, so an
    # unsorted spec can't silently cap max_batch below the largest bucket.
    buckets = _parse_buckets(os.environ.get("BENCH_SERVE_BUCKETS", "1,8,32,128"))
    window_ms = float(os.environ.get("BENCH_SERVE_WINDOW_MS", "2.0"))
    tok = default_tokenizer()
    model_cfg = ModelConfig(vocab_size=len(tok.vocab))
    trainer = Trainer(model_cfg, TrainConfig())
    params = trainer.init_state(seed=0).params
    spec = get_dataset("cicids2017")
    texts = spec.render_texts(make_synthetic("cicids2017", 256, seed=0))
    engine = ScoreEngine(model_cfg, params, pad_id=tok.pad_id, buckets=buckets)
    server = ScoringServer(
        engine,
        tok,
        spec=spec,
        batcher=MicroBatcher(
            max_batch=buckets[-1],
            max_queue=max(1024, 4 * buckets[-1]),
            gather_window_s=window_ms / 1e3,
        ),
        idle_tick_s=0.01,
    )
    with server:
        run_load(  # warm the sockets + tokenizer caches off the clock
            "127.0.0.1", server.port, texts[:32], concurrency=concurrency,
        )
        stats = run_load(
            "127.0.0.1",
            server.port,
            texts,
            concurrency=concurrency,
            requests=requests,
        )
    _emit(
        {
            "metric": f"serve_flows_per_sec_distilbert_c{concurrency}",
            "value": round(stats["flows_per_sec"], 2),
            "unit": "flows/sec",
            "vs_baseline": round(
                stats["flows_per_sec"] / REFERENCE_EVAL_SAMPLES_PER_SEC, 2
            ),
            "baseline_note": "vs reference offline CPU eval 160 samples/s "
            "(the reference has no online serving path)",
            "p50_ms": round(stats["p50_ms"], 2),
            "p95_ms": round(stats["p95_ms"], 2),
            "p99_ms": round(stats["p99_ms"], 2),
            "mean_batch": round(stats["mean_batch"], 2),
            "rejected": stats["rejected"],
            "device": jax.devices()[0].device_kind,
        }
    )


def bench_controller() -> dict | None:
    """Control-plane cadence on a dryrun fleet (ISSUE 3), now as a round-
    pipelining A/B (ISSUE 5): the unattended round -> eval-gate -> promote
    loop (control/Controller over the real TCP round engine with real
    in-process clients) measured end to end, TWICE — the barrier arm
    (stream_chunk_bytes=0: single-frame uploads, aggregation exposed after
    the last upload) vs the streaming arm (chunk-streamed uploads folded
    into the running mean as chunks arrive, comm/stream_agg.py).

    The record's value is the STREAMING arm's rounds/hour (the production
    shape); ``promotion_latency_ms`` is the round-end -> serving-pointer-
    swap gap, ``gate_rejections`` is machine-parsed so a driver can assert
    the gate stayed quiet. Pipelining headline fields (asserted present by
    the train-mode headline, exit 3): ``comm_overlap_frac`` — bytes-
    weighted fraction of aggregation input folded while the wire phase was
    still active — and ``server_peak_agg_bytes`` — the aggregation-state
    peak, O(model + in-flight leaves) under streaming vs O(clients x
    model) at the barrier. ``barrier_comm_phase_wait_s`` is the A/B's
    other arm on the same run."""
    import tempfile

    rounds = int(os.environ.get("BENCH_CTRL_ROUNDS", "5"))
    n_clients = int(os.environ.get("BENCH_CTRL_CLIENTS", "2"))
    # Model-sized payloads dominate the round wall; default ~4 MB keeps
    # the record cheap while exercising real encode/decode + registry IO.
    # Split over leaves (a real state dict's shape): per-LEAF folds are
    # what overlap with the slower clients' remaining wire transfer.
    param_mb = float(os.environ.get("BENCH_CTRL_PARAM_MB", "4"))
    n_leaves = 32
    leaf_elems = max(1, int(param_mb * 1e6 / 4 / n_leaves))
    rng = np.random.default_rng(0)
    base = {
        f"w{i:02d}": rng.normal(size=leaf_elems).astype(np.float32)
        for i in range(n_leaves)
    }
    # Chunks sized well under one leaf so each upload streams in many
    # frames and the server's running fold has in-flight wire to overlap.
    chunk = max(64 << 10, int(param_mb * (1 << 20)) // 16)

    def run_arm(stream_chunk_bytes: int):
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
            ModelRegistry,
        )

        root = tempfile.mkdtemp(prefix="bench-registry-")
        evals = [0]

        def eval_fn(params):
            # Monotonically improving synthetic metric: every round
            # promotes, so the record measures the FULL promote path.
            evals[0] += 1
            return {"Accuracy": min(0.5 + 0.01 * evals[0], 0.99)}

        errors: list[Exception] = []
        try:
            out = _run_controller_fleet(
                ModelRegistry(root), base, rounds, n_clients, eval_fn,
                errors, stream_chunk_bytes=stream_chunk_bytes,
            )
        finally:
            import shutil

            shutil.rmtree(root, ignore_errors=True)  # rounds x param_mb
        return out + (errors,)

    # Barrier arm first (stream off), then the streaming arm the record
    # headlines — same base, same rounds, same loopback host.
    b_stats, b_wall, b_phases, _b_stream, b_errors = run_arm(0)
    stats, wall, comm_phases, stream_info, errors = run_arm(chunk)
    if (
        errors
        or b_errors
        or stats.rounds_completed == 0
        # A zero-round barrier arm would publish ~0 barrier_* fields and
        # turn the A/B headline into an arbitrary speedup — fail loudly,
        # same as the streaming arm.
        or b_stats.rounds_completed == 0
    ):
        first = (errors or b_errors)[0] if (errors or b_errors) else None
        record = {
            "metric": "bench_error",
            "error": "controller_round_failed",
            "detail": str(first)[:300] if first else "no round completed",
        }
        _emit(record)
        return record
    lat = stats.promotion_latency_s
    record = {
        "metric": f"controller_rounds_per_hour_c{n_clients}",
        "value": round(stats.rounds_completed / wall * 3600.0, 1),
        "unit": "rounds/hour",
        # Orchestration efficiency: round-engine wall over full cycle wall
        # (1.0 = the control plane adds nothing on top of the rounds).
        "vs_baseline": round(
            stats.round_wall_s / max(stats.cycle_wall_s, 1e-9), 3
        ),
        "baseline_note": "fraction of unattended-cycle wall inside the "
        "round engine itself (reference: no unattended loop exists)",
        "promotion_latency_ms": round(float(np.mean(lat)) * 1e3, 2)
        if lat
        else None,
        "promotions": stats.promotions,
        "gate_rejections": stats.gate_rejections,
        "rounds": stats.rounds_completed,
        "param_mb": param_mb,
        # The round engine's measured comm/compute breakdown (obs layer:
        # AggregationServer.phase_seconds) — wait (accept + straggler +
        # upload wire), agg (aggregation compute), reply (fan-out) —
        # machine-parsed so the driver tracks where round wall goes.
        "comm_phase_wait_s": round(comm_phases.get("wait", 0.0), 4),
        "comm_phase_agg_s": round(comm_phases.get("agg", 0.0), 4),
        "comm_phase_reply_s": round(comm_phases.get("reply", 0.0), 4),
        # Round pipelining (ISSUE 5): overlapped-vs-exposed fold
        # attribution + aggregation-state peak from the streaming arm,
        # and the barrier arm's wait/agg on the same run as the A/B.
        "comm_overlap_frac": round(stream_info["overlap_frac"], 4),
        "server_peak_agg_bytes": int(stream_info["peak_agg_bytes"]),
        # The LAST (fully streamed) round's aggregation-state peak —
        # O(model + in-flight leaves); the cross-round max above still
        # carries the dense first round's O(clients x model).
        "server_round_peak_agg_bytes": int(
            stream_info["last_round_peak_bytes"]
        ),
        "stream_uploads": int(stream_info["stream_uploads"]),
        "stream_chunk_bytes": chunk,
        "barrier_comm_phase_wait_s": round(b_phases.get("wait", 0.0), 4),
        "barrier_comm_phase_agg_s": round(b_phases.get("agg", 0.0), 4),
        "barrier_wall_s": round(b_wall, 3),
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def _run_controller_fleet(
    registry, base, rounds, n_clients, eval_fn, errors,
    *, stream_chunk_bytes: int = 0,
):
    """One controller campaign over an in-process TCP fleet; returns
    (ControllerStats, wall seconds, round-engine phase seconds, streaming
    fold stats — overlap_frac/peak_agg_bytes/stream_uploads)."""
    import threading

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        AggregationServer,
        FederatedClient,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ControlConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.control import (
        Controller,
    )

    with AggregationServer(
        port=0, num_clients=n_clients, timeout=120,
        stream_chunk_bytes=stream_chunk_bytes,
    ) as server:
        controller = Controller(
            server,
            registry,
            eval_fn,
            control=ControlConfig(round_deadline_s=60.0),
        )

        def client_loop(cid: int) -> None:
            try:
                fc = FederatedClient(
                    "127.0.0.1", server.port, client_id=cid, timeout=120
                )
                cur = base
                for _ in range(rounds):
                    upload = {
                        k: v + np.float32(0.001 * (cid + 1))
                        for k, v in cur.items()
                    }
                    cur = fc.exchange(upload)
            except Exception as e:
                errors.append(e)

        threads = [
            threading.Thread(target=client_loop, args=(c,), daemon=True)
            for c in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        stats = controller.run(max_rounds=rounds)
        wall = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=30)
        comm_phases = dict(server.phase_seconds)
        stream_info = {
            "overlap_frac": server.comm_overlap_frac(),
            "peak_agg_bytes": server.stream_totals["peak_agg_bytes"],
            "last_round_peak_bytes": server.stream_totals[
                "last_round_peak_bytes"
            ],
            "stream_uploads": server.stream_totals["stream_uploads"],
        }
    return stats, wall, comm_phases, stream_info


def _fleet_chaos_arm() -> dict:
    """The fleet bench's chaos arm (ISSUE 14): a depth-2 tree with ONE
    relay killed mid-round by the seeded dead-relay fault plan
    (faults/deadrelay.py — a throttling FaultProxy fronts the victim's
    subtree and tears the relay down once the forwarded upload bytes
    cross the seeded threshold). The victim's clients re-home to the
    surviving relay (ranked fallback parents), the root completes a
    DEGRADED round over the surviving subtree within its deadline, and
    the aggregate must be crc-bit-exact vs ``aggregate_tree`` replayed
    over the ROOT's recorded actual (relay -> contributors) assignment.
    Returns the fleet record's chaos fields (or ``{"error": ...}``)."""
    import threading as _threading

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        AggregationServer,
        FederatedClient,
        RelayAggregator,
        aggregate_tree,
        wire,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults import (
        DeadRelayFault,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults.deadrelay import (
        wait_registered,
    )

    n_clients, half = 8, 4
    root_deadline = float(os.environ.get("BENCH_CHAOS_DEADLINE", "8"))
    rehome_budget = 2.0
    # Zero-hung-rounds bound: the acceptance contract — the degraded
    # round must resolve within root-deadline + one re-home dial budget
    # (slack for thread scheduling).
    hang_bound = root_deadline + rehome_budget + 4.0
    rng = np.random.default_rng(1)
    uploads = [
        {
            f"w{j}": rng.normal(size=4096).astype(np.float32)
            for j in range(4)
        }
        for _ in range(n_clients)
    ]
    victims = list(range(half, n_clients))
    results: dict[int, dict] = {}
    rehomes: dict[int, dict] = {}
    errors: list = []
    root_agg: list = [None]
    t0 = time.perf_counter()
    try:
        with AggregationServer(
            port=0, num_clients=2, min_clients=1, weighted=True,
            timeout=60, stream_chunk_bytes=1 << 15,
        ) as root:
            relays = [
                RelayAggregator(
                    "127.0.0.1", 0, parent_host="127.0.0.1",
                    parent_port=root.port, relay_id=r, num_clients=half,
                    timeout=60, stream_chunk_bytes=1 << 15,
                )
                for r in range(2)
            ]
            fault = DeadRelayFault(relays[1], seed=0)
            try:
                def root_loop() -> None:
                    try:
                        root_agg[0] = root.serve_round(
                            deadline=root_deadline
                        )
                    except RuntimeError as e:
                        errors.append(e)

                rt = _threading.Thread(target=root_loop, daemon=True)
                rt.start()
                for rel in relays:
                    _threading.Thread(
                        target=rel.serve, args=(1,), daemon=True
                    ).start()

                def client_loop(cid: int) -> None:
                    victim = cid in victims
                    fc = FederatedClient(
                        fault.host if victim else "127.0.0.1",
                        fault.port if victim else relays[0].port,
                        client_id=cid, timeout=30,
                        fallback_parents=(
                            [("127.0.0.1", relays[0].port)]
                            if victim
                            else None
                        ),
                        rehome_dial_budget=rehome_budget,
                    )
                    try:
                        results[cid] = fc.exchange(
                            uploads[cid], n_samples=cid + 1,
                            max_retries=3,
                        )
                        rehomes[cid] = dict(fc.rehomes)
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)

                vt = [
                    _threading.Thread(
                        target=client_loop, args=(c,), daemon=True
                    )
                    for c in victims
                ]
                for t in vt:
                    t.start()
                # Deterministic ordering: the surviving relay's own
                # clients hold their uploads until the kill landed and
                # the re-homed uploads registered there — the adoption
                # window stays open.
                fault.killed.wait(timeout=hang_bound)
                wait_registered(
                    relays[0].server, victims, timeout=hang_bound
                )
                st = [
                    _threading.Thread(
                        target=client_loop, args=(c,), daemon=True
                    )
                    for c in range(half)
                ]
                for t in st:
                    t.start()
                for t in vt + st:
                    t.join(timeout=hang_bound + 30)
                rt.join(timeout=hang_bound + 30)
            finally:
                fault.close()
                for rel in relays:
                    rel.close()
            assignment = root.last_assignment
            subtree_failures = root.tree_totals["subtree_failures"]
            degraded_rounds = root.tree_totals["degraded_rounds"]
    except Exception as e:  # noqa: BLE001 - one parseable line
        return {"error": f"{type(e).__name__}: {e}"}
    wall = time.perf_counter() - t0
    if root_agg[0] is None or assignment is None:
        return {
            "error": (
                f"degraded round failed: {errors[0]}"
                if errors
                else "degraded round produced no aggregate"
            )
        }
    want = aggregate_tree(
        uploads,
        [float(c + 1) for c in range(n_clients)],
        assignment["groups"],
    )
    crc_exact = wire.flat_crc32(root_agg[0]) == wire.flat_crc32(want)
    rehomes_total = sum(sum(r.values()) for r in rehomes.values())
    completed = {c for c in results}
    degraded_ok = (
        crc_exact
        and degraded_rounds >= 1
        and subtree_failures >= 1
        and rehomes_total >= len(victims)
        and completed == set(range(n_clients))
        and wall <= hang_bound + 30  # joins bound it; belt + braces
    )
    return {
        "fleet_rehomes_total": int(rehomes_total),
        "fleet_subtree_failures": int(subtree_failures),
        "fleet_degraded_rounds_ok": 1.0 if degraded_ok else 0.0,
        "fleet_chaos_crc_exact": 1.0 if crc_exact else 0.0,
        "fleet_chaos_wall_s": round(wall, 3),
        "fleet_chaos_assignment": assignment["groups"],
    }


def bench_fleet() -> dict | None:
    """Fleet-scale rounds (ISSUE 7): a LIVE loopback depth-2 fold tree —
    BENCH_FLEET_CLIENTS simulated clients (default 64) behind
    BENCH_FLEET_RELAYS relays (default 8) behind one weighted root, every
    hop chunk-streamed both ways (uploads AND replies). Headline fields
    (asserted present by the train-mode headline, exit 3):
    ``fleet_rounds_per_hour`` — full-fleet round cadence including the
    relay forward hop — and ``relay_peak_agg_bytes`` — the worst relay's
    aggregation-state peak, the O(model + in-flight) bound that replaces
    the flat tier's O(clients x model). ``fleet_crc_exact`` pins the
    root aggregate bit-exact against aggregate_tree's replay of the
    captured uploads (the PR 5/6 crc contract at depth 2)."""
    import threading as _threading

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        AggregationServer,
        FederatedClient,
        RelayAggregator,
        aggregate_tree,
        wire,
    )

    n_clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "64"))
    n_relays = int(os.environ.get("BENCH_FLEET_RELAYS", "8"))
    rounds = int(os.environ.get("BENCH_FLEET_ROUNDS", "2"))
    param_mb = float(os.environ.get("BENCH_FLEET_PARAM_MB", "1"))
    per = max(1, n_clients // n_relays)
    n_clients = per * n_relays
    n_leaves = 16
    leaf_elems = max(1, int(param_mb * 1e6 / 4 / n_leaves))
    rng = np.random.default_rng(0)
    base = {
        f"w{i:02d}": rng.normal(size=leaf_elems).astype(np.float32)
        for i in range(n_leaves)
    }
    chunk = max(64 << 10, int(param_mb * (1 << 20)) // 8)
    groups = [list(range(r * per, (r + 1) * per)) for r in range(n_relays)]
    uploads = [
        {k: v + np.float32(0.001 * (cid + 1)) for k, v in base.items()}
        for cid in range(n_clients)
    ]
    errors: list[Exception] = []
    root_aggs: list[dict] = []
    replies: dict[int, dict] = {}
    try:
        with AggregationServer(
            port=0, num_clients=n_relays, weighted=True, timeout=120,
            stream_chunk_bytes=chunk,
        ) as root:
            relays = [
                RelayAggregator(
                    "127.0.0.1", 0, parent_host="127.0.0.1",
                    parent_port=root.port, relay_id=r, num_clients=per,
                    timeout=120, stream_chunk_bytes=chunk,
                )
                for r in range(n_relays)
            ]
            try:
                def root_loop():
                    for _ in range(rounds):
                        try:
                            root_aggs.append(root.serve_round())
                        except RuntimeError as e:
                            errors.append(e)

                rt = _threading.Thread(target=root_loop, daemon=True)
                rt.start()
                for rel in relays:
                    _threading.Thread(
                        target=rel.serve, args=(rounds,), daemon=True
                    ).start()
                clients = [
                    FederatedClient(
                        "127.0.0.1", relays[cid // per].port,
                        client_id=cid, timeout=120,
                    )
                    for cid in range(n_clients)
                ]

                def client_loop(cid: int) -> None:
                    try:
                        for _ in range(rounds):
                            replies[cid] = clients[cid].exchange(
                                uploads[cid]
                            )
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)

                t0 = time.perf_counter()
                cthreads = [
                    _threading.Thread(
                        target=client_loop, args=(c,), daemon=True
                    )
                    for c in range(n_clients)
                ]
                for t in cthreads:
                    t.start()
                for t in cthreads:
                    t.join(timeout=240)
                rt.join(timeout=60)
                wall = time.perf_counter() - t0
                relay_peak = max(
                    rel.server.stream_totals["peak_agg_bytes"]
                    for rel in relays
                )
                stream_replies = root.stream_totals[
                    "stream_replies"
                ] + sum(
                    rel.server.stream_totals["stream_replies"]
                    for rel in relays
                )
            finally:
                for rel in relays:
                    rel.close()
            root_peak = root.stream_totals["peak_agg_bytes"]
    except Exception as e:  # noqa: BLE001 - one parseable line, not a dump
        errors.append(e)
        wall = 1.0
    if errors or len(root_aggs) < rounds or len(replies) < n_clients:
        record = {
            "metric": "bench_error",
            "error": "fleet_round_failed",
            "detail": (
                str(errors[0])[:300]
                if errors
                else f"{len(root_aggs)}/{rounds} rounds, "
                f"{len(replies)}/{n_clients} clients completed"
            ),
        }
        _emit(record)
        return record
    want = aggregate_tree(uploads, None, groups)
    want_crc = wire.flat_crc32(want)
    crc_ok = wire.flat_crc32(root_aggs[-1]) == want_crc and all(
        wire.flat_crc32(replies[c]) == want_crc for c in replies
    )
    # Chaos arm (ISSUE 14): one relay killed mid-round; the round must
    # complete over re-homed + surviving contributors, crc-exact vs the
    # recorded actual assignment, with no hung round.
    chaos = _fleet_chaos_arm()
    if chaos.get("error"):
        record = {
            "metric": "bench_error",
            "error": "fleet_chaos_failed",
            "detail": str(chaos["error"])[:300],
        }
        _emit(record)
        return record
    record = {
        "metric": f"fleet_rounds_per_hour_c{n_clients}_r{n_relays}",
        "value": round(rounds / wall * 3600.0, 1),
        "unit": "rounds/hour",
        # Scale headroom vs the flat tier's connection ceiling: clients
        # terminated per process at depth 2 vs flat (lower is better for
        # the root; vs_baseline is the fan-in reduction factor).
        "vs_baseline": round(n_clients / n_relays, 2),
        "baseline_note": "fan-in reduction at the root vs the flat "
        "single-server tier (which terminates every client itself)",
        "fleet_rounds_per_hour": round(rounds / wall * 3600.0, 1),
        "relay_peak_agg_bytes": int(relay_peak),
        "root_peak_agg_bytes": int(root_peak),
        "fleet_crc_exact": 1.0 if crc_ok else 0.0,
        "fleet_clients": n_clients,
        "fleet_relays": n_relays,
        "tree_depth": 2,
        "rounds": rounds,
        "param_mb": param_mb,
        "stream_replies": int(stream_replies),
        "wall_s": round(wall, 3),
        **chaos,
    }
    _emit(record)
    return record


def _wire_fleet_arm(
    *,
    wire_dtype: str = "fp32",
    upward_topk: float | None = None,
    n_clients: int = 64,
    n_relays: int = 8,
    rounds: int = 2,
    param_mb: float = 1.0,
) -> dict:
    """One wire-efficiency A/B arm: a live loopback depth-2 tree
    (bench_fleet's shape) driven ROUND-BY-ROUND so per-round byte counts
    are exact — clients all land round r before round r+1 starts.
    Returns walls, per-round client-upload and relay-upward bytes, the
    final replies/root aggregate, and the inputs the caller replays."""
    import threading as _threading

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        AggregationServer,
        FederatedClient,
        RelayAggregator,
    )

    per = max(1, n_clients // n_relays)
    n_clients = per * n_relays
    n_leaves = 16
    leaf_elems = max(1, int(param_mb * 1e6 / 4 / n_leaves))
    rng = np.random.default_rng(0)
    base = {
        f"w{i:02d}": rng.normal(size=leaf_elems).astype(np.float32)
        for i in range(n_leaves)
    }
    chunk = max(64 << 10, int(param_mb * (1 << 20)) // 8)
    groups = [list(range(r * per, (r + 1) * per)) for r in range(n_relays)]
    uploads = [
        {k: v + np.float32(0.001 * (cid + 1)) for k, v in base.items()}
        for cid in range(n_clients)
    ]
    errors: list[Exception] = []
    root_aggs: list[dict] = []
    replies: dict[int, dict] = {}
    round_walls: list[float] = []
    up_bytes_by_round: list[int] = []
    client_bytes_by_round: list[int] = []
    with AggregationServer(
        port=0, num_clients=n_relays, weighted=True, timeout=120,
        stream_chunk_bytes=chunk,
    ) as root:
        relays = [
            RelayAggregator(
                "127.0.0.1", 0, parent_host="127.0.0.1",
                parent_port=root.port, relay_id=r, num_clients=per,
                timeout=120, stream_chunk_bytes=chunk,
                upward_topk=upward_topk,
            )
            for r in range(n_relays)
        ]
        try:
            def root_loop():
                for _ in range(rounds):
                    try:
                        root_aggs.append(root.serve_round())
                    except RuntimeError as e:
                        errors.append(e)

            rt = _threading.Thread(target=root_loop, daemon=True)
            rt.start()
            for rel in relays:
                _threading.Thread(
                    target=rel.serve, args=(rounds,), daemon=True
                ).start()
            clients = [
                FederatedClient(
                    "127.0.0.1", relays[cid // per].port,
                    client_id=cid, timeout=120, wire_dtype=wire_dtype,
                )
                for cid in range(n_clients)
            ]

            def one(cid: int) -> None:
                try:
                    replies[cid] = clients[cid].exchange(uploads[cid])
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            up_prev = 0
            for _ in range(rounds):
                t0 = time.perf_counter()
                ths = [
                    _threading.Thread(target=one, args=(c,), daemon=True)
                    for c in range(n_clients)
                ]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join(timeout=240)
                round_walls.append(time.perf_counter() - t0)
                up_now = sum(rel.upward_bytes for rel in relays)
                up_bytes_by_round.append(up_now - up_prev)
                up_prev = up_now
                client_bytes_by_round.append(
                    sum(c.last_upload_bytes for c in clients)
                )
            rt.join(timeout=60)
        finally:
            for rel in relays:
                rel.close()
    return {
        "errors": errors,
        "uploads": uploads,
        "groups": groups,
        "root_aggs": root_aggs,
        "replies": replies,
        "round_walls": round_walls,
        "up_bytes_by_round": up_bytes_by_round,
        "client_bytes_by_round": client_bytes_by_round,
        "last_wire_dtypes": {c.client_id: c.last_wire_dtype for c in clients},
        "fold_engine": root.stream_totals.get("fold_engine", ""),
        "n_clients": n_clients,
        "n_relays": n_relays,
    }


def _wire_fold_ab(
    k: int = 8, elems: int | None = None, reps: int = 3
) -> dict:
    """Compiled-vs-naive fold A/B in the out-of-cache regime the blocked
    engine exists for: K leaves large enough that the K-leaf working set
    exceeds the host's last-level cache. Best-of-reps per engine; both
    engines' outputs are asserted bit-identical (the crc contract)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import (
        fold,
    )

    elems = elems or int(os.environ.get("BENCH_WIRE_FOLD_ELEMS", str(1 << 24)))
    rng = np.random.default_rng(0)
    leaves = [
        rng.normal(size=elems).astype(np.float32) for _ in range(k)
    ]
    weights = [np.float32(1.0 / k)] * k
    folded_bytes = 4 * k * elems

    def best(engine: str) -> tuple[float, np.ndarray]:
        t_best, out = float("inf"), None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fold.fold_ordered(leaves, weights, engine=engine)
            t_best = min(t_best, time.perf_counter() - t0)
        return t_best, out

    t_naive, out_naive = best("naive")
    engine = fold.engine_name() if fold.engine_name() != "naive" else "blocked"
    t_fast, out_fast = best(engine)
    bit_exact = bool(np.array_equal(out_naive, out_fast))
    return {
        "fold_engine": engine,
        "fold_throughput_gbps": round(folded_bytes / t_fast / 1e9, 3),
        "fold_naive_gbps": round(folded_bytes / t_naive / 1e9, 3),
        "fold_speedup": round(t_naive / t_fast, 3),
        "fold_bit_exact": 1.0 if bit_exact else 0.0,
        "fold_k": k,
        "fold_elems": elems,
    }


def bench_wire() -> dict:
    """Wire efficiency (ISSUE 17): three live loopback fleet arms at 64
    clients / 8 relays — fp32-dense (today's wire, asserted bit-identical
    to aggregate_tree), int8-streamed (negotiated quantized uploads,
    crc-pinned against the deterministic dequantization replay), and
    sparse-upward (relays diff their subtree partial against the last
    root aggregate and send topk deltas up) — plus a compiled-vs-numpy
    fold A/B in the out-of-cache regime. Headline fields (asserted
    present by the train-mode headline, exit 3):
    ``relay_upward_bytes`` — the sparse arm's round-2 relay-to-root hop
    bytes — ``fold_throughput_gbps`` — the batched fold engine's rate —
    and ``wire_round_cadence_ratio`` — fp32 round wall over int8 round
    wall at equal fleet shape. Gates: >= 3x upload-byte reduction (int8
    vs fp32), >= 3x upward-hop reduction (sparse vs dense), >= 2x fold
    speedup, and every arm crc-exact."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        aggregate_tree,
        wire,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.quant import (
        dequantize_int8c,
        quantize_int8c,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import (
        fold,
    )

    n_clients = int(os.environ.get("BENCH_WIRE_CLIENTS", "64"))
    n_relays = int(os.environ.get("BENCH_WIRE_RELAYS", "8"))
    param_mb = float(os.environ.get("BENCH_WIRE_PARAM_MB", "1"))
    topk = float(os.environ.get("BENCH_WIRE_TOPK", "0.05"))
    try:
        arm_fp32 = _wire_fleet_arm(
            wire_dtype="fp32", n_clients=n_clients, n_relays=n_relays,
            param_mb=param_mb,
        )
        arm_int8 = _wire_fleet_arm(
            wire_dtype="int8", n_clients=n_clients, n_relays=n_relays,
            param_mb=param_mb,
        )
        arm_sparse = _wire_fleet_arm(
            upward_topk=topk, n_clients=n_clients, n_relays=n_relays,
            param_mb=param_mb,
        )
    except Exception as e:  # noqa: BLE001 - one parseable line, not a dump
        record = {
            "metric": "bench_error",
            "error": "wire_arm_failed",
            "detail": str(e)[:300],
        }
        _emit(record)
        return record
    for name, arm in (
        ("fp32", arm_fp32), ("int8", arm_int8), ("sparse", arm_sparse)
    ):
        if arm["errors"] or len(arm["root_aggs"]) < 2 or (
            len(arm["replies"]) < arm["n_clients"]
        ):
            record = {
                "metric": "bench_error",
                "error": f"wire_{name}_arm_failed",
                "detail": (
                    str(arm["errors"][0])[:300]
                    if arm["errors"]
                    else f"{len(arm['root_aggs'])}/2 rounds, "
                    f"{len(arm['replies'])}/{arm['n_clients']} clients"
                ),
            }
            _emit(record)
            return record

    # fp32 arm: bit-identical to today's fold — the aggregate_tree
    # replay of the raw uploads, the exact PR 5/6 contract.
    want_fp32 = aggregate_tree(arm_fp32["uploads"], None, arm_fp32["groups"])
    crc_fp32 = wire.flat_crc32(want_fp32)
    fp32_ok = wire.flat_crc32(arm_fp32["root_aggs"][-1]) == crc_fp32 and all(
        wire.flat_crc32(r) == crc_fp32 for r in arm_fp32["replies"].values()
    )
    # int8 arm round 2: every client upgraded (round 1 carried the
    # advert) and the fold equals the deterministic dequantization
    # replay — fleet_crc_exact extends to quantized rounds.
    int8_upgraded = all(
        d == "int8" for d in arm_int8["last_wire_dtypes"].values()
    )
    rt_uploads = [
        {
            k: dequantize_int8c(quantize_int8c(v), v.shape)
            for k, v in up.items()
        }
        for up in arm_int8["uploads"]
    ]
    crc_int8 = wire.flat_crc32(
        aggregate_tree(rt_uploads, None, arm_int8["groups"])
    )
    int8_ok = int8_upgraded and wire.flat_crc32(
        arm_int8["root_aggs"][-1]
    ) == crc_int8
    # Sparse arm round 2: every relay sent topk(partial - base); the
    # root reconstructed base + densify per relay and folded by mass.
    # Replay with the same fold arithmetic (uniform subtrees: the
    # normalized weight is exactly 1/n_relays in fp32).
    base_agg = arm_sparse["root_aggs"][0]
    partials = [
        aggregate_tree(
            [arm_sparse["uploads"][c] for c in g], None, [list(range(len(g)))]
        )
        for g in arm_sparse["groups"]
    ]
    w_r = [np.float32(1.0 / len(partials))] * len(partials)
    expected_sparse = {}
    for key in sorted(base_agg):
        b = np.asarray(base_agg[key], np.float32)
        recon = []
        for p in partials:
            d = np.asarray(p[key], np.float32) - b
            recon.append(
                b + wire.densify_topk(wire.sparsify_topk(d, topk), d.shape)
            )
        expected_sparse[key] = fold.fold_ordered(recon, w_r)
    sparse_ok = wire.flat_crc32(arm_sparse["root_aggs"][-1]) == (
        wire.flat_crc32(expected_sparse)
    )

    upload_fp32 = arm_fp32["client_bytes_by_round"][-1]
    upload_int8 = arm_int8["client_bytes_by_round"][-1]
    upload_reduction = upload_fp32 / max(1, upload_int8)
    up_dense = arm_fp32["up_bytes_by_round"][-1]
    up_sparse = arm_sparse["up_bytes_by_round"][-1]
    upward_reduction = up_dense / max(1, up_sparse)
    cadence = arm_fp32["round_walls"][-1] / max(
        1e-9, arm_int8["round_walls"][-1]
    )
    fold_ab = _wire_fold_ab()
    record = {
        "metric": f"wire_upload_reduction_int8_vs_fp32_c{n_clients}",
        "value": round(upload_reduction, 2),
        "unit": "x",
        "vs_baseline": round(upload_reduction, 2),
        "baseline_note": "round-2 client upload bytes, fp32-dense arm "
        "over int8-streamed arm at equal fleet shape",
        "wire_dtype": "int8",
        "wire_upload_bytes_fp32": int(upload_fp32),
        "wire_upload_bytes_int8": int(upload_int8),
        "wire_upload_reduction": round(upload_reduction, 2),
        "relay_upward_bytes": int(up_sparse),
        "relay_upward_bytes_dense": int(up_dense),
        "relay_upward_reduction": round(upward_reduction, 2),
        "wire_round_cadence_ratio": round(cadence, 3),
        "wire_crc_exact": 1.0 if (fp32_ok and int8_ok and sparse_ok) else 0.0,
        "fleet_crc_exact": 1.0 if fp32_ok else 0.0,
        "wire_fp32_bit_identical": 1.0 if fp32_ok else 0.0,
        "wire_int8_upgraded_frac": (
            sum(
                1
                for d in arm_int8["last_wire_dtypes"].values()
                if d == "int8"
            )
            / arm_int8["n_clients"]
        ),
        "upward_topk": topk,
        "fleet_clients": n_clients,
        "fleet_relays": n_relays,
        "param_mb": param_mb,
        **fold_ab,
    }
    _emit(record)
    return record


def _wire_broken(rec: dict) -> bool:
    """The wire-efficiency acceptance gates (exit 3): >= 3x upload-byte
    reduction, >= 3x sparse upward-hop reduction, >= 2x fold speedup in
    the out-of-cache regime, every arm crc-exact, and the fold engines
    bit-identical."""
    return (
        rec.get("wire_crc_exact", 0.0) < 1.0
        or rec.get("fleet_crc_exact", 0.0) < 1.0
        or rec.get("wire_upload_reduction", 0.0) < 3.0
        or rec.get("relay_upward_reduction", 0.0) < 3.0
        or rec.get("fold_speedup", 0.0) < 2.0
        or rec.get("fold_bit_exact", 0.0) < 1.0
    )


def _router_worker(spec_json: str) -> None:
    """One serving-tier subprocess for bench_router's A/B arms — a
    scorer replica (``role: "replica"``) or the router itself
    (``role: "router"``). Subprocesses on purpose: the PRODUCTION fleet
    shape is separate ``infer-serve`` processes behind a separate
    ``fedtpu route`` process, one GIL each; in-process arms would
    serialize the whole tier's Python on the parent's GIL (and bias the
    A/B — the parent also runs the load generator). Forced-CPU like the
    clientdp child: a chip belongs to one process, the parent holds it,
    and a child that asked for it would fail or hang.
    Writes the bound port to the port-file, then parks until the parent
    terminates it."""
    jax.config.update("jax_platforms", "cpu")
    spec = json.loads(spec_json)
    if spec.get("role") == "router":
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router import (
            ScoringRouter,
        )

        server = ScoringRouter(
            [(h, p) for h, p in spec["backends"]],
            probe_interval_s=0.25,
        ).start()
    else:
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
            default_tokenizer,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
            get_dataset,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
            ModelRegistry,
        )
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router import (
            FleetReplica,
        )

        tok = default_tokenizer()
        registry = ModelRegistry(spec["registry"])
        info = registry.serving_info()
        manifest = registry.manifest(info["artifact"])
        model_cfg = ModelConfig(**manifest["model_config"])
        params = registry.load_params(info["artifact"])
        server = FleetReplica(
            int(spec["replica"]),
            model_cfg,
            params,
            tok,
            spec=get_dataset("cicids2017"),
            round_id=int(manifest.get("round", 1)),
            buckets=tuple(spec["buckets"]),
            max_queue=max(1024, 4 * max(spec["buckets"])),
        ).start()
    tmp = spec["port_file"] + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(server.port))
    os.replace(tmp, spec["port_file"])
    while True:  # parked; the parent terminates this process
        time.sleep(3600)


def _spawn_router_workers(specs, tmpdir, timeout_s=180):
    """Spawn one forced-CPU subprocess per worker spec; returns (procs,
    ports) once every child reported its bound port."""
    import subprocess

    procs = []
    for i, spec in enumerate(specs):
        spec["port_file"] = os.path.join(
            tmpdir, f"worker-{spec.get('role', 'replica')}-{i}.port"
        )
        try:
            # A stale file from an earlier arm's worker of the same name
            # would satisfy the wait below instantly with a DEAD port.
            os.remove(spec["port_file"])
        except OSError:
            pass
        env = {**os.environ, "BENCH_ROUTER_WORKER": json.dumps(spec)}
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                env=env,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
        )
    ports = []
    deadline = time.monotonic() + timeout_s
    for i, spec in enumerate(specs):
        while not os.path.exists(spec["port_file"]):
            if procs[i].poll() is not None or time.monotonic() > deadline:
                for p in procs:
                    p.terminate()
                raise RuntimeError(
                    f"worker subprocess {i} failed to come up "
                    f"(exit {procs[i].poll()})"
                )
            time.sleep(0.1)
        with open(spec["port_file"]) as f:
            ports.append(int(f.read().strip()))
    return procs, ports


def bench_router() -> dict | None:
    """Serving replica fleet (ISSUE 9): a live loopback A/B — ONE scorer
    replica driven directly vs BENCH_ROUTER_REPLICAS (default 3) behind
    the thin router (router/) — with a registry promotion fired MID-LOAD
    so the fleet's rolling hot-reload (drain one replica at a time,
    swap, readmit) runs under traffic.

    "Sustained QPS at a pinned p99 SLO" is measured the way the phrase
    means: each arm walks an OPEN-LOOP QPS ladder (run_load target_qps —
    requests fire on a fixed schedule regardless of replies, so queueing
    shows up as latency instead of sender self-throttling) and its
    sustained QPS is the highest rung it achieves with p99 <=
    BENCH_ROUTER_SLO_MS. A single scorer near capacity queues — its p99
    blows the SLO rungs below its raw throughput — while the fleet
    spreads the same offered load over N scorer processes; the ladder is
    anchored at the single arm's measured closed-loop capacity so the
    two arms climb identical rungs. Headline fields (asserted present by
    the train-mode headline, exit 3): ``router_qps_sustained`` — the
    fleet's highest in-SLO rung's achieved QPS — ``router_p99_ms`` — its
    p99 at that rung — and ``router_rolling_reload_dropped`` — requests
    that failed across the whole fleet run, **asserted == 0**: a
    promotion under load must complete without shedding a single request
    (the PR-3 ladder's zero-downtime deploy contract, measured).

    The tiny preset is the default on purpose: the router tier's win is
    fan-out of the per-request host work (framing, tokenize, dispatch
    bookkeeping) across scorer processes' threads — with a model small
    enough that compute doesn't serialize the arms on one shared
    accelerator, the A/B isolates exactly that. BENCH_ROUTER_PRESET=
    distilbert measures the flagship-model shape instead."""
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
        make_synthetic,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
        get_dataset,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
        ModelRegistry,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router import (
        FleetReplica,
        ServingFleet,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        run_load,
    )

    n_replicas = max(2, int(os.environ.get("BENCH_ROUTER_REPLICAS", "3")))
    concurrency = int(os.environ.get("BENCH_ROUTER_CONCURRENCY", "16"))
    requests = int(os.environ.get("BENCH_ROUTER_REQUESTS", "1024"))
    pipeline = int(os.environ.get("BENCH_ROUTER_PIPELINE", "4"))
    slo_ms = float(os.environ.get("BENCH_ROUTER_SLO_MS", "500"))
    target_qps = float(os.environ.get("BENCH_ROUTER_QPS", "0")) or None
    preset = os.environ.get("BENCH_ROUTER_PRESET", "tiny")
    tok = default_tokenizer()
    model_cfg = (
        ModelConfig.tiny(vocab_size=len(tok.vocab))
        if preset == "tiny"
        else ModelConfig(vocab_size=len(tok.vocab))
    )
    buckets = tuple(
        int(b)
        for b in os.environ.get("BENCH_ROUTER_BUCKETS", "1,8,32").split(",")
    )
    trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
    params1 = trainer.init_state(seed=0).params
    params2 = trainer.init_state(seed=1).params
    spec = get_dataset("cicids2017")
    texts = spec.render_texts(make_synthetic("cicids2017", 128, seed=0))

    def load(port, n_requests, qps=None):
        return run_load(
            "127.0.0.1",
            port,
            texts,
            concurrency=concurrency,
            requests=n_requests,
            pipeline=pipeline,
            target_qps=qps,
            timeout=120.0,
        )

    def climb_ladder(port, rungs):
        """Open-loop SLO search: walk the shared QPS rungs upward; the
        sustained point is the last rung whose measured p99 held the
        SLO. Returns (sustained stats | the first rung's stats, rung
        index or -1)."""
        best, best_i = None, -1
        for i, rung in enumerate(rungs):
            n = max(6 * concurrency, int(rung * 4))  # ~4 s per rung
            s = load(port, n, qps=rung)
            if best is None:
                best = s  # report the first rung even when out of SLO
            if s["p99_ms"] <= slo_ms and s["rejected"] == 0:
                best, best_i = s, i
            else:
                break
        return best, best_i

    try:
        root = tempfile.mkdtemp(prefix="bench-router-registry-")
        registry = ModelRegistry(root)
        aid1 = registry.add(params1, round_index=1, model_config=model_cfg)
        registry.promote(aid1, to="serving")

        # Arm A: ONE replica subprocess, driven directly (no router in
        # the path). Subprocesses on purpose — the production fleet
        # shape is separate scorer processes; see _router_worker.
        replica_spec = {"registry": root, "buckets": list(buckets)}
        procs, ports = _spawn_router_workers(
            [{**replica_spec, "replica": 0}], root
        )
        try:
            load(ports[0], 4 * concurrency)  # warm sockets + caches
            s_single_cap = load(ports[0], requests)
            # The shared ladder, anchored at the single arm's measured
            # closed-loop capacity: both arms climb identical rungs.
            cap = max(s_single_cap["flows_per_sec"], 4.0)
            rungs = [cap * f for f in (0.4, 0.7, 1.0, 1.4, 2.0, 2.8)]
            if target_qps is not None:
                rungs = [target_qps]  # operator-pinned single rung
            s_single, single_rung = climb_ladder(ports[0], rungs)
        finally:
            for p in procs:
                p.terminate()

        # Arm B: n replica subprocesses behind a ROUTER subprocess (its
        # own process, like `fedtpu route` — the parent keeps only the
        # load generator, exactly as in arm A), same rungs.
        procs, ports = _spawn_router_workers(
            [{**replica_spec, "replica": i} for i in range(n_replicas)],
            root,
        )
        rprocs, rports = _spawn_router_workers(
            [
                {
                    "role": "router",
                    "backends": [["127.0.0.1", p] for p in ports],
                }
            ],
            root,
        )
        try:
            load(rports[0], 4 * concurrency)  # warm
            s_fleet_cap = load(rports[0], requests)
            s_fleet_slo, fleet_rung = climb_ladder(rports[0], rungs)
        finally:
            for p in rprocs + procs:
                p.terminate()

        # Phase C: the zero-drop contract — the MANAGED in-process fleet
        # (fedtpu fleet's shape, where the manager can drive each
        # engine's hot-swap) under closed-loop load with a promotion
        # fired mid-run; every reject across the window is a drop.
        replicas = [
            FleetReplica(
                i, model_cfg, params1, tok, spec=spec, round_id=1,
                buckets=buckets, max_queue=max(1024, 4 * buckets[-1]),
            ).start()
            for i in range(n_replicas)
        ]
        fleet = ServingFleet(
            replicas,
            registry=registry,
            probe_interval_s=0.25,
            reload_poll_s=0.25,
            drain_timeout_s=30.0,
        ).start()
        errors: list[Exception] = []
        fleet_out: list[dict] = []
        try:
            load(fleet.port, 4 * concurrency)  # warm

            def fleet_load():
                try:
                    # The promotion races THIS closed-loop run (max
                    # pressure — the hardest time to not drop).
                    fleet_out.append(load(fleet.port, requests))
                except Exception as e:  # a dropped request IS the finding
                    errors.append(e)

            lt = threading.Thread(target=fleet_load, daemon=True)
            lt.start()
            # Fire the promotion once the load is demonstrably mid-run,
            # then let the manager's rolling sweep race live traffic.
            deadline = time.monotonic() + 60.0
            while (
                fleet.router.stats()["forwarded"] < requests // 4
                and lt.is_alive()
                and time.monotonic() < deadline
            ):
                time.sleep(0.05)
            aid2 = registry.add(
                params2, round_index=2, model_config=model_cfg
            )
            registry.promote(aid2, to="serving")
            lt.join(timeout=180.0)
            # The reload may outlive the load; trickle requests while it
            # finishes so zero-drop stays measured under traffic.
            trickle_dropped = 0
            deadline = time.monotonic() + 60.0
            while (
                fleet.stats()["reloads"] < 1
                and time.monotonic() < deadline
            ):
                t = load(fleet.port, concurrency)
                trickle_dropped += t["rejected"]
            rounds = [rep.round_id for rep in replicas]
        finally:
            fleet.close()
            import shutil

            shutil.rmtree(root, ignore_errors=True)
    except Exception as e:  # noqa: BLE001 - one parseable line, not a dump
        record = {
            "metric": "bench_error",
            "error": "router_ab_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    if errors or not fleet_out:
        record = {
            "metric": "bench_error",
            "error": "router_fleet_load_failed",
            "detail": (
                str(errors[0])[:300] if errors else "fleet load never ran"
            ),
        }
        _emit(record)
        return record
    s_reload = fleet_out[0]
    dropped = s_reload["rejected"] + trickle_dropped
    reload_ok = rounds == [2] * n_replicas
    record = {
        "metric": f"router_qps_{preset}_r{n_replicas}_c{concurrency}",
        "value": round(s_fleet_slo["flows_per_sec"], 2),
        "unit": "flows/sec",
        # The A/B itself: the fleet's sustained-in-SLO QPS over the
        # single replica's, on the identical open-loop rung ladder.
        "vs_baseline": round(
            s_fleet_slo["flows_per_sec"]
            / max(s_single["flows_per_sec"], 1e-9),
            2,
        ),
        "baseline_note": f"vs one replica driven directly: "
        f"{s_single['flows_per_sec']:.1f} flows/s sustained at p99 <= "
        f"{slo_ms:.0f} ms (rung {single_rung}); a promotion fired "
        "mid-load and rolling-reloaded under traffic",
        "router_qps_sustained": round(s_fleet_slo["flows_per_sec"], 2),
        "router_p99_ms": round(s_fleet_slo["p99_ms"], 2),
        "router_p99_slo_ms": slo_ms,
        "router_p99_within_slo": 1.0 if fleet_rung >= 0 else 0.0,
        "router_sustained_rung": fleet_rung,
        "router_rolling_reload_dropped": int(dropped),
        "router_reload_complete": 1.0 if reload_ok else 0.0,
        "router_single_qps": round(s_single["flows_per_sec"], 2),
        "router_single_p99_ms": round(s_single["p99_ms"], 2),
        "router_single_rung": single_rung,
        "router_fleet_capacity_qps": round(
            s_fleet_cap["flows_per_sec"], 2
        ),
        "router_single_capacity_qps": round(
            s_single_cap["flows_per_sec"], 2
        ),
        "router_replicas": n_replicas,
        "router_requests": requests,
        "router_pipeline": pipeline,
        # The A/B's physical precondition: the fleet arm runs
        # n_replicas + 1 extra processes — on a host with fewer cores
        # than that, the ratio reads contention, not the tier's scaling.
        "router_host_cpus": os.cpu_count(),
        "replica_rounds": rounds,
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def bench_scenario() -> dict | None:
    """Persona-matrix loopback sweep (ISSUE 6): the `fedtpu scenario`
    harness run small — a persona x partition matrix of LIVE TCP rounds
    with wire-level fault injection (faults/) — as a machine-parsed
    robustness record. Headline fields: ``scenario_rounds_ok_frac`` —
    the fraction of (cell, round) outcomes that succeeded over
    survivors; every cell is quorum-satisfiable by construction, so the
    driver asserts 1.0 (exit 3) — and ``scenario_straggler_wait_s`` —
    the worst per-round straggler wait the obs timeline attributed
    (the slow/intermittent personas' cost). ``scenario_crc_exact_frac``
    pins the bit-exact survivor-mean contract across the whole matrix."""
    import shutil
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults.scenario import (
        ScenarioConfig,
        contract_violations,
        run_matrix,
    )

    personas = tuple(
        p for p in os.environ.get(
            "BENCH_SCN_PERSONAS", "lazy,intermittent"
        ).split(",") if p
    )
    partitions = tuple(
        p for p in os.environ.get(
            "BENCH_SCN_PARTITIONS", "iid,dirichlet"
        ).split(",") if p
    )
    rounds = int(os.environ.get("BENCH_SCN_ROUNDS", "2"))
    cfg = ScenarioConfig(
        num_clients=int(os.environ.get("BENCH_SCN_CLIENTS", "3")),
        rounds=rounds,
        personas=personas,
        partitions=partitions,
        deadline_s=float(os.environ.get("BENCH_SCN_DEADLINE", "6")),
        payload_kb=int(os.environ.get("BENCH_SCN_PAYLOAD_KB", "64")),
    )
    out_dir = tempfile.mkdtemp(prefix="bench-scenario-")
    t0 = time.perf_counter()
    try:
        results, _grid = run_matrix(cfg, out_dir)
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "scenario_matrix_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    wall = time.perf_counter() - t0
    total = sum(len(r.rounds) for r in results)
    ok = sum(r.ok_rounds for r in results)
    exact = sum(r.exact_rounds for r in results)
    worst_wait = max(
        (o.straggler_wait_s for r in results for o in r.rounds),
        default=0.0,
    )
    violations = contract_violations(results)
    record = {
        "metric": f"scenario_matrix_c{cfg.num_clients}_"
        f"{len(results)}cells",
        "value": round(ok / max(total, 1), 4),
        "unit": "rounds_ok_frac",
        "vs_baseline": None,
        "baseline_note": "reference: no fault tolerance at all — one "
        "dead client hangs its accept loop until timeout "
        "(server.py:69-71)",
        "scenario_rounds_ok_frac": round(ok / max(total, 1), 4),
        "scenario_crc_exact_frac": round(exact / max(ok, 1), 4),
        "scenario_straggler_wait_s": round(worst_wait, 3),
        "cells": len(results),
        "rounds_per_cell": rounds,
        "personas": list(personas),
        "partitions": list(partitions),
        "violations": violations[:5],
        "wall_s": round(wall, 2),
    }
    _emit(record)
    return record


#: BENCH_MODE=strategy regression floor for the non-IID accuracy lift
#: in percentage points (best non-fedavg strategy's final-aggregate
#: accuracy minus the fedavg baseline's; ops/metrics.py reports
#: Accuracy on a 0-100 scale). Regime: Dirichlet alpha=0.1 at seed 5 —
#: a 3-client split where the big mixed-label shard sits on the LAZY
#: client (0.25 train scale) and a pure-one-class shard dominates the
#: honest fleet, so plain averaging stalls near chance while FedProx's
#: proximal anchor keeps the lazy client's updates usable. Measured on
#: this host (5 rounds, 3 clients, deterministic seeds): fedavg 48.44,
#: fedprox:mu=1.0 67.19 (+18.75), fedopt:adam,lr=0.1 and
#: headboost:gamma=2.0 48.44 (no lift in this regime). Pinned well
#: under the measured lead-candidate lift so only a real regression (a
#: strategy that stops helping at all) trips, not seed-local noise.
STRATEGY_LIFT_FLOOR = float(os.environ.get("BENCH_STRAT_LIFT_FLOOR", "5.0"))


def bench_strategy() -> dict | None:
    """Server aggregation strategy sweep (ISSUE 16): the `fedtpu
    scenario` harness with ``--train`` on its hardest cell — Dirichlet
    alpha=0.1 label skew with the lazy persona on client 0 — run once
    under the fedavg baseline and once per candidate strategy
    (strategies/), same seeds, same partitions, same faults. Headline
    fields: ``strategy_noniid_acc_lift`` — the best candidate's
    final-aggregate held-out accuracy minus fedavg's (the driver asserts
    >= STRATEGY_LIFT_FLOOR, exit 3: at least one non-FedAvg strategy
    must still beat plain averaging on the non-IID + lazy fleet) — and
    ``strategy_crc_exact`` — every successful round's transformed
    aggregate bit-exact against the strategy replay over the clean
    survivor mean (asserted 1.0: the pure-transform contract that lets
    the crc gates extend to every strategy)."""
    import shutil
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults.scenario import (
        ScenarioConfig,
        contract_violations,
        run_matrix,
    )

    specs = tuple(
        s for s in os.environ.get(
            "BENCH_STRAT_SPECS",
            "fedprox:mu=1.0;fedopt:opt=adam,lr=0.1;headboost:gamma=2.0",
        ).split(";") if s
    )
    rounds = int(os.environ.get("BENCH_STRAT_ROUNDS", "5"))
    cfg = ScenarioConfig(
        num_clients=int(os.environ.get("BENCH_STRAT_CLIENTS", "3")),
        rounds=rounds,
        personas=("lazy",),
        partitions=("dirichlet",),
        dirichlet_alpha=0.1,
        # Seed picks the partition: the default (5) is the measured
        # differentiating regime above — most seeds give all-or-nothing
        # shards where every strategy lands on the same constant
        # predictor and the lift is 0 by construction.
        seed=int(os.environ.get("BENCH_STRAT_SEED", "5")),
        deadline_s=float(os.environ.get("BENCH_STRAT_DEADLINE", "20")),
        auth_cell=False,
        train=True,
        strategies=specs,
    )
    out_dir = tempfile.mkdtemp(prefix="bench-strategy-")
    t0 = time.perf_counter()
    try:
        results, _grid = run_matrix(cfg, out_dir)
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "strategy_sweep_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    wall = time.perf_counter() - t0
    base = next(
        (r for r in results if r.spec.strategy == "fedavg"), None
    )
    candidates = [r for r in results if r.spec.strategy != "fedavg"]
    if base is None or base.accuracy is None or not candidates or all(
        r.accuracy is None for r in candidates
    ):
        record = {
            "metric": "bench_error",
            "error": "strategy_sweep_no_comparator",
            "detail": "fedavg baseline or candidate accuracy missing "
            f"(cells: {[r.spec.name for r in results]})",
        }
        _emit(record)
        return record
    accuracies = {
        r.spec.strategy: r.accuracy
        for r in results
        if r.accuracy is not None
    }
    best = max(
        (r for r in candidates if r.accuracy is not None),
        key=lambda r: r.accuracy,
    )
    lift = round(best.accuracy - base.accuracy, 4)
    total_ok = sum(r.ok_rounds for r in results)
    exact = sum(r.exact_rounds for r in results)
    violations = contract_violations(results)
    record = {
        "metric": f"strategy_noniid_sweep_{len(candidates)}cand",
        "value": lift,
        "unit": "acc_lift_vs_fedavg",
        "vs_baseline": None,
        "baseline_note": "fedavg baseline cell: same seeds/partition/"
        "persona, identity strategy — the reference server's only "
        "aggregation rule",
        "strategy_noniid_acc_lift": lift,
        "strategy_crc_exact": 1.0
        if total_ok > 0 and exact == total_ok and not violations
        else 0.0,
        "strategy_best": best.spec.strategy,
        "strategy_accuracies": accuracies,
        "fedavg_accuracy": base.accuracy,
        "strategy_rounds_ok": total_ok,
        "strategy_rounds_exact": exact,
        "rounds_per_cell": rounds,
        "dirichlet_alpha": cfg.dirichlet_alpha,
        "violations": violations[:5],
        "wall_s": round(wall, 2),
    }
    _emit(record)
    return record


def _measure_local_steps(trainer, model_cfg, batch_size, steps, warmup) -> float:
    """samples/sec of a client-local train step fed host batches — the TCP
    client's real per-batch flow (host numpy in, device_put inside the
    meshed step), identical for the single-device and meshed trainers so
    the A/B is placement-only."""
    state = trainer.init_state(seed=0)
    rng = np.random.default_rng(0)
    L = model_cfg.max_len
    host = {
        "input_ids": rng.integers(
            0, model_cfg.vocab_size, (batch_size, L)
        ).astype(np.int32),
        "attention_mask": np.ones((batch_size, L), np.int32),
        "labels": rng.integers(0, 2, batch_size).astype(np.int32),
    }
    for _ in range(warmup):
        state, loss = trainer.train_step(state, host)
    _sync(loss)
    repeats = max(1, int(os.environ.get("BENCH_REPEATS", "3")))
    dt = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = trainer.train_step(state, host)
        _sync(loss)
        window = time.perf_counter() - t0
        dt = window if dt is None else min(dt, window)
    return batch_size * steps / dt


def _virtual_cpu_respawn(
    mode: str, force_var: str, n: int, *, env_defaults: dict, timeout_var: str
) -> dict:
    """Capture a multi-device bench record from a forced-CPU subprocess
    over ``n`` virtual devices — the single-accelerator-host fallback
    shared by ``clientdp`` and ``fsdp``. When ``force_var`` is already
    set we ARE the child and the forcing failed: report, never re-spawn
    (an unbounded subprocess chain is the alternative). The child's last
    JSON stdout line is the record."""
    if os.environ.get(force_var):
        record = {
            "metric": "bench_error",
            "error": f"{mode}_needs_devices",
            "detail": f"forced-CPU child still sees "
            f"{len(jax.devices())} device(s) (< {n}); virtual-device "
            "forcing ineffective on this host",
        }
        _emit(record)
        return record
    import subprocess

    env = {
        **os.environ,
        "BENCH_MODE": mode,
        force_var: "1",
        "BENCH_SECONDARY": "0",
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip(),
    }
    for k, v in env_defaults.items():
        env.setdefault(k, v)
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True,
            text=True,
            env=env,
            timeout=int(os.environ.get(timeout_var, "600")),
        )
        line = [
            ln for ln in out.stdout.splitlines() if ln.startswith("{")
        ][-1]
        record = json.loads(line)
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": f"{mode}_subprocess_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
    _emit(record)
    return record


def bench_client_dp() -> dict | None:
    """The multi-chip TCP client's local phase (ISSUE 2 tentpole): the
    meshed client trainer at ``--data-parallel N`` vs the single-device
    engine on the same host — the speedup a cross-silo client with a full
    host of chips gains on the separate-process tier.

    Needs N local devices; on a single-accelerator host the record is
    captured from a subprocess over N virtual CPU devices instead (tiny
    model — it proves the path and records the A/B shape; a shared-core
    CPU ratio is NOT a hardware speedup claim, and the record says so)."""
    n = max(2, int(os.environ.get("BENCH_DATA_PARALLEL", "2")))
    if len(jax.devices()) < n:
        return _virtual_cpu_respawn(
            "clientdp",
            "BENCH_CLIENTDP_FORCE_CPU",
            n,
            env_defaults={
                "BENCH_CLIENTDP_PRESET": "tiny",
                "BENCH_BATCH": "16",
            },
            timeout_var="BENCH_CLIENTDP_TIMEOUT",
        )

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
        make_host_mesh,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.client_mesh import (
        MeshTrainer,
    )

    preset = os.environ.get("BENCH_CLIENTDP_PRESET", "distilbert")
    model_cfg = ModelConfig.tiny() if preset == "tiny" else ModelConfig()
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    if batch_size % n:
        batch_size += n - batch_size % n
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    train_cfg = TrainConfig(prng_impl=os.environ.get("BENCH_PRNG", "rbg"))
    sps_1 = _measure_local_steps(
        Trainer(model_cfg, train_cfg), model_cfg, batch_size, steps, warmup
    )
    sps_n = _measure_local_steps(
        MeshTrainer(model_cfg, train_cfg, mesh=make_host_mesh(n)),
        model_cfg,
        batch_size,
        steps,
        warmup,
    )
    virtual = jax.devices()[0].platform == "cpu"
    record = {
        "metric": f"client_dp_samples_per_sec_{preset}_n{n}_bs{batch_size}",
        "value": round(sps_n, 2),
        "unit": "samples/sec",
        # The client-local speedup itself: meshed vs single-device on the
        # SAME host (not the cross-machine reference ratio).
        "vs_baseline": round(sps_n / sps_1, 2),
        "baseline_note": (
            f"vs the single-device client's {sps_1:.1f} samples/s on this "
            "host"
            + (
                " (virtual CPU devices share the host cores: path/parity "
                "capture, not a hardware speedup)"
                if virtual
                else ""
            )
        ),
        "n1_samples_per_sec": round(sps_1, 2),
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def bench_fsdp() -> dict | None:
    """FSDP client mesh A/B (ISSUE 15 tentpole): the shard-at-rest
    trainer (`client --data-parallel N --fsdp`) vs the replicated meshed
    trainer at the SAME global batch on the same host mesh.

    Headline fields (asserted present by the train-mode headline,
    exit 3): ``fsdp_peak_param_opt_bytes_ratio`` — per-chip static-state
    bytes (params + Adam moments, exact addressable-shard accounting)
    sharded over replicated, asserted <= 0.6 on a >= 2-device mesh
    (ideal 1/N + the undividable-leaf remainder) and
    "unavailable"-graceful when no 2-device mesh exists;
    ``fsdp_step_time_ratio`` — FSDP step time over replicated at equal
    global batch, asserted <= 1.15 (the gather-at-use + backward
    re-gather + reduce-scatter budget); ``fsdp_crc_exact`` — the
    wire-exchange gather contract: adopt-aggregate (scatter onto
    shards) then host-gather must round-trip crc-bit-exact.

    Needs N local devices; on a single-accelerator host the record is
    captured from a subprocess over N virtual CPU devices (tiny model —
    proves the path and the byte/crc contracts; the CPU step ratio is a
    shared-core number, not a hardware claim, and the record says so)."""
    n = max(2, int(os.environ.get("BENCH_FSDP_SHARDS", "2")))
    if len(jax.devices()) < n:
        return _virtual_cpu_respawn(
            "fsdp",
            "BENCH_FSDP_FORCE_CPU",
            n,
            env_defaults={
                "BENCH_FSDP_PRESET": "tiny",
                "BENCH_BATCH": "16",
            },
            timeout_var="BENCH_FSDP_TIMEOUT",
        )

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        wire as _wire,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
        device_memory_stats,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
        device_tree_bytes,
        make_host_mesh,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.client_mesh import (
        FsdpMeshTrainer,
        MeshTrainer,
    )

    preset = os.environ.get("BENCH_FSDP_PRESET", "distilbert")
    model_cfg = ModelConfig.tiny() if preset == "tiny" else ModelConfig()
    batch_size = int(os.environ.get("BENCH_BATCH", "64"))
    if batch_size % n:
        batch_size += n - batch_size % n
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "5")))
    train_cfg = TrainConfig(prng_impl=os.environ.get("BENCH_PRNG", "rbg"))
    mesh = make_host_mesh(n)

    def _in_use_bytes() -> float | None:
        """Live device bytes RIGHT NOW (bytes_in_use, not the cumulative
        peak — earlier benches in the same process would contaminate a
        peak), or None on stats-less backends (CPU)."""
        stats = device_memory_stats()
        if stats is None:
            return None
        v = stats.get("bytes_in_use")
        return float(v) if v else None

    def _init_delta(before: float | None) -> float | None:
        """Bytes this arm's init actually allocated (after - before):
        the process baseline — compiled executables, constants, the
        OTHER arm's caches — subtracts out, so the cross-check ratio
        compares the two inits and not whatever else is resident."""
        after = _in_use_bytes()
        if before is None or after is None or after <= before:
            return None
        return after - before

    rep_base = _in_use_bytes()
    rep = MeshTrainer(model_cfg, train_cfg, mesh=mesh)
    rep_state = rep.init_state(seed=0)
    rep_bytes = device_tree_bytes((rep_state.params, rep_state.opt_state))
    rep_in_use = _init_delta(rep_base)
    del rep_state
    sps_rep = _measure_local_steps(rep, model_cfg, batch_size, steps, warmup)

    fsdp_base = _in_use_bytes()
    fsdp = FsdpMeshTrainer(model_cfg, train_cfg, mesh=mesh)
    fsdp_state = fsdp.init_state(seed=0)
    fsdp_bytes = device_tree_bytes(
        (fsdp_state.params, fsdp_state.opt_state)
    )
    fsdp_in_use = _init_delta(fsdp_base)
    # Wire-exchange gather contract: host-gather -> adopt (scatter onto
    # shards, fresh sharded Adam) -> host-gather must be crc-bit-exact —
    # the invariant that lets secure-agg/DP/streamed uploads compose
    # with sharding unchanged. host_params returns DEVICE-backed shards
    # (the lazy pack-time gather); materialize to numpy first so the
    # adopt below exercises the real host->shard scatter instead of
    # round-tripping the same device buffers.
    host = jax.tree.map(np.asarray, fsdp.host_params(fsdp_state))
    crc0 = _wire.flat_crc32(_wire.flatten_params(host))
    adopted = fsdp.adopt_aggregate(fsdp_state, host)
    crc1 = _wire.flat_crc32(_wire.flatten_params(fsdp.host_params(adopted)))
    del fsdp_state, adopted, host
    sps_fsdp = _measure_local_steps(fsdp, model_cfg, batch_size, steps, warmup)

    virtual = jax.devices()[0].platform == "cpu"
    record = {
        "metric": f"fsdp_samples_per_sec_{preset}_n{n}_bs{batch_size}",
        "value": round(sps_fsdp, 2),
        "unit": "samples/sec",
        # The cost of sharding itself: FSDP vs replicated on the SAME
        # mesh (not the cross-tier reference ratio).
        "vs_baseline": round(sps_fsdp / sps_rep, 4),
        "baseline_note": (
            f"vs the replicated meshed trainer's {sps_rep:.1f} samples/s "
            "at equal global batch"
            + (
                " (virtual CPU devices share the host cores: path/"
                "contract capture, not a hardware claim)"
                if virtual
                else ""
            )
        ),
        "fsdp_shards": n,
        "fsdp_step_time_ratio": round(sps_rep / sps_fsdp, 4),
        "fsdp_peak_param_opt_bytes_ratio": (
            round(fsdp_bytes / rep_bytes, 4) if rep_bytes else "unavailable"
        ),
        "fsdp_static_bytes_sharded": int(fsdp_bytes),
        "fsdp_static_bytes_replicated": int(rep_bytes),
        "fsdp_crc_exact": 1.0 if crc0 == crc1 else 0.0,
        # Measured watermark cross-check: each arm's init-allocation
        # DELTA (bytes_in_use after minus before that arm's init — the
        # resident baseline, incl. the other arm's executables/caches,
        # subtracts out): "unavailable" on stats-less backends (CPU);
        # the shard-byte ratio above is the exact accounting either way.
        "fsdp_device_bytes_in_use_ratio": (
            round(fsdp_in_use / rep_in_use, 4)
            if fsdp_in_use and rep_in_use
            else "unavailable"
        ),
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def _fsdp_broken(rec: dict) -> bool:
    """The exit-3 contract shared by BENCH_MODE=fsdp and the train-mode
    headline: static state must actually shard (<= 0.6 per chip at
    N >= 2), the step-time price must stay inside the gather budget
    (<= 1.15x replicated on real accelerators), and the wire-exchange
    gather must round-trip crc-bit-exact. An "unavailable" bytes ratio
    (no 2-device mesh) skips that one check only. The virtual-CPU
    record's step gate is 1.25x: shared-core memcpy collectives measure
    ~1.0x there (so 1.25 still catches the forward-replay regression
    class, a whole-loss remat measuring ~1.3x+), but the cores are
    co-tenant and a hardware-grade 1.15 would flake on healthy code —
    the record's own baseline_note disclaims the CPU ratio as a
    hardware claim."""
    ratio = rec.get("fsdp_peak_param_opt_bytes_ratio")
    if isinstance(ratio, (int, float)) and ratio > 0.6:
        return True
    step_bound = 1.25 if rec.get("device") == "cpu" else 1.15
    step_ratio = rec.get("fsdp_step_time_ratio")
    if not isinstance(step_ratio, (int, float)) or step_ratio > step_bound:
        return True
    return rec.get("fsdp_crc_exact", 0.0) < 1.0


def bench_serve_fsdp() -> dict | None:
    """Sharded scorer A/B (ISSUE 20 tentpole): the FSDP predict path
    (``infer-serve --data-parallel N --fsdp``) vs the replicated engine
    from the SAME init params on the same host.

    Headline fields (asserted present by the train-mode headline,
    exit 3): ``serve_fsdp_static_bytes_ratio`` — per-chip at-rest param
    bytes sharded over replicated (exact addressable-shard accounting),
    asserted <= 0.6 at N = 2; ``serve_fsdp_crc_exact`` — served
    probabilities AND per-class softmax bit-identical to the replicated
    engine across the whole bucket ladder including pad-row shapes (the
    gather-at-use constraint must be a pure layout annotation, never a
    numeric change); ``serve_reload_recompiles`` — bucket-path retraces
    across warmup + a mid-load rolling reload (swap while a scorer
    thread hammers warm buckets), asserted 0: ``fsdp_spec`` is shape-
    deterministic, so the swapped params land on the exact layout every
    warm program was compiled for.

    Needs N local devices; on a single-accelerator host the record is
    captured from a subprocess over N virtual CPU devices (tiny model —
    proves the byte/crc/recompile contracts; throughput there is a
    shared-core number, not a hardware claim, and the record says so)."""
    n = max(2, int(os.environ.get("BENCH_SERVE_FSDP_SHARDS", "2")))
    if len(jax.devices()) < n:
        return _virtual_cpu_respawn(
            "serve",
            "BENCH_SERVE_FSDP_FORCE_CPU",
            n,
            env_defaults={"BENCH_SERVE_PRESET": "tiny"},
            timeout_var="BENCH_SERVE_FSDP_TIMEOUT",
        )

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.serving import (
        _parse_buckets,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
        device_tree_bytes,
        make_host_mesh,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        ScoreEngine,
    )

    preset = os.environ.get("BENCH_SERVE_PRESET", "distilbert")
    tok = default_tokenizer()
    model_cfg = (
        ModelConfig.tiny(vocab_size=len(tok.vocab))
        if preset == "tiny"
        else ModelConfig(vocab_size=len(tok.vocab))
    )
    buckets = _parse_buckets(os.environ.get("BENCH_SERVE_BUCKETS", "1,8,32"))
    trainer = Trainer(model_cfg, TrainConfig())
    # Host-side tree so BOTH engines pay a fresh placement (replicated
    # device_put vs scatter onto fsdp_spec shards) from identical bytes.
    params = jax.tree.map(np.asarray, trainer.init_state(seed=0).params)
    rep = ScoreEngine(model_cfg, params, pad_id=tok.pad_id, buckets=buckets)
    shard = ScoreEngine(
        model_cfg,
        params,
        pad_id=tok.pad_id,
        buckets=buckets,
        mesh=make_host_mesh(n),
    )
    # Exact at-rest accounting: addressable shard bytes of the lowest-id
    # device (ideal 1/N plus the undividable-leaf remainder).
    rep_bytes = device_tree_bytes(rep.snapshot()[0])
    shard_bytes = device_tree_bytes(shard.snapshot()[0])
    rep.warmup()
    shard.warmup()
    # Bit-identity across the bucket ladder, including pad-row shapes
    # (n < bucket) and the n == 1 / n == largest-bucket edges.
    rng = np.random.default_rng(0)
    sizes = sorted({1, *buckets, max(1, buckets[-1] - 1)})
    crc_exact = 1.0
    for rows in sizes:
        ids = rng.integers(
            1,
            model_cfg.vocab_size,
            size=(rows, model_cfg.max_len),
            dtype=np.int32,
        )
        mask = np.ones_like(ids)
        mask[:, model_cfg.max_len // 2:] = 0  # ragged lengths
        p0, cp0, _, _ = rep.score(ids, mask)
        p1, cp1, _, _ = shard.score(ids, mask)
        if not (np.array_equal(p0, p1) and np.array_equal(cp0, cp1)):
            crc_exact = 0.0
    # Mid-load rolling reload: a scorer thread hammers warm buckets
    # while the main thread swaps new params in (the engine-level
    # drain→swap the fleet tier's rolling_reload drives per replica).
    # The sharded ledger must stay at 0 recompiles throughout.
    stop = threading.Event()
    scored = {"batches": 0}
    load_rows = min(8, buckets[-1])
    ids = rng.integers(
        1,
        model_cfg.vocab_size,
        size=(load_rows, model_cfg.max_len),
        dtype=np.int32,
    )
    mask = np.ones_like(ids)

    def _load() -> None:
        while not stop.is_set():
            shard.score(ids, mask)
            shard.score(ids[:1], mask[:1])
            scored["batches"] += 2

    scorer = threading.Thread(target=_load, daemon=True)
    t0 = time.monotonic()
    scorer.start()
    swapped = jax.tree.map(
        lambda a: np.asarray(a) + np.float32(1e-3), params
    )
    for rid in range(1, 4):
        time.sleep(0.05)
        shard.swap(swapped if rid % 2 else params, round_id=rid)
    time.sleep(0.05)
    stop.set()
    scorer.join(timeout=60.0)
    elapsed = time.monotonic() - t0
    recompiles = len(shard.ledger.recompiles())
    virtual = jax.devices()[0].platform == "cpu"
    record = {
        "metric": f"serve_fsdp_flows_per_sec_{preset}_n{n}",
        "value": round(scored["batches"] * (load_rows + 1) / 2 / elapsed, 2)
        if elapsed
        else 0.0,
        "unit": "flows/sec",
        "baseline_note": (
            "sharded engine under mid-reload load; contract fields are "
            "the headline"
            + (
                " (virtual CPU devices share the host cores: path/"
                "contract capture, not a hardware claim)"
                if virtual
                else ""
            )
        ),
        "serve_fsdp_shards": n,
        "serve_fsdp_static_bytes_ratio": (
            round(shard_bytes / rep_bytes, 4) if rep_bytes else "unavailable"
        ),
        "serve_fsdp_static_bytes_sharded": int(shard_bytes),
        "serve_fsdp_static_bytes_replicated": int(rep_bytes),
        "serve_fsdp_crc_exact": crc_exact,
        "serve_reload_recompiles": recompiles,
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def _serve_fsdp_broken(rec: dict) -> bool:
    """The exit-3 contract shared by BENCH_MODE=serve and the train-mode
    headline: at-rest param bytes must actually shard (<= 0.6 per chip
    at N >= 2; "unavailable" skips that one check), served probs must be
    bit-identical to the replicated engine, and the bucket ladder must
    survive warmup + a mid-load rolling reload with 0 retraces."""
    ratio = rec.get("serve_fsdp_static_bytes_ratio")
    if isinstance(ratio, (int, float)) and ratio > 0.6:
        return True
    if rec.get("serve_fsdp_crc_exact", 0.0) < 1.0:
        return True
    return rec.get("serve_reload_recompiles", 1) != 0


def _watchdog(seconds: int, record: dict) -> threading.Timer:
    """Hard deadline that fires even while the main thread is blocked inside
    an XLA C++ call — a SIGALRM handler would wait for the interpreter to
    regain control, i.e. forever. The timer thread emits the diagnostic
    JSON and hard-exits 2."""

    def fire():
        _emit(record)
        sys.stdout.flush()
        os._exit(2)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


MODES = (
    "train", "bert", "bertlarge", "eval", "fedavg", "flash", "ring",
    "fed2", "fedseq", "serve", "clientdp", "controller", "scenario",
    "fleet", "check", "router", "obs", "profile", "shadow", "fsdp",
    "strategy", "wire", "labels", "sentinel",
)


def bench_shadow() -> dict | None:
    """Shadow evaluation plane (ISSUE 13): a live loopback run of the
    whole disagreement-gated promotion path — router under closed-loop
    load, the traffic mirror armed, and TWO gated candidates: one that
    agrees with the incumbent on live traffic (promotes through the
    gate, rolling-reloads the fleet) and one that demonstrably regresses
    (every mirrored pair flips: REJECTED, the pointer never moves, the
    registry event records the measured verdict).

    Headline fields (asserted present by the train-mode headline,
    exit 3): ``shadow_pairs_total`` — mirrored pairs accumulated across
    both gates (each asserted >= the gate's min_pairs: the promotion was
    GATED on live evidence, not a rubber stamp); ``shadow_gate_verdicts``
    — gate decisions rendered (asserted 2: one promote, one reject);
    ``shadow_added_p99_ms`` — the mirror-armed arm's client-observed p99
    minus the mirror-off arm's, asserted ~0 (the fire-and-forget
    contract: mirroring must not ride the serving path), with
    ``shadow_live_dropped`` — live requests rejected across every arm —
    asserted 0.

    The regressed candidate is constructed, not trained: the incumbent's
    params with the classifier bias slammed to [+10, -10], which drives
    P(attack) to ~0 on every flow a ~0.5-scoring incumbent serves — a
    deterministic 100% flip rate, so the reject arm can never flake.

    BENCH_SHADOW_SAMPLE defaults to 8 (mirror 1 in 8), the production
    shape: the added-p99 contract is about the MIRROR staying off the
    serving path, and on a core-starved host a 100% mirror would read
    the shadow replica's own scoring as serving contention —
    ``shadow_host_cpus`` is recorded for exactly that caveat, like the
    router A/B's."""
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
        wire as _wire,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
        make_synthetic,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
        get_dataset,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
        ModelRegistry,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router import (
        FleetReplica,
        ServingFleet,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        run_load,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.shadow import (
        ShadowGate,
        read_status,
    )

    n_replicas = max(2, int(os.environ.get("BENCH_SHADOW_REPLICAS", "2")))
    concurrency = int(os.environ.get("BENCH_SHADOW_CONCURRENCY", "8"))
    requests = int(os.environ.get("BENCH_SHADOW_REQUESTS", "256"))
    min_pairs = int(os.environ.get("BENCH_SHADOW_PAIRS", "64"))
    sample = max(1, int(os.environ.get("BENCH_SHADOW_SAMPLE", "8")))
    p99_slack_ms = float(os.environ.get("BENCH_SHADOW_P99_SLACK_MS", "50"))
    tok = default_tokenizer()
    model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
    trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
    params1 = trainer.init_state(seed=0).params
    flat = _wire.flatten_params(params1)
    # Agreeing candidate: one leaf nudged by 1e-6 — a distinct artifact
    # id whose scores are indistinguishable on live traffic.
    agree = dict(flat)
    k0 = sorted(agree)[0]
    agree[k0] = np.asarray(agree[k0]) + np.float32(1e-6)
    params_agree = _wire.unflatten_params(agree)
    # Regressing candidate: classifier bias slammed so P(attack) ~ 0.
    bad = dict(flat)
    bad["classifier/bias"] = np.asarray([10.0, -10.0], np.float32)
    params_bad = _wire.unflatten_params(bad)
    spec = get_dataset("cicids2017")
    texts = spec.render_texts(make_synthetic("cicids2017", 64, seed=0))

    def load(port, n):
        return run_load(
            "127.0.0.1", port, texts, concurrency=concurrency,
            requests=n, pipeline=4, timeout=120.0,
        )

    try:
        root = tempfile.mkdtemp(prefix="bench-shadow-registry-")
        registry = ModelRegistry(root)
        aid1 = registry.add(params1, round_index=1, model_config=model_cfg)
        registry.promote(aid1, to="serving")
        replicas = [
            FleetReplica(
                i, model_cfg, params1, tok, spec=spec, round_id=1,
                buckets=(1, 8), max_queue=1024,
            ).start()
            for i in range(n_replicas)
        ]

        def shadow_factory(s_params, *, round_id):
            return FleetReplica(
                n_replicas, model_cfg, s_params, tok, spec=spec,
                round_id=round_id, buckets=(1, 8), max_queue=1024,
            ).start()

        fleet = ServingFleet(
            replicas,
            registry=registry,
            probe_interval_s=0.25,
            reload_poll_s=0.1,
            shadow_factory=shadow_factory,
            shadow_sample=sample,
        ).start()
        dropped = 0
        verdicts = 0
        pairs_total = 0
        p99_reps = max(1, int(os.environ.get("BENCH_SHADOW_P99_REPS", "3")))
        try:
            load(fleet.port, 4 * concurrency)  # warm sockets + buckets

            def p99_arm():
                """Min-of-N p99: on a single-core loopback host a lone
                p99 sample swings 3-5x on scheduler noise (which only
                ever ADDS latency) — the minimum over a few short runs
                is the stable estimate of each arm's intrinsic tail."""
                best = None
                drops = 0
                for _ in range(p99_reps):
                    s = load(fleet.port, requests)
                    drops += s["rejected"]
                    if best is None or s["p99_ms"] < best["p99_ms"]:
                        best = s
                return best, drops

            # Arm A: mirror OFF (nothing in the shadow state).
            s_off, d = p99_arm()
            dropped += d

            def wait_armed(aid, timeout=30.0):
                deadline = time.monotonic() + timeout
                while fleet.stats()["shadow_artifact"] != aid:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"shadow plane never armed for {aid}"
                        )
                    time.sleep(0.05)

            def drive_gate(aid):
                """Closed-loop load until the gate rules on live pairs."""
                out: dict = {}
                stop = threading.Event()

                def loader():
                    while not stop.is_set():
                        s = load(fleet.port, requests)
                        out["rejected"] = (
                            out.get("rejected", 0) + s["rejected"]
                        )
                        out.setdefault("arms", []).append(s)

                lt = threading.Thread(target=loader, daemon=True)
                lt.start()
                try:
                    gate = ShadowGate(
                        root, min_pairs=min_pairs, timeout_s=120.0,
                        poll_s=0.1,
                    )
                    ok, verdict = gate.wait(aid)
                finally:
                    stop.set()
                    lt.join(timeout=180.0)
                return ok, verdict, out

            # Arm B: the AGREEING candidate — mirror armed, gate passes,
            # promotion rolling-reloads the fleet under the same load.
            aid2 = registry.add(
                params_agree, round_index=2, model_config=model_cfg
            )
            registry.promote(aid2, to="shadow")
            wait_armed(aid2)
            s_on, d = p99_arm()
            dropped += d
            ok_agree, v_agree, out_agree = drive_gate(aid2)
            verdicts += 1
            pairs_total += int(v_agree.get("pairs") or 0)
            dropped += out_agree.get("rejected", 0)
            if ok_agree:
                registry.promote(aid2, to="serving")
            deadline = time.monotonic() + 60.0
            while (
                fleet.stats()["reloads"] < 1
                and time.monotonic() < deadline
            ):
                t = load(fleet.port, concurrency)
                dropped += t["rejected"]
            promoted_ok = (
                ok_agree
                and registry.serving_info()["artifact"] == aid2
                and fleet.stats()["reloads"] >= 1
            )
            # Arm C: the REGRESSED candidate — every pair flips; the
            # gate fails closed, the pointer stays on aid2, the verdict
            # rides the registry event.
            aid3 = registry.add(
                params_bad, round_index=3, model_config=model_cfg
            )
            registry.promote(aid3, to="shadow")
            wait_armed(aid3)
            ok_bad, v_bad, out_bad = drive_gate(aid3)
            verdicts += 1
            pairs_total += int(v_bad.get("pairs") or 0)
            dropped += out_bad.get("rejected", 0)
            if not ok_bad:
                registry.reject(
                    aid3, reason=v_bad["reason"], verdict=v_bad
                )
            held_out = (
                not ok_bad
                and registry.serving_info()["artifact"] == aid2
                and registry.manifest(aid3)["state"] == "rejected"
            )
            status_bad = read_status(root, aid3)
        finally:
            fleet.close()
            for rep in replicas:
                rep.close()
            import shutil

            shutil.rmtree(root, ignore_errors=True)
    except Exception as e:  # noqa: BLE001 - one parseable line, not a dump
        record = {
            "metric": "bench_error",
            "error": "shadow_plane_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    added_p99 = s_on["p99_ms"] - s_off["p99_ms"]
    record = {
        "metric": f"shadow_gate_r{n_replicas}_c{concurrency}",
        "value": round(added_p99, 3),
        "unit": "added_p99_ms",
        "vs_baseline": round(
            s_on["p99_ms"] / max(s_off["p99_ms"], 1e-9), 3
        ),
        "baseline_note": "mirror-armed arm p99 vs the mirror-off arm on "
        "the same closed-loop load; two candidates gated on live "
        "mirrored pairs (agree -> promoted+rolling-reloaded, regressed "
        "-> rejected with the verdict on the registry event)",
        "shadow_pairs_total": pairs_total,
        "shadow_gate_verdicts": verdicts,
        "shadow_added_p99_ms": round(added_p99, 3),
        "shadow_p99_off_ms": round(s_off["p99_ms"], 3),
        "shadow_p99_on_ms": round(s_on["p99_ms"], 3),
        "shadow_p99_slack_ms": p99_slack_ms,
        "shadow_live_dropped": int(dropped),
        "shadow_min_pairs": min_pairs,
        "shadow_promoted": 1.0 if promoted_ok else 0.0,
        "shadow_rejected_held_out": 1.0 if held_out else 0.0,
        "shadow_reject_flip_rate": (
            round(float(v_bad.get("flip_rate") or 0.0), 4)
        ),
        "shadow_reject_psi": (
            status_bad.get("psi") if status_bad else None
        ),
        "shadow_sample": sample,
        "shadow_replicas": n_replicas,
        # The added-p99 caveat's physical precondition: with fewer cores
        # than replicas + shadow + loadgen, the delta reads host
        # contention from the shadow replica's own scoring, not
        # serving-path cost (the mirror is still off the serving path).
        "shadow_host_cpus": os.cpu_count(),
        "device": jax.devices()[0].device_kind,
    }
    _emit(record)
    return record


def shadow_broken(rec: dict) -> bool:
    """The exit-3 contract shared by BENCH_MODE=shadow and the train-
    mode headline: the promotion must be GATED on >= min_pairs live
    pairs, zero live requests dropped, the regressed candidate held out
    of serving, and the mirror's added p99 inside the slack (vs the
    mirror-off arm — approximately zero on any healthy host)."""
    return (
        rec.get("shadow_gate_verdicts", 0) < 2
        or rec.get("shadow_pairs_total", 0) < 2 * rec.get(
            "shadow_min_pairs", 1
        )
        or rec.get("shadow_live_dropped", 1) > 0
        or rec.get("shadow_promoted", 0.0) < 1.0
        or rec.get("shadow_rejected_held_out", 0.0) < 1.0
        or rec.get("shadow_added_p99_ms", 1e9) > max(
            rec.get("shadow_p99_slack_ms", 50.0),
            0.5 * rec.get("shadow_p99_off_ms", 0.0),
        )
    )


def bench_profile() -> dict | None:
    """The device performance plane (ISSUE 12): one run_profile_session
    over the REAL flagship train step — compile ledger with recompile
    flagging, fenced host/dispatch/device step attribution, memory
    watermarks, the analytic-vs-XLA FLOPs cross-check, and the bucketed
    serving path's zero-recompile storm.

    Headline fields (asserted present by the train-mode headline,
    exit 3): ``profile_compile_count`` — session compiles across every
    ledger site; ``profile_recompiles`` — new-signature-at-warm-site
    events, the shape-leak detector (train sites may legitimately see
    warm-up shapes; the SERVING path's ``profile_serving_recompiles``
    is asserted 0 — the bucket ladder makes a recompile a bug);
    ``profile_step_device_ms_p50`` — sampled device-execute median;
    ``profile_peak_device_bytes`` — the high-water memory watermark
    (0 on backends without memory_stats, with
    ``profile_memory_available`` saying which case you're in). The
    XLA-vs-analytic ``profile_flops_ratio`` is pinned inside
    FLOPS_RATIO_TOLERANCE whenever the backend exposes a cost model —
    the MFU headline's analytic FLOPs, anchored to what XLA built.

    BENCH_PROFILE_PRESET=tiny swaps the tiny config in for quick local
    runs; batch/prng default to the headline bench's own so the profile
    session and the dense headline share one compiled program."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        TrainConfig as _TrainConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
        run_profile_session,
    )

    preset = os.environ.get("BENCH_PROFILE_PRESET", "distilbert")
    presets = {
        "tiny": ModelConfig.tiny,
        "distilbert": ModelConfig,
        "bert": ModelConfig.bert_base,
        "bertlarge": ModelConfig.bert_large,
    }
    if preset not in presets:  # loud, like the BENCH_MODE validation —
        # a typo must not silently profile the wrong model under a
        # healthy-looking record
        raise SystemExit(
            f"unknown BENCH_PROFILE_PRESET {preset!r} "
            f"({'|'.join(presets)})"
        )
    model_cfg = presets[preset]()
    batch = int(
        os.environ.get(
            "BENCH_PROFILE_BATCH", os.environ.get("BENCH_BATCH", "64")
        )
    )
    steps = int(os.environ.get("BENCH_PROFILE_STEPS", "8"))
    stride = int(os.environ.get("BENCH_PROFILE_STRIDE", "2"))
    t0 = time.perf_counter()
    try:
        rep = run_profile_session(
            model_cfg,
            _TrainConfig(prng_impl=os.environ.get("BENCH_PRNG", "rbg")),
            steps=steps,
            batch_size=batch,
            stride=stride,
        )
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "profile_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    dt = time.perf_counter() - t0
    step = rep.get("step") or {}
    device = step.get("device") or {}
    host = step.get("host") or {}
    dispatch = step.get("dispatch") or {}
    srv = rep.get("serving") or {}
    mem_available = any(
        v.get("available") for v in (rep.get("memory") or {}).values()
    )
    record = {
        "metric": "profile_plane",
        "value": round(device.get("p50", 0.0) * 1e3, 3),
        "unit": "ms/step-device-p50",
        "device": jax.devices()[0].device_kind,
        "profile_compile_count": rep["compile_count"],
        "profile_recompiles": len(rep["recompiles"]),
        "profile_step_device_ms_p50": round(
            device.get("p50", 0.0) * 1e3, 3
        ),
        "profile_step_device_ms_p95": round(
            device.get("p95", 0.0) * 1e3, 3
        ),
        "profile_step_host_ms_p50": round(host.get("p50", 0.0) * 1e3, 3),
        "profile_step_dispatch_ms_p50": round(
            dispatch.get("p50", 0.0) * 1e3, 3
        ),
        "profile_peak_device_bytes": int(rep["peak_device_bytes"]),
        "profile_memory_available": 1 if mem_available else 0,
        "profile_flops_analytic": rep["flops_analytic"],
        "profile_flops_xla": rep["flops_xla"],
        "profile_flops_ratio": rep["flops_ratio"],
        "profile_serving_compiles": srv.get("compiles", 0),
        "profile_serving_recompiles": srv.get("recompiles", -1),
        "profile_sites": {
            k: v["compiles"] for k, v in rep["sites"].items()
        },
        "profile_runtime_s": round(dt, 2),
    }
    _emit(record)
    return record


def _profile_broken(rec: dict) -> bool:
    """The profile record's exit-3 contract: the bucketed serving path
    must not recompile, and the XLA-vs-analytic FLOPs ratio must sit
    inside FLOPS_RATIO_TOLERANCE whenever the backend reported one."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
        flops_ratio_ok,
    )

    return rec["profile_serving_recompiles"] != 0 or not flops_ratio_ok(
        rec["profile_flops_ratio"]
    )


def bench_obs() -> dict:
    """The fleet health plane (ISSUE 11): a LIVE loopback round campaign
    run under the scrape hub — the server exports /metrics.json, the hub
    polls it, and the burn-rate machinery judges it end to end.

    The demo drives the full alert lifecycle on real wire traffic:
    (1) a deliberately slow round breaches the round-duration SLO and
    FIRES the burn alert; (2) a quorum-missed round trips the flight
    recorder into a postmortem bundle; (3) fast healthy rounds drain the
    short burn window and CLEAR the alert. Headline fields (asserted
    present in train mode, exit 3): ``slo_alerts_fired`` (>= 1 or the
    obs mode exits 3), ``postmortem_bundles`` (>= 1), and
    ``obs_scrape_lag_ms`` — the hub's worst per-target /metrics.json
    scrape latency, the health plane's own cost."""
    import shutil
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.client import (
        FederatedClient,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.server import (
        AggregationServer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
        SLO,
        FlightRecorder,
        MetricsServer,
        ScrapeHub,
        Target,
        Tracer,
        list_bundles,
        set_global_recorder,
    )

    # The SLO bound sits on the round histogram's 1.0 s bucket edge. A
    # loopback round's wall is dominated by the server's accept-loop
    # poll granularity (it notices "all uploads in" up to min(1 s,
    # remaining-deadline) late), so healthy rounds run under a 0.6 s
    # deadline (wall ~0.7 s, inside the bound) and the slow round adds
    # a 1 s client sleep under the full timeout (wall ~2 s, past it).
    slow_s = float(os.environ.get("BENCH_OBS_SLOW_S", "1.0"))
    le = float(os.environ.get("BENCH_OBS_SLO_LE", "1.0"))
    out_dir = tempfile.mkdtemp(prefix="bench-obs-")
    t_bench0 = time.perf_counter()
    server = msrv = None
    try:
        events = os.path.join(out_dir, "server.jsonl")
        flight_dir = os.path.join(out_dir, "flight")
        tracer = Tracer(events, proc="server")
        recorder = FlightRecorder(
            flight_dir, proc="server", tracer=tracer, min_interval_s=0.0
        )
        set_global_recorder(recorder)
        server = AggregationServer(
            port=0, num_clients=2, timeout=30, tracer=tracer
        )
        msrv = MetricsServer(0, host="127.0.0.1").start()
        slo = SLO(
            name="round-duration",
            metric="fedtpu_server_round_seconds",
            kind="latency",
            le=le,
            objective=0.9,
            # Short demo windows: fire on the slow round, clear once
            # one second of healthy rounds drains the short window.
            windows=((8.0, 2.0), (1.0, 2.0)),
        )
        hub = ScrapeHub(
            [Target("serve", "127.0.0.1", msrv.port, events_jsonl=events)],
            slos=(slo,),
            alerts_jsonl=os.path.join(out_dir, "alerts.jsonl"),
            snapshot_jsonl=os.path.join(out_dir, "fleet.jsonl"),
            tracer=tracer,
        )

        def run_round(
            delay_s: float = 0.0,
            clients: int = 2,
            deadline: float | None = 0.6,
        ) -> None:
            errs: list = []

            def srv() -> None:
                try:
                    server.serve_round(deadline=deadline)
                except RuntimeError:
                    pass  # the quorum-miss round fails BY DESIGN

            def cli(cid: int) -> None:
                try:
                    time.sleep(delay_s)
                    fc = FederatedClient(
                        "127.0.0.1", server.port, client_id=cid, timeout=10
                    )
                    fc.exchange(
                        {"w": np.full(64, cid + 1.0, np.float32)},
                        n_samples=10,
                    )
                except Exception as e:  # the failed round's client dies
                    errs.append(e)

            st = threading.Thread(target=srv)
            cts = [
                threading.Thread(target=cli, args=(c,))
                for c in range(clients)
            ]
            st.start()
            for t in cts:
                t.start()
            for t in cts:
                t.join(timeout=30)
            st.join(timeout=30)
            if errs and clients == 2:
                # A HEALTHY round's client died: the downstream
                # fire/clear choreography would fail confusingly on the
                # clear assertion — report the real cause instead.
                raise RuntimeError(
                    f"healthy-round client failed: {errs[0]!r}"
                )

        hub.poll()  # burn baseline
        # Slow round under the FULL timeout: the client sleep + the
        # accept-loop's 1 s completion poll put the wall past le.
        run_round(delay_s=slow_s, deadline=None)
        fire_events = hub.poll()["events"]
        # Quorum miss -> flight-recorder bundle. ZERO clients connect:
        # a partial fleet would retry into (and pollute) the healthy
        # rounds below — an empty round fails identically and cleanly.
        run_round(clients=0, deadline=0.5)
        hub.poll()  # base point for the short window's clear delta
        run_round()  # two healthy rounds drain the short window
        run_round()
        time.sleep(1.1)
        clear_events = hub.poll()["events"]
        lag_ms = hub.last_scrape_lag_ms
        bundles = list_bundles(flight_dir)
        record = {
            "metric": "obs_health_plane",
            "value": hub.alerts.fired_total,
            "unit": "alerts_fired",
            "vs_baseline": None,
            "baseline_note": "reference: no operational visibility at "
            "all (timestamped prints; nothing watches anything)",
            "slo_alerts_fired": hub.alerts.fired_total,
            "slo_alerts_cleared": hub.alerts.cleared_total,
            "postmortem_bundles": len(bundles),
            "obs_scrape_lag_ms": lag_ms,
            "obs_polls": hub.polls,
            "fired_on_poll": bool(
                any(e["event"] == "fire" for e in fire_events)
            ),
            "cleared_on_poll": bool(
                any(e["event"] == "clear" for e in clear_events)
            ),
            "bundle_reasons": sorted({b["reason"] for b in bundles}),
            "wall_s": round(time.perf_counter() - t_bench0, 2),
        }
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "obs_health_plane_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
    finally:
        set_global_recorder(None)
        if server is not None:
            server.close()
        if msrv is not None:
            msrv.close()
        shutil.rmtree(out_dir, ignore_errors=True)
    _emit(record)
    return record


def bench_check() -> dict:
    """The static-analysis gate (ISSUE 8): `fedtpu check` over this
    tree with the reviewed baseline. Headline fields:
    ``check_findings_new`` — non-baselined findings, asserted 0 (exit 3:
    an invariant regression must fail the bench exactly like a broken
    crc contract, not scroll past) — and ``check_runtime_s`` — the full
    four-pass scan wall, the cost of running the gate in CI."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.analysis import (
        run_check,
    )

    root = os.path.dirname(os.path.abspath(__file__))
    try:
        result = run_check(root)
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "check_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    record = {
        "metric": "check",
        "value": len(result.new),
        "unit": "new_findings",
        "check_findings_new": len(result.new),
        "check_runtime_s": round(result.runtime_s, 3),
        "check_findings_baselined": len(result.baselined),
        "check_findings_allowed": result.allowed,
        "check_modules_scanned": result.modules_scanned,
        "check_new": [f.render() for f in result.new[:20]],
    }
    _emit(record)
    return record

def bench_labels() -> dict:
    """Delayed ground-truth plane (ISSUE 18): three arms over the
    labels/ journal + join + supervised gate, all pure host arithmetic
    (no accelerator beyond the CPU backend the K-class arm's metric
    kernels run on).

    Arm 1 — supervised reject: a candidate that flips under the
    unsupervised gate's flip-rate budget (clean PSI, ``evaluate_status``
    PASSES) but whose every flip is serving-right -> candidate-WRONG
    against the journal. The flip-rate/PSI rung would promote it; the
    label gate must measure the error regression and refuse.

    Arm 2 — coverage fail-closed: the same pairs joined against a
    journal that covers almost none of them. A verdict over three flows
    out of four hundred is noise; the gate must refuse on the coverage
    floor, not rule.

    Arm 3 — K-class bit-identity: the K = 2 route of the class-counts
    data plane (``class_counts``/``finalize_class_metrics``) must render
    a metrics dict crc-identical to the binary path's on the same
    logits — the K-class generalization cannot move the binary numbers.

    Headline fields (asserted present by the train-mode headline,
    exit 3): ``labels_supervised_reject`` + ``labels_unsupervised_pass``
    (the arm-1 pincer: BOTH must be 1.0 — a reject the unsupervised
    rung would also have made proves nothing),
    ``labels_coverage_fail_closed``, and ``labels_kclass_crc_exact``."""
    import shutil
    import tempfile
    import zlib

    import jax.numpy as jnp

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.labels import (
        LabelGate,
        LabelStore,
        journal_path,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.metrics import (
        binary_counts,
        class_counts,
        finalize_class_metrics,
        finalize_metrics,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.shadow.compare import (
        ShadowCompare,
        evaluate_status,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.shadow.gate import (
        pairs_path,
    )

    n_pairs = int(os.environ.get("BENCH_LABELS_PAIRS", "400"))
    # Flip budget chosen UNDER the unsupervised gate's 2% default: the
    # candidate must pass flip-rate/PSI and still be caught supervised.
    n_flips = max(1, n_pairs // 64)
    tmp = tempfile.mkdtemp(prefix="fedtpu-bench-labels-")
    t0 = time.perf_counter()
    try:
        rng = np.random.default_rng(1808)
        aid = "cand-bench"
        compare = ShadowCompare(
            threshold=0.5, pairs_jsonl=pairs_path(tmp, aid)
        )
        # Alternating benign/attack truth; serving always on the correct
        # side of the threshold (jitter never crosses 0.5).
        truth = (np.arange(n_pairs) % 2).astype(np.int64)
        serving = np.where(truth == 1, 0.9, 0.1) + rng.uniform(
            -0.05, 0.05, n_pairs
        )
        # The candidate flips n_flips attack flows to benign — each one
        # a serving-correct -> candidate-wrong decision — and agrees
        # everywhere else.
        cand = serving.copy()
        flip_rows = [2 * i + 1 for i in range(n_flips)]
        for i in flip_rows:
            cand[i] = 0.08
        for i in range(n_pairs):
            compare.register_rid(i, f"r{i}")
            compare.note_serving(i, float(serving[i]))
            compare.note_shadow(i, float(cand[i]))
        unsup_ok, unsup_reason = evaluate_status(
            compare.snapshot(),
            min_pairs=min(100, n_pairs),
            max_flip_rate=0.02,
            psi_threshold=0.25,
        )
        snap = compare.snapshot()

        # Arm 1: journal covering 75% of the scored flows (delayed
        # labels are always partial), every flip row inside the covered
        # prefix; the supervised rung must measure the regression.
        store = LabelStore(journal_path(tmp))
        n_labeled = int(n_pairs * 0.75)
        for i in range(n_labeled):
            store.ingest(f"r{i}", int(truth[i]), ts=float(i))
        store.advance_watermark(float(n_labeled))
        sup_ok, sup = LabelGate(
            tmp, min_joined=64, coverage_floor=0.05, max_regression=0.0
        ).evaluate(aid)
        supervised_reject = (not sup_ok) and (
            "regression" in sup.get("reason", "")
        )

        # Arm 2: a journal that labels 8 of the same 400 pairs —
        # coverage 2% under the 5% floor. min_joined is satisfied, so
        # the refusal is the coverage clause, nothing else.
        sparse_journal = os.path.join(tmp, "labels", "sparse.jsonl")
        store_b = LabelStore(sparse_journal)
        for i in range(min(8, n_pairs)):
            store_b.ingest(f"r{i}", int(truth[i]), ts=float(i))
        cov_ok, cov = LabelGate(
            tmp,
            journal=sparse_journal,
            min_joined=4,
            coverage_floor=0.05,
            max_regression=0.0,
        ).evaluate(aid)
        coverage_fail_closed = (not cov_ok) and (
            "coverage" in cov.get("reason", "")
        )

        # Arm 3: K = 2 class-counts path vs the binary path, same
        # seeded logits — the rendered metric dicts must be crc-equal.
        n = 512
        logits = jnp.asarray(
            rng.normal(size=(n, 2)).astype(np.float32)
        )
        y = jnp.asarray(rng.integers(0, 2, size=n).astype(np.int32))
        loss = jnp.asarray(np.float32(0.693))

        def _canon(v):
            if isinstance(v, np.ndarray):
                return v.tolist()
            if isinstance(v, dict):
                return {k: _canon(v[k]) for k in sorted(v)}
            if isinstance(v, (np.floating, np.integer)):
                return v.item()
            return v

        def _crc(metrics: dict) -> int:
            return zlib.crc32(
                json.dumps(_canon(metrics), sort_keys=True).encode()
            )

        crc_binary = _crc(finalize_metrics(binary_counts(logits, y, loss)))
        crc_kclass = _crc(
            finalize_class_metrics(class_counts(logits, y, loss))
        )
        kclass_exact = crc_binary == crc_kclass
    except Exception as e:  # noqa: BLE001 - one parseable line, not a dump
        record = {
            "metric": "bench_error",
            "error": "labels_arm_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
        _emit(record)
        return record
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    record = {
        "metric": f"labels_delayed_truth_n{n_pairs}",
        "value": int(sup.get("joined") or 0),
        "unit": "joined_flows",
        "labels_supervised_reject": 1.0 if supervised_reject else 0.0,
        "labels_unsupervised_pass": 1.0 if unsup_ok else 0.0,
        "labels_coverage_fail_closed": 1.0 if coverage_fail_closed else 0.0,
        "labels_kclass_crc_exact": 1.0 if kclass_exact else 0.0,
        "labels_kclass_crc": int(crc_binary),
        "labels_flip_rate": round(float(snap["flip_rate"]), 6),
        "labels_pair_psi": snap["psi"],
        "labels_joined": int(sup.get("joined") or 0),
        "labels_coverage": sup.get("coverage"),
        "labels_serving_error": sup.get("serving_error"),
        "labels_candidate_error": sup.get("candidate_error"),
        "labels_sparse_coverage": cov.get("coverage"),
        "labels_journal_watermark": sup.get("watermark"),
        "labels_runtime_s": round(time.perf_counter() - t0, 3),
        "unsup_reason": unsup_reason[:160],
        "supervised_reason": sup.get("reason", "")[:160],
        "coverage_reason": cov.get("reason", "")[:160],
    }
    _emit(record)
    return record


def _labels_broken(rec: dict) -> bool:
    """The ground-truth plane's acceptance gates (exit 3): the
    unsupervised rung must PASS the label-regressed candidate (else the
    supervised reject proves nothing), the label gate must reject it,
    the coverage floor must fail closed, and the K = 2 class path must
    be crc-identical to the binary path."""
    return (
        rec.get("labels_supervised_reject", 0.0) < 1.0
        or rec.get("labels_unsupervised_pass", 0.0) < 1.0
        or rec.get("labels_coverage_fail_closed", 0.0) < 1.0
        or rec.get("labels_kclass_crc_exact", 0.0) < 1.0
    )


def bench_sentinel() -> dict:
    """Sentinel plane (ISSUE 19): the standing watch daemon judged
    against a LIVE loopback serving fleet — canary probes ride the real
    client/wire/scorer chain against the real registry pointer, the
    journal tail replays delayed ground truth into the supervised drift
    monitor, and the retention ring trends client-observed latency
    against its pinned first-window baseline.

    Choreography, every arm asserted (exit 3): (1) clean control ticks
    fire NOTHING; (2) a legitimate promotion (registry pointer swap +
    engine hot-swap together) re-keys the canaries — scores change,
    nothing fires; (3) a stale-pointer replica (registry advances, the
    engine does not) fires pointer mismatches; (4) a delayed-label
    error ramp disagreeing with the live scores fires the supervised
    drift verdict AND pokes a SentinelLink (the controller's corrective
    round trigger, end to end through the verdicts journal); (5) a
    genuine latency step (the running engine's score path slowed under
    the live server) fires the long-horizon regression. Headline fields
    (asserted present in train mode, exit 3): ``sentinel_canary_flips``
    / ``sentinel_drift_fires`` / ``sentinel_regression_fires``."""
    import shutil
    import tempfile

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.control.drift import (
        ErrorRateMonitor,
        SentinelLink,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
        make_synthetic,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.datasets import (
        get_dataset,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.sentinel import (
        CanaryProber,
        JournalTail,
        RetentionRing,
        Sentinel,
        load_canary_flows,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.registry import (
        ModelRegistry,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
        ScoreEngine,
        ScoringServer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving.client import (
        probe_scores,
    )

    step_s = float(os.environ.get("BENCH_SENTINEL_STEP_S", "0.25"))
    ramp_n = int(os.environ.get("BENCH_SENTINEL_RAMP", "80"))
    out_dir = tempfile.mkdtemp(prefix="bench-sentinel-")
    t_bench0 = time.perf_counter()
    server = None
    try:
        tok = default_tokenizer()
        model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
        trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
        params1 = trainer.init_state(seed=0).params
        params2 = trainer.init_state(seed=1).params
        params3 = trainer.init_state(seed=2).params

        registry = ModelRegistry(os.path.join(out_dir, "registry"))
        aid1 = registry.add(params1, round_index=1, model_config=model_cfg)
        registry.promote(aid1, to="serving")

        scored = os.path.join(out_dir, "scored.jsonl")
        journal = os.path.join(out_dir, "journal.jsonl")
        verdicts = os.path.join(out_dir, "verdicts.jsonl")
        for p in (scored, journal):
            open(p, "w").close()
        spec = get_dataset("cicids2017")
        engine = ScoreEngine(
            model_cfg, params1, pad_id=tok.pad_id, buckets=(1, 8), round_id=1
        )
        server = ScoringServer(
            engine, tok, spec=spec, scored_jsonl=scored, idle_tick_s=0.01
        )
        flows = load_canary_flows(
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tests", "data", "canary_flows.jsonl",
            ),
            preset="cicids2017",
        )
        with server:
            prober = CanaryProber(
                flows, "127.0.0.1", server.port, registry=registry
            )
            tail = JournalTail(
                scored,
                journal,
                monitor=ErrorRateMonitor(
                    reference_error=0.05, margin=0.2, min_joined=32
                ),
                verdicts_jsonl=verdicts,
            )
            # Latency is the only trended field here: the error ramp
            # below would legitimately trip a supervised_error trend
            # too, and the regression arm must count exactly the
            # injected latency step.
            ring = RetentionRing(
                os.path.join(out_dir, "ring.jsonl"),
                max_records=64,
                baseline_n=3,
                window_n=3,
                trend_fields={"latency_p99_ms": (1.5, 5.0, "up")},
            )
            link = SentinelLink(verdicts)  # armed before any verdict
            sentinel = Sentinel(
                prober=prober,
                tail=tail,
                ring=ring,
                alerts_jsonl=os.path.join(out_dir, "alerts.jsonl"),
            )
            # Warm sockets + jit paths off the clock so the pinned
            # baseline window holds steady-state latency.
            probe_scores("127.0.0.1", server.port, [f.text for f in flows])

            # (1) clean control: fills the pinned baseline AND a full
            # trend window at steady state — any fire here is false.
            for _ in range(6):
                sentinel.tick()
            false_fires = (
                sentinel.canary_flips
                + sentinel.drift_fires
                + sentinel.regression_fires
            )

            # (2) legitimate promotion: pointer and replica move
            # together — the canary scores flip, the sentinel re-keys.
            before = dict(prober._scores)
            aid2 = registry.add(
                params2, round_index=2, model_config=model_cfg
            )
            registry.promote(aid2, to="serving")
            engine.swap(params2, round_id=2)
            sentinel.tick()
            after = dict(prober._scores)
            promotion_flipped = any(
                (aid2, f.id) in after
                and after[(aid2, f.id)] != before.get((aid1, f.id))
                for f in flows
            )
            promotion_quiet = (
                sentinel.canary_flips
                + sentinel.drift_fires
                + sentinel.regression_fires
            ) == false_fires

            # (3) stale pointer: the registry advances, the replica
            # keeps serving round 2 — every canary reports a mismatch.
            aid3 = registry.add(
                params3, round_index=3, model_config=model_cfg
            )
            registry.promote(aid3, to="serving")
            canary_report = sentinel.tick()["canary"]
            pointer_mismatches = canary_report["mismatches"]
            engine.swap(params3, round_id=3)  # repair the fleet
            sentinel.tick()  # re-keyed: quiet again

            # (4) delayed ground truth disagreeing with the live
            # scores: labels arrive as the exact opposite of what the
            # server answered, the join error saturates, the monitor
            # fires, and the verdict lands in the controller's journal.
            texts = spec.render_texts(
                make_synthetic("cicids2017", ramp_n, seed=1)
            )
            replies = probe_scores("127.0.0.1", server.port, texts)
            with open(journal, "a") as f:
                for reply, _lat in replies:
                    f.write(
                        json.dumps(
                            {
                                "schema": "fedtpu-label-v1",
                                "rid": str(reply["id"]),
                                "label": 1 - int(reply["prediction"]),
                                "ts": time.time(),
                            }
                        )
                        + "\n"
                    )
            sentinel.tick()
            poke = link.poll()
            link_poked = (
                poke is not None and poke.get("method") == "error_rate"
            )

            # (5) latency step: slow the LIVE engine's score path (the
            # sleep rides under the running server, so the step is
            # client-observed through the real chain), then let the
            # trend window fill past the pinned baseline.
            real_score = engine.score

            def slow_score(*a, **kw):
                time.sleep(step_s)
                return real_score(*a, **kw)

            engine.score = slow_score
            for _ in range(4):
                sentinel.tick()
        record = {
            "metric": "sentinel_plane",
            "value": sentinel.canary_flips
            + sentinel.drift_fires
            + sentinel.regression_fires,
            "unit": "incidents_detected",
            "vs_baseline": None,
            "baseline_note": "reference: no standing watch at all — a "
            "stale replica, a drifted model, or a latency regression "
            "goes unnoticed until a human reruns an offline eval",
            "sentinel_canary_flips": sentinel.canary_flips,
            "sentinel_drift_fires": sentinel.drift_fires,
            "sentinel_regression_fires": sentinel.regression_fires,
            "sentinel_false_fires": false_fires,
            "sentinel_pointer_mismatches": pointer_mismatches,
            "sentinel_promotion_flipped": promotion_flipped,
            "sentinel_promotion_quiet": promotion_quiet,
            "sentinel_link_poked": link_poked,
            "sentinel_drift_error": (
                None if poke is None else poke.get("error")
            ),
            "sentinel_ticks": sentinel.ticks,
            "sentinel_canaries": len(flows),
            "wall_s": round(time.perf_counter() - t_bench0, 2),
        }
    except Exception as e:
        record = {
            "metric": "bench_error",
            "error": "sentinel_plane_failed",
            "detail": f"{type(e).__name__}: {str(e)[:300]}",
        }
    finally:
        if server is not None:
            server.close()
        shutil.rmtree(out_dir, ignore_errors=True)
    _emit(record)
    return record


def _sentinel_broken(rec: dict) -> bool:
    """The sentinel plane's acceptance gates (exit 3): zero false fires
    on the clean control, the legitimate promotion flips scores WITHOUT
    firing, the stale pointer fires mismatches, the error ramp fires
    the drift verdict and pokes the controller link, and the latency
    step fires the long-horizon regression."""
    return (
        rec.get("sentinel_false_fires", 1) != 0
        or rec.get("sentinel_canary_flips", 0) < 1
        or rec.get("sentinel_drift_fires", 0) < 1
        or rec.get("sentinel_regression_fires", 0) < 1
        or not rec.get("sentinel_promotion_flipped", False)
        or not rec.get("sentinel_promotion_quiet", False)
        or not rec.get("sentinel_link_poked", False)
    )


#: Federated product-step MFU floor (fed2/fedseq): the driver-captured
#: records sit at 0.585/0.56 (BENCH_r05, a pre-PR-1 capture; not measured
#: on the current installation); a regression below 0.50 exits
#: nonzero so it cannot pass silently (VERDICT r5 weak #7).
MFU_FLOOR = float(os.environ.get("BENCH_MFU_FLOOR", "0.50"))


def _check_mfu_floor(records: dict[str, dict | None]) -> list[str]:
    """Names of federated records whose measured MFU broke the floor
    (records without an mfu field — CPU hosts — are exempt)."""
    return [
        name
        for name, rec in records.items()
        if rec is not None and rec.get("mfu") is not None
        and rec["mfu"] < MFU_FLOOR
    ]


def main() -> None:
    # One compile cache for this process and every child it spawns, placed
    # before the first compilation (utils/compile_cache.py).
    place_compile_cache()
    worker_spec = os.environ.get("BENCH_ROUTER_WORKER")
    if worker_spec:
        # A bench_router replica subprocess: no watchdog,
        # forced-CPU — serves until the parent terminates it.
        _router_worker(worker_spec)
        return
    mode = os.environ.get("BENCH_MODE", "train")
    if mode not in MODES:  # validate before touching the backend
        raise SystemExit(f"unknown BENCH_MODE {mode!r} ({'|'.join(MODES)})")
    if mode == "check":
        # Pure-AST scan: no accelerator, no watchdog.
        rec = bench_check()
        if rec.get("metric") == "bench_error" or rec.get(
            "check_findings_new", 1
        ):
            raise SystemExit(3)
        return
    if mode == "obs":
        # Host-side loopback (sockets + stdlib HTTP): no accelerator.
        # The health plane's acceptance contract: the
        # live demo must fire AND clear a burn alert and leave a
        # postmortem bundle behind — anything less exits 3.
        rec = bench_obs()
        if rec.get("metric") == "bench_error" or (
            rec.get("slo_alerts_fired", 0) < 1
            or rec.get("slo_alerts_cleared", 0) < 1
            or rec.get("postmortem_bundles", 0) < 1
            or rec.get("obs_scrape_lag_ms") is None
        ):
            raise SystemExit(3)
        return
    if mode == "wire":
        # numpy + loopback sockets only: no accelerator.
        # The wire-efficiency acceptance: >= 3x int8 upload reduction,
        # >= 3x sparse upward-hop reduction, >= 2x fold speedup, every
        # arm crc-exact — anything less exits 3.
        rec = bench_wire()
        if rec.get("metric") == "bench_error" or _wire_broken(rec):
            raise SystemExit(3)
        return
    if mode == "labels":
        # Journal/join/gate arithmetic is pure host work; the K-class
        # crc arm touches jnp, so pin the CPU backend before first use —
        # this mode needs no chip and must not take one. Safe here only
        # because nothing else runs in this process.
        jax.config.update("jax_platforms", "cpu")
        rec = bench_labels()
        if rec.get("metric") == "bench_error" or _labels_broken(rec):
            raise SystemExit(3)
        return
    if mode == "sentinel":
        # Loopback fleet + watch daemon on the tiny model: the engine
        # touches jnp, so pin the CPU backend before first use — this
        # mode needs no chip and must not take one. Safe here only
        # because nothing else runs in this process.
        jax.config.update("jax_platforms", "cpu")
        rec = bench_sentinel()
        if rec.get("metric") == "bench_error" or _sentinel_broken(rec):
            raise SystemExit(3)
        return
    if (mode == "clientdp" and os.environ.get("BENCH_CLIENTDP_FORCE_CPU")) or (
        mode == "fsdp" and os.environ.get("BENCH_FSDP_FORCE_CPU")
    ) or (
        mode == "serve" and os.environ.get("BENCH_SERVE_FSDP_FORCE_CPU")
    ):
        # The virtual-device fallback subprocess (bench_client_dp /
        # bench_fsdp): force the CPU platform before backend init; the
        # device COUNT rides XLA_FLAGS from the parent.
        jax.config.update("jax_platforms", "cpu")
    # Global watchdog: a stall mid-bench still produces one JSON line.
    budget = int(os.environ.get("BENCH_TIMEOUT", "1500"))
    guard = None
    if budget:
        guard = _watchdog(
            budget,
            {
                "metric": "bench_error",
                "error": "bench_stalled",
                "detail": f"BENCH_MODE={mode} exceeded the {budget}s watchdog",
            },
        )
    try:
        if mode == "train":
            # Secondary records first (the FEDERATED product steps the
            # VERDICT r4 asked the driver bench to capture — 2-axis
            # vmapped-dense and 3-axis fedseq — plus the multi-chip TCP
            # client A/B); the headline dense line stays LAST so tail
            # parsers keep reading the same metric, and it carries the
            # federated MFUs as machine-parsed fields. BENCH_SECONDARY=0
            # restores the single-line behavior.
            rec_fed2 = rec_fedseq = rec_ctrl = rec_resid = rec_scn = None
            rec_fleet = rec_check = rec_router = rec_obs = None
            rec_profile = rec_shadow = rec_fsdp = rec_wire = None
            rec_labels = rec_sentinel = rec_serve_fsdp = None
            if os.environ.get("BENCH_SECONDARY", "1").lower() not in (
                "", "0", "false",
            ):
                rec_fed2 = bench_fed2()
                rec_fedseq = bench_fedseq()
                if os.environ.get(
                    "BENCH_FEDSEQ_DECOMP", "1"
                ).lower() not in ("", "0", "false"):
                    rec_resid = bench_fedseq_residual(rec_fed2, rec_fedseq)
                bench_client_dp()
                rec_fsdp = bench_fsdp()
                bench_serving()
                rec_serve_fsdp = bench_serve_fsdp()
                rec_ctrl = bench_controller()
                rec_scn = bench_scenario()
                rec_fleet = bench_fleet()
                rec_wire = bench_wire()
                rec_router = bench_router()
                rec_shadow = bench_shadow()
                rec_obs = bench_obs()
                # Profile LAST among the jitted secondaries: it marks
                # the engine train site warm, and the headline
                # bench_train below shares its compiled program (same
                # batch/prng), so nothing after it traces a new shape
                # at a warm site.
                rec_profile = bench_profile()
                rec_check = bench_check()
                rec_labels = bench_labels()
                rec_sentinel = bench_sentinel()
            extra = {}
            for key, rec in (("fed2", rec_fed2), ("fedseq", rec_fedseq)):
                if rec is not None and rec.get("mfu") is not None:
                    extra[f"{key}_mfu"] = rec["mfu"]
            if rec_resid is not None:
                # The fedseq residual decomposition as headline fields:
                # the driver pins the 2.5-point fed2-vs-fedseq gap (and
                # any closure) per round, machine-parsed.
                extra["fedseq_residual_gap_ms"] = rec_resid["value"]
                for part in (
                    "hash_dropout_ms", "ring_merge_ms", "degenerate_ring_ms",
                ):
                    extra[f"fedseq_residual_{part}"] = rec_resid[part]
                if "mfu_gap_points" in rec_resid:
                    extra["fedseq_residual_mfu_points"] = rec_resid[
                        "mfu_gap_points"
                    ]
            if rec_ctrl is not None and rec_ctrl.get("metric") != "bench_error":
                # Control-plane companions on the headline record: the
                # driver's tail parser reads rounds/hour and the gate's
                # rejection count as machine-parsed fields.
                extra["controller_rounds_per_hour"] = rec_ctrl["value"]
                extra["controller_gate_rejections"] = rec_ctrl[
                    "gate_rejections"
                ]
                # comm_phase_* / round-pipelining headline fields (obs
                # round-phase accounting + streaming chunk aggregation):
                # ASSERTED present — a refactor that drops the round
                # engine's phase or fold accounting must fail the bench
                # loudly, not silently stop tracking the breakdown.
                missing = [
                    k
                    for k in (
                        "comm_phase_wait_s",
                        "comm_phase_agg_s",
                        "comm_phase_reply_s",
                        "comm_overlap_frac",
                        "server_peak_agg_bytes",
                    )
                    if k not in rec_ctrl
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "comm_phase_fields_missing",
                            "detail": f"controller record lacks {missing} "
                            "(AggregationServer.phase_seconds / "
                            "stream_totals accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "comm_phase_wait_s",
                    "comm_phase_agg_s",
                    "comm_phase_reply_s",
                    "comm_overlap_frac",
                    "server_peak_agg_bytes",
                    "barrier_comm_phase_wait_s",
                ):
                    if k in rec_ctrl:
                        extra[k] = rec_ctrl[k]
            scenario_broken = False
            if rec_scn is not None and rec_scn.get("metric") != "bench_error":
                # Robustness headline fields (ISSUE 6): the persona
                # matrix's round-success fraction is asserted 1.0 —
                # every bench cell is quorum-satisfiable, so any failed
                # round is a robustness regression, not flake.
                extra["scenario_rounds_ok_frac"] = rec_scn[
                    "scenario_rounds_ok_frac"
                ]
                extra["scenario_straggler_wait_s"] = rec_scn[
                    "scenario_straggler_wait_s"
                ]
                extra["scenario_crc_exact_frac"] = rec_scn[
                    "scenario_crc_exact_frac"
                ]
                scenario_broken = (
                    rec_scn["scenario_rounds_ok_frac"] < 1.0
                    or rec_scn["scenario_crc_exact_frac"] < 1.0
                )
            fleet_broken = False
            if rec_fleet is not None and (
                rec_fleet.get("metric") != "bench_error"
            ):
                # Fleet-scale headline fields (ISSUE 7): ASSERTED present
                # — a refactor that drops the relay tier's fold or peak
                # accounting must fail the bench loudly (exit 3), exactly
                # like the comm_phase_* / comm_overlap_frac contract.
                missing = [
                    k
                    for k in (
                        "fleet_rounds_per_hour",
                        "relay_peak_agg_bytes",
                        # Survivability headline fields (ISSUE 14): the
                        # chaos arm's re-home / degraded-round proof
                        # must stay machine-parsed — a refactor that
                        # drops the failover plane fails the bench
                        # loudly, exactly like a crc mismatch.
                        "fleet_rehomes_total",
                        "fleet_subtree_failures",
                        "fleet_degraded_rounds_ok",
                    )
                    if k not in rec_fleet
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "fleet_fields_missing",
                            "detail": f"fleet record lacks {missing} "
                            "(relay stream_totals / chaos-arm "
                            "accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "fleet_rounds_per_hour",
                    "relay_peak_agg_bytes",
                    "fleet_crc_exact",
                    "fleet_rehomes_total",
                    "fleet_subtree_failures",
                    "fleet_degraded_rounds_ok",
                ):
                    extra[k] = rec_fleet[k]
                # Degraded rounds asserted OK: a chaos round that hung,
                # lost a re-homed contributor, or landed off-crc is a
                # robustness regression (exit 3).
                fleet_broken = (
                    rec_fleet["fleet_crc_exact"] < 1.0
                    or rec_fleet["fleet_degraded_rounds_ok"] < 1.0
                )
            wire_broken_flag = False
            if rec_wire is not None and (
                rec_wire.get("metric") != "bench_error"
            ):
                # Wire-efficiency headline fields (ISSUE 17): ASSERTED
                # present — a refactor that drops the upward-byte
                # counter, the fold-throughput accounting, or the
                # quantized-round crc replay must fail the bench loudly
                # — with the int8 and sparse reductions, the fold
                # speedup, and every arm's crc gated exactly like a crc
                # mismatch (exit 3).
                missing = [
                    k
                    for k in (
                        "relay_upward_bytes",
                        "fold_throughput_gbps",
                        "wire_round_cadence_ratio",
                        "wire_dtype",
                    )
                    if k not in rec_wire
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "wire_fields_missing",
                            "detail": f"wire record lacks {missing} "
                            "(relay upward_bytes / StreamAgg fold "
                            "accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "relay_upward_bytes",
                    "relay_upward_reduction",
                    "wire_upload_reduction",
                    "fold_throughput_gbps",
                    "fold_speedup",
                    "wire_round_cadence_ratio",
                    "wire_crc_exact",
                ):
                    if k in rec_wire:
                        extra[k] = rec_wire[k]
                wire_broken_flag = _wire_broken(rec_wire)
            router_broken = False
            if rec_router is not None and (
                rec_router.get("metric") != "bench_error"
            ):
                # Serving-fleet headline fields (ISSUE 9): ASSERTED
                # present, and router_rolling_reload_dropped asserted 0
                # (exit 3) — a promotion under load that sheds even one
                # request is a zero-downtime-deploy regression, failed
                # exactly like a crc mismatch.
                missing = [
                    k
                    for k in (
                        "router_qps_sustained",
                        "router_p99_ms",
                        "router_rolling_reload_dropped",
                    )
                    if k not in rec_router
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "router_fields_missing",
                            "detail": f"router record lacks {missing} "
                            "(router/fleet load accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "router_qps_sustained",
                    "router_p99_ms",
                    "router_rolling_reload_dropped",
                    "router_single_qps",
                    "router_p99_within_slo",
                ):
                    if k in rec_router:
                        extra[k] = rec_router[k]
                router_broken = (
                    rec_router["router_rolling_reload_dropped"] > 0
                    or rec_router.get("router_reload_complete", 1.0) < 1.0
                )
            shadow_gate_broken = False
            if rec_shadow is not None and (
                rec_shadow.get("metric") != "bench_error"
            ):
                # Shadow-plane headline fields (ISSUE 13): ASSERTED
                # present — a refactor that drops the mirror/compare/gate
                # accounting must fail the bench loudly — with zero live
                # requests dropped, the promotion gated on >= min_pairs
                # mirrored pairs, the regressed candidate held out of
                # serving, and the mirror's added p99 inside the slack.
                missing = [
                    k
                    for k in (
                        "shadow_pairs_total",
                        "shadow_gate_verdicts",
                        "shadow_added_p99_ms",
                    )
                    if k not in rec_shadow
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "shadow_fields_missing",
                            "detail": f"shadow record lacks {missing} "
                            "(shadow/ mirror/compare/gate accounting "
                            "broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "shadow_pairs_total",
                    "shadow_gate_verdicts",
                    "shadow_added_p99_ms",
                    "shadow_live_dropped",
                    "shadow_reject_flip_rate",
                ):
                    if k in rec_shadow:
                        extra[k] = rec_shadow[k]
                shadow_gate_broken = shadow_broken(rec_shadow)
            obs_broken = False
            if rec_obs is not None and (
                rec_obs.get("metric") != "bench_error"
            ):
                # Fleet-health headline fields (ISSUE 11): ASSERTED
                # present — a refactor that drops the burn-alert or
                # flight-recorder accounting must fail the bench loudly
                # — and the live demo must have fired >= 1 alert and
                # produced >= 1 postmortem bundle (exit 3 otherwise).
                missing = [
                    k
                    for k in (
                        "slo_alerts_fired",
                        "obs_scrape_lag_ms",
                        "postmortem_bundles",
                    )
                    if k not in rec_obs
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "obs_fields_missing",
                            "detail": f"obs record lacks {missing} "
                            "(scrape hub / alert manager / flight "
                            "recorder accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "slo_alerts_fired",
                    "slo_alerts_cleared",
                    "obs_scrape_lag_ms",
                    "postmortem_bundles",
                ):
                    if k in rec_obs:
                        extra[k] = rec_obs[k]
                obs_broken = (
                    rec_obs["slo_alerts_fired"] < 1
                    or rec_obs.get("slo_alerts_cleared", 0) < 1
                    or rec_obs["postmortem_bundles"] < 1
                    or rec_obs["obs_scrape_lag_ms"] is None
                )
            fsdp_broken = False
            if rec_fsdp is not None and (
                rec_fsdp.get("metric") != "bench_error"
            ):
                # FSDP headline fields (ISSUE 15): ASSERTED present — a
                # refactor that drops the shard-byte accounting, the A/B
                # step ratio, or the gather crc contract must fail the
                # bench loudly — with the static state asserted actually
                # sharded (<= 0.6 per chip), the step price inside the
                # gather budget (<= 1.15x), and the wire-exchange
                # round-trip crc-bit-exact (exit 3 otherwise).
                missing = [
                    k
                    for k in (
                        "fsdp_peak_param_opt_bytes_ratio",
                        "fsdp_step_time_ratio",
                        "fsdp_crc_exact",
                    )
                    if k not in rec_fsdp
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "fsdp_fields_missing",
                            "detail": f"fsdp record lacks {missing} "
                            "(FsdpMeshTrainer shard/byte/crc accounting "
                            "broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "fsdp_peak_param_opt_bytes_ratio",
                    "fsdp_step_time_ratio",
                    "fsdp_crc_exact",
                    "fsdp_shards",
                    "fsdp_device_bytes_in_use_ratio",
                ):
                    if k in rec_fsdp:
                        extra[k] = rec_fsdp[k]
                fsdp_broken = _fsdp_broken(rec_fsdp)
            serve_fsdp_broken = False
            if rec_serve_fsdp is not None and (
                rec_serve_fsdp.get("metric") != "bench_error"
            ):
                # Sharded-scorer headline fields (ISSUE 20): ASSERTED
                # present — a refactor that drops the at-rest shard-byte
                # accounting, the replicated-vs-sharded bit-identity, or
                # the reload recompile ledger must fail the bench loudly
                # — with the bytes ratio <= 0.6 at N = 2, probs crc-bit-
                # exact, and 0 bucket retraces across warmup + a mid-
                # load rolling reload (exit 3 otherwise).
                missing = [
                    k
                    for k in (
                        "serve_fsdp_static_bytes_ratio",
                        "serve_fsdp_crc_exact",
                        "serve_reload_recompiles",
                    )
                    if k not in rec_serve_fsdp
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "serve_fsdp_fields_missing",
                            "detail": f"serve_fsdp record lacks {missing} "
                            "(ScoreEngine shard/byte/ledger accounting "
                            "broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "serve_fsdp_static_bytes_ratio",
                    "serve_fsdp_crc_exact",
                    "serve_reload_recompiles",
                    "serve_fsdp_shards",
                ):
                    if k in rec_serve_fsdp:
                        extra[k] = rec_serve_fsdp[k]
                serve_fsdp_broken = _serve_fsdp_broken(rec_serve_fsdp)
            profile_broken = False
            if rec_profile is not None and (
                rec_profile.get("metric") != "bench_error"
            ):
                # Device-plane headline fields (ISSUE 12): ASSERTED
                # present — a refactor that drops the compile ledger,
                # the fenced step timers, or the memory watermarks must
                # fail the bench loudly — with the serving path's
                # recompiles asserted 0 and the XLA-vs-analytic FLOPs
                # ratio pinned inside FLOPS_RATIO_TOLERANCE.
                missing = [
                    k
                    for k in (
                        "profile_compile_count",
                        "profile_recompiles",
                        "profile_step_device_ms_p50",
                        "profile_peak_device_bytes",
                    )
                    if k not in rec_profile
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "profile_fields_missing",
                            "detail": f"profile record lacks {missing} "
                            "(obs/profile.py session accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "profile_compile_count",
                    "profile_recompiles",
                    "profile_step_device_ms_p50",
                    "profile_step_host_ms_p50",
                    "profile_peak_device_bytes",
                    "profile_memory_available",
                    "profile_flops_ratio",
                    "profile_serving_recompiles",
                ):
                    if k in rec_profile:
                        extra[k] = rec_profile[k]
                profile_broken = _profile_broken(rec_profile)
            check_broken = False
            if rec_check is not None and (
                rec_check.get("metric") != "bench_error"
            ):
                # Static-analysis headline fields (ISSUE 8): ASSERTED
                # present, and check_findings_new asserted 0 (exit 3) —
                # an invariant regression fails the driver bench exactly
                # like a crc mismatch or a broken MFU floor would.
                missing = [
                    k
                    for k in ("check_findings_new", "check_runtime_s")
                    if k not in rec_check
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "check_fields_missing",
                            "detail": f"check record lacks {missing} "
                            "(analysis.run_check result shape broken?)",
                        }
                    )
                    raise SystemExit(3)
                extra["check_findings_new"] = rec_check["check_findings_new"]
                extra["check_runtime_s"] = rec_check["check_runtime_s"]
                check_broken = rec_check["check_findings_new"] > 0
            labels_broken_flag = False
            if rec_labels is not None and (
                rec_labels.get("metric") != "bench_error"
            ):
                # Ground-truth-plane headline fields (ISSUE 18):
                # ASSERTED present — a refactor that drops the journal
                # join, the supervised rung, or the K-class crc replay
                # must fail the bench loudly — with the supervised
                # reject, the coverage fail-closed, and the K = 2 crc
                # identity all gated exit 3 (_labels_broken).
                missing = [
                    k
                    for k in (
                        "labels_supervised_reject",
                        "labels_coverage_fail_closed",
                        "labels_kclass_crc_exact",
                    )
                    if k not in rec_labels
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "labels_fields_missing",
                            "detail": f"labels record lacks {missing} "
                            "(labels/ journal/join/gate accounting "
                            "broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "labels_supervised_reject",
                    "labels_unsupervised_pass",
                    "labels_coverage_fail_closed",
                    "labels_kclass_crc_exact",
                    "labels_joined",
                    "labels_coverage",
                    "labels_flip_rate",
                ):
                    if k in rec_labels:
                        extra[k] = rec_labels[k]
                labels_broken_flag = _labels_broken(rec_labels)
            sentinel_broken_flag = False
            if rec_sentinel is not None and (
                rec_sentinel.get("metric") != "bench_error"
            ):
                # Sentinel-plane headline fields (ISSUE 19): ASSERTED
                # present — a refactor that drops the canary identity
                # check, the journal-tail drift rung, or the retention-
                # ring trend accounting must fail the bench loudly —
                # with every injected incident class caught and zero
                # false fires all gated exit 3 (_sentinel_broken).
                missing = [
                    k
                    for k in (
                        "sentinel_canary_flips",
                        "sentinel_drift_fires",
                        "sentinel_regression_fires",
                    )
                    if k not in rec_sentinel
                ]
                if missing:
                    _emit(
                        {
                            "metric": "bench_error",
                            "error": "sentinel_fields_missing",
                            "detail": f"sentinel record lacks {missing} "
                            "(obs/sentinel.py prober/tail/ring "
                            "accounting broken?)",
                        }
                    )
                    raise SystemExit(3)
                for k in (
                    "sentinel_canary_flips",
                    "sentinel_drift_fires",
                    "sentinel_regression_fires",
                    "sentinel_false_fires",
                    "sentinel_link_poked",
                ):
                    if k in rec_sentinel:
                        extra[k] = rec_sentinel[k]
                sentinel_broken_flag = _sentinel_broken(rec_sentinel)
            broken = _check_mfu_floor(
                {"fed2": rec_fed2, "fedseq": rec_fedseq}
            )
            if broken:
                extra.update(mfu_floor=MFU_FLOOR, mfu_floor_broken=broken)
            bench_train(ModelConfig(), "distilbert", extra=extra or None)
            if (
                broken
                or scenario_broken
                or fleet_broken
                or wire_broken_flag
                or router_broken
                or shadow_gate_broken
                or obs_broken
                or profile_broken
                or fsdp_broken
                or serve_fsdp_broken
                or check_broken
                or labels_broken_flag
                or sentinel_broken_flag
            ):
                raise SystemExit(3)
        elif mode == "bert":
            bench_train(ModelConfig.bert_base(), "bertbase")
        elif mode == "bertlarge":
            # 335 M params: bs 32 fits one v5e chip comfortably with remat off.
            os.environ.setdefault("BENCH_BATCH", "32")
            bench_train(ModelConfig.bert_large(), "bertlarge")
        elif mode == "eval":
            bench_eval()
        elif mode == "fedavg":
            bench_fedavg()
        elif mode == "flash":
            bench_flash()
        elif mode == "ring":
            bench_ring()
        elif mode == "fed2":
            if _check_mfu_floor({"fed2": bench_fed2()}):
                raise SystemExit(3)
        elif mode == "fedseq":
            if _check_mfu_floor({"fedseq": bench_fedseq()}):
                raise SystemExit(3)
        elif mode == "serve":
            if not os.environ.get("BENCH_SERVE_FSDP_FORCE_CPU"):
                bench_serving()
            # Sharded arm LAST: the virtual-CPU child's record must be
            # the final JSON stdout line its parent parses.
            rec = bench_serve_fsdp()
            if rec is None or rec.get("metric") == "bench_error" or (
                _serve_fsdp_broken(rec)
            ):
                raise SystemExit(3)
        elif mode == "clientdp":
            bench_client_dp()
        elif mode == "controller":
            bench_controller()
        elif mode == "scenario":
            rec = bench_scenario()
            if rec is not None and rec.get("metric") != "bench_error" and (
                rec["scenario_rounds_ok_frac"] < 1.0
                or rec["scenario_crc_exact_frac"] < 1.0
            ):
                raise SystemExit(3)
        elif mode == "fleet":
            rec = bench_fleet()
            if rec is not None and rec.get("metric") != "bench_error" and (
                rec["fleet_crc_exact"] < 1.0
            ):
                raise SystemExit(3)
        elif mode == "router":
            rec = bench_router()
            if rec is not None and rec.get("metric") != "bench_error" and (
                rec["router_rolling_reload_dropped"] > 0
                or rec.get("router_reload_complete", 1.0) < 1.0
            ):
                raise SystemExit(3)
        elif mode == "profile":
            rec = bench_profile()
            if rec is None or rec.get("metric") == "bench_error" or (
                _profile_broken(rec)
            ):
                raise SystemExit(3)
        elif mode == "shadow":
            rec = bench_shadow()
            if rec is None or rec.get("metric") == "bench_error" or (
                shadow_broken(rec)
            ):
                raise SystemExit(3)
        elif mode == "fsdp":
            rec = bench_fsdp()
            if rec is None or rec.get("metric") == "bench_error" or (
                _fsdp_broken(rec)
            ):
                raise SystemExit(3)
        elif mode == "strategy":
            rec = bench_strategy()
            if rec is None or rec.get("metric") == "bench_error" or (
                rec["strategy_crc_exact"] < 1.0
                or rec["strategy_noniid_acc_lift"] < STRATEGY_LIFT_FLOOR
            ):
                raise SystemExit(3)
    finally:
        if guard is not None:
            guard.cancel()


if __name__ == "__main__":
    sys.exit(main())
