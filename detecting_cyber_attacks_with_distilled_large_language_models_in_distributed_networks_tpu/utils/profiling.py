"""Profiling: analytic FLOPs, MFU accounting, and jax.profiler traces.

The reference's entire profiling story is timestamped ``print`` bracketing
plus tqdm rates (reference client1.py:85,92,97,115 and the golden terminal
logs, SURVEY.md §5) — there is no FLOPs or utilization accounting anywhere.
Here the model's step cost is computed analytically from the config, so any
timed step yields MFU against the local chip's peak (the benchmark keeps
its own copy of this arithmetic, PERF.md §7), and ``trace`` wraps
``jax.profiler`` for real TPU timelines (xprof/tensorboard).
"""

from __future__ import annotations

import contextlib
import re
from typing import Iterator

from ..config import ModelConfig

#: Peak dense bf16 matmul TFLOPs per CHIP by TPU generation (public specs;
#: the mental model follows jax-ml.github.io/scaling-book). Keys are matched
#: against ``jax.Device.device_kind`` strings like "TPU v4".
TPU_PEAK_TFLOPS: dict[str, float] = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5e": 197.0,
    "v5 lite": 197.0,
    "v5litepod": 197.0,
    "v5p": 459.0,
    "v5": 459.0,
    "v6e": 918.0,
    "v6 lite": 918.0,
}


def forward_flops(
    cfg: ModelConfig, batch_size: int, seq_len: int | None = None
) -> float:
    """Analytic matmul FLOPs of one classifier forward pass.

    Counts every dense contraction (2·M·N·K per matmul): per transformer
    layer the Q/K/V/output projections (8·L·D²), the attention score and
    value contractions (4·L²·D), and the two FFN matmuls (4·L·D·F); plus the
    CLS head (2·D·C). Embedding gathers, layernorms, softmaxes, and biases
    are O(L·D) — negligible against the D² terms and excluded, which also
    matches how XLA's own cost model attributes transformer step cost.
    """
    from ..models import family_module

    module = family_module(cfg)
    if module is not None:  # another family counts its own (models/<family>.py)
        return module.forward_flops(cfg, batch_size, seq_len)
    L = seq_len if seq_len is not None else cfg.max_len
    D, F = cfg.dim, cfg.hidden_dim
    per_layer = 8 * L * D * D + 4 * L * L * D + 4 * L * D * F
    head = 2 * D * cfg.n_classes
    return float(batch_size) * (cfg.n_layers * per_layer + head)


def train_step_flops(
    cfg: ModelConfig, batch_size: int, seq_len: int | None = None
) -> float:
    """Forward + backward ≈ 3× forward (the backward pass contracts twice
    per forward matmul: grads w.r.t. activations and w.r.t. weights)."""
    return 3.0 * forward_flops(cfg, batch_size, seq_len)


def device_peak_flops(device=None) -> float | None:
    """Peak bf16 FLOPs/s of one device, or None when unknown (e.g. CPU).

    ``device`` defaults to ``jax.devices()[0]``.
    """
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    m = re.search(r"v\d+\s*(e|p|lite(pod)?)?", kind.lower())
    if not m:
        return None
    key = m.group(0).strip()
    tflops = TPU_PEAK_TFLOPS.get(key)
    if tflops is None:
        # "v5 litepod" etc. — retry with just the generation number.
        tflops = TPU_PEAK_TFLOPS.get(key.split()[0])
    return tflops * 1e12 if tflops is not None else None


def mfu(
    flops_per_step: float,
    step_time_s: float,
    n_devices: int = 1,
    peak_flops_per_device: float | None = None,
) -> float | None:
    """Model FLOPs utilization in [0, 1], or None when the peak is unknown."""
    if peak_flops_per_device is None:
        peak_flops_per_device = device_peak_flops()
    if peak_flops_per_device is None or step_time_s <= 0:
        return None
    return flops_per_step / (step_time_s * peak_flops_per_device * n_devices)


@contextlib.contextmanager
def trace(log_dir: str | None) -> Iterator[None]:
    """``jax.profiler.trace`` gated on ``log_dir`` — pass None for a no-op,
    so call sites need no branching (the CLI's --profile-dir plumbs here).
    View with xprof/tensorboard."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
