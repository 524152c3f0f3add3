"""Operations and bytes a step requires, from shapes alone.

The benchmark's own copy of the arithmetic in the program's
``utils/profiling.py`` (``forward_flops`` / ``train_step_flops``), kept here
so that no later PR can move the yardstick; ``selftest`` checks the two still
agree. Matmul FLOPs only (2*M*N*K per contraction), no recompute: embedding
gathers, LayerNorm, softmax and biases are O(L*D) and left out, as there.

``model`` is the ``model`` object of a ``benchmark/configs/<config>.json``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def forward_flops(model: dict, rows: int = 1, seq_len: int | None = None) -> float:
    """One classifier forward pass over ``rows`` sequences: per layer the
    Q/K/V/output projections (8*L*D^2), the score and value contractions
    (4*L^2*D) and the two FFN matmuls (4*L*D*F); plus the CLS head."""
    L = model["max_len"] if seq_len is None else seq_len
    D, F = model["dim"], model["hidden_dim"]
    per_layer = 8 * L * D * D + 4 * L * L * D + 4 * L * D * F
    head = 2 * D * model["n_classes"]
    return float(rows) * (model["n_layers"] * per_layer + head)


def train_step_flops(model: dict, rows: int = 1, seq_len: int | None = None) -> float:
    """Forward + backward = 3x forward: the backward pass contracts twice
    per forward matmul (gradients w.r.t. activations and w.r.t. weights)."""
    return 3.0 * forward_flops(model, rows, seq_len)


def param_count(model: dict) -> int:
    """Parameters of the encoder + head as ``models/distilbert.py`` builds
    them: word and position tables, embedding LayerNorm, per layer four
    DxD projections, two FFN matrices and two LayerNorms (all with biases),
    and the dim -> n_classes head."""
    D, F = model["dim"], model["hidden_dim"]
    emb = (model["vocab_size"] + model["max_position_embeddings"]) * D + 2 * D
    layer = 4 * (D * D + D) + (D * F + F) + (F * D + D) + 4 * D
    return emb + model["n_layers"] * layer + D * model["n_classes"] + model["n_classes"]


def train_step_bytes(model: dict) -> float:
    """The least HBM traffic of one optimizer step, whatever the batch:
    fp32 parameters read and written (8 B), gradients written and read
    (8 B), Adam's two moments read and written (16 B) = 32 B a parameter.
    Activations are left out: the floor, not an estimate."""
    return 32.0 * param_count(model)


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"benchmark/peaks.json has no entry for device kind {device_kind!r}: "
            "add one with its source before reporting a utilisation"
        )
    return table[device_kind]


def roofline_floor_s(flops: float, nbytes: float, peaks: dict, chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` chips could take, and which roof sets it."""
    t_c = flops / (peaks["bf16_flops_per_s"] * chips)
    t_m = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
