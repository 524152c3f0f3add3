"""Family ``bert_encoder``: the BERT-family encoder with the paper's head
(``models/distilbert.py`` under ``ModelConfig``): DistilBERT-base and
BERT-large at their published widths.

The operations and bytes a step requires are the benchmark's own copy of the
arithmetic in the program's ``utils/profiling.py`` (``forward_flops`` /
``train_step_flops``), kept here so that no later PR can move the yardstick;
``selftest`` checks the two still agree. Matmul FLOPs only (2*M*N*K per
contraction), no recompute: embedding gathers, LayerNorm, softmax and biases
are O(L*D) and left out, as there.

``model`` is the ``model`` object of a ``benchmark/configs/<config>.json``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..harness import pkg
from ..reference import encoder_fp32

#: The program computes the encoder in bf16 (8 bits of mantissa) with
#: float32 softmax and LayerNorm statistics, and the head in float32; the
#: reference is float32 throughout. What is compared is what separates one
#: input from another: each sequence's last hidden states over its real
#: tokens (``[tokens, dim]``, relative L2 error), not two logits, which a
#: young model gives nearly alike for every flow.
#: tools/tolerance_probe.py measured at the published sizes, on random
#: weights (PERF.md section 2): the program against the reference 0.73%
#: (6 layers) and 1.16% (24 layers), the reference rounded to bfloat16 the
#: same; the reference with every weight and sub-layer output rounded to
#: float8 (e4m3) 11.2% and 19.5%. ``hidden_rel`` sits midway on a log scale:
#: 3 x over the worst bf16, 3 x under the best float8. The nearest other
#: sequence lies 28-57% away, so a model that ignored or mixed up its
#: inputs fails the ``binding``: every sequence at least that many times
#: nearer to its own reference than to any other's.
#: The logits follow from the CLS vector through the head, so theirs is the
#: second check: the error against the logit a unit-variance CLS vector
#: gives (``logit_scale``; a young model answers with logits of 0.2, which
#: would make a plain relative error a lottery). The probe: the program
#: 1.0% and 2.5% (6 and 24 layers), the float8 reference 15.9% and 38%; on
#: the chip bf16 measured 0.07-0.8% in the training cells and up to 2.4%
#: through the 24-layer served path (PERF.md). ``logit_rel`` sits midway on
#: a log scale again.
#: ``reply_abs``, a served probability against the reference's: bf16
#: through the served path measured 0.002-0.008 on the chip (PERF.md
#: section 7), the reference rounded to float8 0.032 (6 layers) and 0.103
#: (24 layers) (tools/tolerance_probe.py).
TOLERANCES = {"hidden_rel": 0.035, "logit_rel": 0.06, "binding": 2.0, "reply_abs": 0.02}

#: Keys of a configuration's ``model`` that are not sizes: a rehearsal keeps them.
REHEARSAL_KEEPS = ("gelu", "dropout", "attention_dropout", "head_dropout", "n_classes")


# ------------------------------------------------------------ the program
def model_config(model: dict):
    """The program's configuration object for ``model``."""
    return pkg("config").ModelConfig(**model)


def tiny(model: dict) -> dict:
    """The model a CPU rehearsal runs: the tiny preset's sizes, and every
    other key of the configuration."""
    preset = dataclasses.asdict(pkg("config").ModelConfig.tiny())
    return {**preset, **{k: model[k] for k in REHEARSAL_KEEPS}}


def init_params(model_cfg, key):
    """The model's weights, random from ``key``: the body of one jitted call."""
    m = pkg("models.distilbert")
    return m.init_params(m.DDoSClassifier(model_cfg), model_cfg, key)


def program(model_cfg):
    """``(params, ids, mask) -> (last hidden states, logits)`` through the
    program's own classes, as its eval path calls them."""
    m = pkg("models.distilbert")

    def forward(p, i, a):
        hidden = m.DistilBertEncoder(model_cfg).apply({"params": p["encoder"]}, i, a, True)
        return hidden, m.DDoSClassifier(model_cfg).apply({"params": p}, i, a, True)

    return forward


# ---------------------------------------------------------- the reference
#: The plain float32 forward, ``(params, ids, mask, model, rnd=identity)``:
#: last hidden states ``[B, L, dim]`` and logits ``[B, n_classes]``. ``rnd``
#: rounds every weight and every sub-layer's output
#: (tools/tolerance_probe.py's lower precision).
reference = encoder_fp32.forward


def logit_scale(params, want: np.ndarray) -> float:
    """The larger of the largest reference logit and the head's largest
    column norm (0.55-0.64 here)."""
    head = np.asarray(params["classifier"]["kernel"], np.float64)
    return max(float(np.abs(want).max()), float(np.linalg.norm(head, axis=0).max()))


# --------------------------------------------------- operations and bytes
def forward_flops(model: dict, rows: int = 1, seq_len: int | None = None, **_counters) -> float:
    """One classifier forward pass over ``rows`` sequences: per layer the
    Q/K/V/output projections (8*L*D^2), the score and value contractions
    (4*L^2*D) and the two FFN matmuls (4*L*D*F); plus the CLS head."""
    L = model["max_len"] if seq_len is None else seq_len
    D, F = model["dim"], model["hidden_dim"]
    per_layer = 8 * L * D * D + 4 * L * L * D + 4 * L * D * F
    head = 2 * D * model["n_classes"]
    return float(rows) * (model["n_layers"] * per_layer + head)


def train_step_flops(model: dict, rows: int = 1, seq_len: int | None = None, **_counters) -> float:
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


def train_step_bytes(model: dict, steps: float = 1, **_counters) -> float:
    """The least HBM traffic of ``steps`` optimizer steps, whatever the
    batch: fp32 parameters read and written (8 B), gradients written and
    read (8 B), Adam's two moments read and written (16 B) = 32 B a
    parameter a step. Activations are left out: the floor, not an estimate."""
    return 32.0 * param_count(model) * steps
