"""Family ``kimi_linear``: the Kimi-Linear hybrid decoder with the paper's
head on the last real token (``models/kimi_linear.py`` under
``KimiLinearConfig``): Kimi Delta Attention and NoPE latent attention three to
one, a dense SwiGLU layer and then sparse expert layers of which this chip
holds a share.

``model`` is the ``model`` object of a ``benchmark/configs/<config>.json``:
the keyword arguments of the program's configuration object.

Operations: matmul FLOPs only (2*M*N*K a contraction), no recompute; the
routed experts by the token-slots REALLY routed to the experts held here
(``routed_slots_here``, which the driver reads from the program's counters and
puts on the ``fit`` span), or their mean ``tokens * k * held / n_experts`` a
layer where a caller gives none. ``selftest`` checks the program's own
``utils/profiling.py`` counts the same.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..harness import pkg
from ..reference import kimi_linear_fp32

#: The program computes the decoder in bf16 with float32 parameters, RMS
#: statistics, softmax, router scores, decay gate and recurrent state; the
#: reference is float32 throughout and computes KDA as the token recurrence.
#: What is compared is what the BERT family compares (each window's last
#: hidden states over its real tokens, relative L2; the logits over the logit
#: scale; the binding), and, new with this family's driver, the timed step
#: itself: one launch of ``engine.train_step`` at the cell's batch from the
#: seed's weights against the reference's loss (absolute), its gradient over
#: the batch's windows (relative L2 of the WHOLE tree and of the WORST LEAF
#: among those with at least ``grad_floor`` of the largest norm) and a
#: reference Adam step (relative L2 of the parameters' change; Adam's first
#: step is the learning rate times the gradient's sign, so this reads twice
#: the root of the share of elements whose sign differs, and 1 for a state
#: left unchanged).
#: A router's top-k is a discrete choice: bf16's rounding of the residual
#: stream flips it on 1.87-1.97% of the real token-slots (0.10-0.13% naming a
#: held expert), and a flip moves a token by a whole expert's share: compared
#: with a reference that chose for itself, the logits (ONE token a window) read
#: up to 6.24% in one window of 16 and under 4% in the others, single leaves'
#: gradients 6-77%, and float8 only 13.4% and 323% (my chip runs, PR 28, first
#: session; limits of 8%, 5%, 7% and 10% were tried on the logits in that
#: order, the last after the 6.24% was seen: a number with no upper reading).
#: So every continuous number is now compared under ONE choice: the reference
#: computes under the program's (``reference`` above, ``forced``), and the
#: choices are compared as choices (``flip_share``).
#: Readings under that rule (my chip runs, PR 28, second session; published
#: widths, 4,096 tokens; hidden / logits over the scale / loss / gradient
#: whole, worst leaf / parameters' change / flips): the program, seed
#: 2800000101: 1.87% (trained 1.79%) / 2.27% (0.65%) / 0.0045 / 2.99%, 14.2% /
#: 22.5% / 1.87%; the reference rounded to float8 e4m3 (tools/window_probe.py,
#: seed 7, 4 windows): 20.9% / 36.0% / 0.065 / 90.7%, 144.6% / 99.0% / 20.0%,
#: and its binding 6.1; on the CPU at 256-token rows the reference rounded to
#: bfloat16 read 1.88% / 2.02% / 0.0010 / 3.1%, 4.8% / 21.4% / 1.80% beside the
#: program's 2.37% / 1.14% / 0.0032 / 3.9%, 11.1% / 26.1% / 2.30%. Each limit
#: sits about midway, on a log scale, between the program's reading and
#: float8's: hidden 7.5% (1.87 | 20.9; kept from the first session's 2.70 |
#: 21.3), logits 10% (2.27 | 36.0: the limit the first session ended on now
#: has an upper reading), loss 0.05 (0.0045, and 0.029 without the forcing |
#: 0.065: the least room), gradient 30% (2.99 | 90.7), worst leaf 45% (14.2 |
#: 144.6), flips 6% (1.87 | 20.0); the parameters' change 60%, between the
#: reading and the 1 of an unchanged state with the more room above the
#: reading (22.5 | 100; float8 reads 99). The float8 reference fails every
#: one. PERF.md section 2 has the lines.
#: The worst leaf's 14.2% (5.8% on a second seed, and 47.28% on the seed of
#: the driver's check, over the limit) was a fault of the program that this
#: comparison found: the recomputation chose the top-k again, and otherwise,
#: so an expert's gradient missed or gained whole tokens. With the choice kept
#: (``ops/moe.py::ROUTE_CHOICE``) the program reads 4.04% on that seed and
#: 4.02-4.71% on three more (whole tree 2.8-3.1%, change 20.5-21.2%; my chip
#: runs, PR 28, third session); the limits are as they were set.
TOLERANCES = {
    "hidden_rel": 0.075, "logit_rel": 0.10, "binding": 2.0, "reply_abs": 0.02,
    "loss_abs": 0.05, "grad_rel": 0.3, "grad_leaf_rel": 0.45, "grad_floor": 1e-3, "update_rel": 0.6,
    "flip_share": 0.06,
}

#: Tokens a chunk of the program's chunkwise delta rule (``ops/kda.py::CHUNK``,
#: an implementation size; tests/test_benchmark_families.py holds the two equal).
KDA_CHUNK = 64

#: Rows the program's forward takes at once in ``program`` (the held-out
#: windows go through in groups, so that 16 windows of 4,096 tokens fit).
GROUP = 4


# ------------------------------------------------------------ the program
def model_config(model: dict):
    """The program's configuration object for ``model``."""
    kw = dict(model)
    kw["full_attn_layers"] = tuple(kw["full_attn_layers"])
    return pkg("config").KimiLinearConfig(**kw)


def tiny(model: dict) -> dict:
    """The model a CPU rehearsal runs: the tiny preset's sizes with the keys
    of the configuration that are not sizes."""
    preset = dataclasses.asdict(pkg("config").KimiLinearConfig.tiny())
    preset["full_attn_layers"] = list(preset["full_attn_layers"])
    keep = ("remat", "n_classes", "routed_scale", "rms_norm_eps", "initializer_range")
    return {**preset, **{k: model[k] for k in keep if k in model}}


def init_params(model_cfg, key):
    """The model's weights, random from ``key``: the body of one jitted call."""
    m = pkg("models")
    return m.init_params(m.build_classifier(model_cfg), model_cfg, key)


def _grouped(fn, ids, mask):
    """``fn`` over the rows in groups of at most GROUP (``lax.map``)."""
    import jax

    n = len(ids)
    g = max(d for d in range(1, GROUP + 1) if n % d == 0)
    out = jax.lax.map(
        lambda x: fn(*x), (ids.reshape(n // g, g, -1), mask.reshape(n // g, g, -1))
    )
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), out)


@functools.lru_cache(maxsize=None)
def program(model_cfg):
    """``(params, ids, mask) -> (last hidden states, logits)`` through the
    program's own classes, as its eval path calls them. One function a
    configuration, so that the comparison's second set of weights finds the
    first's compiled program."""
    import jax.numpy as jnp

    kimi = pkg("models.kimi_linear")
    classifier = pkg("models").build_classifier(model_cfg)

    def forward(p, i, a):
        def rows(i, a):
            hidden = kimi.KimiLinearEncoder(model_cfg).apply({"params": p["encoder"]}, i, a, True)
            return hidden, classifier.apply({"params": p}, i, a, True)

        return _grouped(rows, jnp.asarray(i), jnp.asarray(a))

    return forward


@functools.lru_cache(maxsize=None)
def routing(model_cfg):
    """``(params, ids, mask) -> [idx [B, L, k] per expert layer]``: the
    experts the PROGRAM's router chose (its layers sow them as
    intermediates): what the reference is made to compute under, and what
    the driver counts the flipped choices of."""
    import jax.numpy as jnp

    classifier = pkg("models").build_classifier(model_cfg)

    def chosen(p, i, a):
        def rows(i, a):
            _, sown = classifier.apply({"params": p}, i, a, True, mutable=["intermediates"])
            enc = sown["intermediates"]["encoder"]
            return [
                enc[f"layer_{n}"]["moe"]["chosen"][0].reshape(i.shape + (-1,))
                for n in range(model_cfg.n_layers) if model_cfg.is_moe(n)
            ]

        return _grouped(rows, jnp.asarray(i), jnp.asarray(a))

    return chosen


# ---------------------------------------------------------- the reference
def reference(params, ids, mask, model: dict, **rnd):
    """The plain float32 forward ``(params, ids, mask, model, rnd=identity)
    -> (last hidden states, logits)``, computed under the PROGRAM's choice of
    experts on these rows (``kimi_linear_fp32``'s ``forced``): a top-k is a
    discrete decision that bf16's rounding of the residual stream flips on
    about 2% of the token-slots, and a flip that names a held expert moves
    that token by a whole expert's share (the logits, which read one token a
    window, by up to 6% of their scale: my chip runs, PR 28). Continuous
    numbers are compared under one choice; the choices are compared as
    choices (the driver's ``step.flip_share``)."""
    import jax

    chosen = jax.jit(routing(model_config(model)))(params, ids, mask)
    return kimi_linear_fp32.forward(params, ids, mask, model, forced=chosen, **rnd)


reference_loss_and_grads = kimi_linear_fp32.loss_and_grads
reference_adam_step = kimi_linear_fp32.adam_first_step


def logit_scale(params, want: np.ndarray) -> float:
    """The larger of the largest reference logit and the head's largest
    column norm, as the BERT family has it."""
    head = np.asarray(params["classifier"]["kernel"], np.float64)
    return max(float(np.abs(want).max()), float(np.linalg.norm(head, axis=0).max()))


# --------------------------------------------------- operations and bytes
def _layers(model: dict) -> tuple[int, int, int, int]:
    """(KDA layers, MLA layers, dense-FFN layers, expert layers)."""
    n = model["n_layers"]
    mla = sum(1 for i in range(n) if i + 1 in model["full_attn_layers"])
    dense = min(n, model["first_dense_layers"])
    return n - mla, mla, dense, n - dense


def kda_chunk_flops(model: dict, tokens: float) -> float:
    """Forward FLOPs of the chunk recurrence alone (``ops/kda.py``, scope
    ``kda/chunks``) for ``tokens`` tokens of ONE layer: per chunk of C tokens
    and head, the two lower-triangular pair matrices (2 * C^2 * dk), the
    substitution (C^2 * (dk + dv)), the in-chunk product with U (C^2 * dv)
    and the three state products (6 * C * dk * dv)."""
    C, H, d = KDA_CHUNK, model["kda_heads"], model["kda_head_dim"]
    return tokens * H * (5 * C * d + 6 * d * d)


def kda_chunk_bytes(model: dict, tokens: float) -> float:
    """The least HBM traffic of the chunk recurrence's forward for ``tokens``
    tokens of one layer: q, k and the log-decay read in float32, v in bf16,
    the write strength, and the float32 output written."""
    H, d = model["kda_heads"], model["kda_head_dim"]
    return tokens * H * (3 * d * 4 + d * 2 + 4 + d * 4)


def expert_flops(model: dict, slots: float) -> float:
    """Forward FLOPs of the grouped product (scope ``moe/experts``) for
    ``slots`` token-slots: three D x F contractions a slot."""
    return slots * 6 * model["dim"] * model["expert_dim"]


def expert_bytes(model: dict, slots: float, layers: float = 1, steps: float = 1) -> float:
    """The least HBM traffic of the grouped product's forward: every held
    expert's weights read once a layer a step in bf16, and a slot's row read
    and its result written in bf16."""
    held = model["experts_held"] * 3 * model["dim"] * model["expert_dim"] * 2
    return held * layers * steps + slots * 2 * model["dim"] * 2


def mean_slots(model: dict, tokens: float) -> float:
    """The token-slots a chip's held experts get on average, all layers."""
    return _layers(model)[3] * tokens * model["experts_per_token"] * model["experts_held"] / model["n_experts"]


def forward_flops(
    model: dict, rows: float = 1, seq_len: int | None = None, *,
    routed_slots_here: float | None = None, **_counters,
) -> float:
    """One classifier forward pass over ``rows`` windows of ``seq_len``
    (default ``max_len``) tokens. Per token: a KDA mixer's projections
    (q, k, v, output, the two low-rank gates, the write strength), short
    convolutions and chunk recurrence; an MLA mixer's projections and its
    causal scores and values (``H * (L + 1) * (dqk + dv)``); the dense FFN;
    an expert layer's router and shared expert; plus the routed experts by
    ``routed_slots_here`` and the head a row."""
    L = model["max_len"] if seq_len is None else seq_len
    D = model["dim"]
    tokens = float(rows) * L
    n_kda, n_mla, n_dense, n_moe = _layers(model)
    Hd = model["kda_heads"] * model["kda_head_dim"]
    r = model["gate_rank"]
    kda = (
        2 * D * Hd * 4 + 2 * (2 * D * r + 2 * r * Hd) + 2 * D * model["kda_heads"]
        + 3 * 2 * model["conv_kernel"] * Hd
    )
    H, dn, dr, dv = model["n_heads"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    mla = (
        2 * D * H * (dn + dr) + 2 * D * (model["kv_lora_rank"] + dr)
        + 2 * model["kv_lora_rank"] * H * (dn + dv) + 2 * H * dv * D
        + H * (L + 1) * (dn + dr + dv)
    )
    dense = 6 * D * model["hidden_dim"]
    moe = 2 * D * model["n_experts"] + 6 * D * model["expert_dim"] * model["n_shared_experts"]
    per_token = n_kda * kda + n_mla * mla + n_dense * dense + n_moe * moe
    slots = mean_slots(model, tokens) if routed_slots_here is None else float(routed_slots_here)
    return (
        tokens * per_token + n_kda * kda_chunk_flops(model, tokens) + expert_flops(model, slots)
        + float(rows) * 2 * D * model["n_classes"]
    )


def train_step_flops(model: dict, rows: float = 1, seq_len: int | None = None, **counters) -> float:
    """Forward + backward = 3x forward; the recomputed forward of
    ``remat`` is not counted."""
    return 3.0 * forward_flops(model, rows, seq_len, **counters)


def param_count(model: dict) -> int:
    """Parameters as ``models/kimi_linear.py`` builds them (no biases but
    the head's; the router's selection bias is a leaf of the tree)."""
    D, Hd, r = model["dim"], model["kda_heads"] * model["kda_head_dim"], model["gate_rank"]
    n_kda, n_mla, n_dense, n_moe = _layers(model)
    kda = (
        4 * D * Hd + 3 * model["conv_kernel"] * Hd + 2 * (D * r + r * Hd) + D * model["kda_heads"]
        + model["kda_heads"] + Hd + model["kda_head_dim"]
    )
    H, dn, dr, dv = model["n_heads"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    rank = model["kv_lora_rank"]
    mla = D * H * (dn + dr) + D * (rank + dr) + rank + rank * H * (dn + dv) + H * dv * D
    F = model["expert_dim"]
    moe = D * model["n_experts"] + model["n_experts"] + 3 * D * F * (model["experts_held"] + model["n_shared_experts"])
    norms = 2 * D * model["n_layers"] + D
    return (
        model["vocab_size"] * D + n_kda * kda + n_mla * mla + n_dense * 3 * D * model["hidden_dim"]
        + n_moe * moe + norms + D * model["n_classes"] + model["n_classes"]
    )


def train_step_bytes(model: dict, steps: float = 1, **_counters) -> float:
    """The least HBM traffic of ``steps`` optimizer steps: 32 B a held
    parameter a step (fp32 parameters, gradients and Adam's two moments,
    each read and written). Activations are left out: the floor."""
    return 32.0 * param_count(model) * steps


def scope_work(model: dict, scope: str, *, tokens: float, steps: float = 1,
               routed_slots_here: float | None = None, **_counters):
    """``(FLOPs, bytes)`` a traced span's work requires inside the named
    scope, forward and backward (3x the forward's operations, and its bytes
    read once more and the gradients written: 3x), for
    ``readers/scope_roofline``; None for a scope this family has no count of."""
    n_kda, _, _, n_moe = _layers(model)
    if scope == "kda/chunks":
        return 3.0 * n_kda * kda_chunk_flops(model, tokens), 3.0 * n_kda * kda_chunk_bytes(model, tokens)
    if scope == "moe/experts":
        slots = mean_slots(model, tokens) if routed_slots_here is None else float(routed_slots_here)
        return 3.0 * expert_flops(model, slots), 3.0 * expert_bytes(model, slots, n_moe, steps)
    return None
