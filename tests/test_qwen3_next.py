"""The fourth model class (``models/qwen3_next.py``): the scalar-gate delta
rule, the grouped key heads, the quarter rotary, the zero-centred norm and the
softmax router against their plain forms, the whole program and each kind of
layer against the plain float32 reference
(``benchmark/reference/qwen3_next_fp32.py``: the token recurrence, every key
scored head by head, every held expert on every token), the share test of
expert parallelism, and the engine's normal path on it. CPU, small sizes,
seeded random weights."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import qwen3_next as family
from benchmark.reference import qwen3_next_fp32 as ref
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    Qwen3NextConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import (
    TokenizedSplit,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models import (
    build_classifier,
    init_params,
    model_preset,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.blocks import (
    SparseMoE,
    rms,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.qwen3_next import (
    GatedDeltaNet,
    Qwen3NextBlock,
    Qwen3NextClassifier,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import kda
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
    VMEM_BUDGET,
    _vmem_bytes,
    causal_tile,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.moe import (
    route_topk,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.rope import (
    apply_rope,
    rope_tables,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.checkpoint import (
    Checkpointer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
    loss_fn,
)
from tests.test_kimi_linear import _eqns, _rel

TINY = Qwen3NextConfig.tiny(max_len=64)
_same = lambda a: a  # noqa: E731


def _model_dict(cfg):
    return dataclasses.asdict(cfg)


def _rows(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    L = cfg.max_len
    mask = (np.arange(L)[None, :] < np.asarray(lens)[:, None]).astype(np.int32)
    ids = rng.integers(1, cfg.vocab_size, (len(lens), L)).astype(np.int32) * mask
    return ids, mask


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(build_classifier(TINY), TINY, jax.random.key(1))


# ------------------------------------------------------------------ the ops
def _gdn_inputs(L, seed=0, B=2, H=4, d=16, decay=2.0):
    """q, k, v and the write strength as a layer makes them, and ONE
    log-decay a head and token."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(B, H, L, d))).astype(np.float32) * d**-0.5
    k = unit(rng.normal(size=(B, H, L, d))).astype(np.float32)
    v = rng.normal(size=(B, H, L, d)).astype(np.float32)
    g = (-np.abs(rng.normal(size=(B, H, L))) * decay).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, size=(B, H, L)).astype(np.float32)
    return q, k, v, g, beta


def _scalar_gate_rule(q, k, v, g, beta):
    """``S_t = (I - b_t k_t k_t^T) e^{g_t} S_{t-1} + b_t k_t v_t^T``, ``o_t =
    S_t^T q_t``, token by token in numpy float64: the published rule, with the
    decay one number a head."""
    B, H, L, dk = q.shape
    o = np.zeros(v.shape, np.float64)
    for b in range(B):
        for h in range(H):
            S = np.zeros((dk, v.shape[-1]))
            for t in range(L):
                k_t = k[b, h, t].astype(np.float64)
                S = np.exp(np.float64(g[b, h, t])) * S
                S = S - beta[b, h, t] * np.outer(k_t, k_t @ S) + beta[b, h, t] * np.outer(k_t, v[b, h, t])
                o[b, h, t] = S.T @ q[b, h, t]
    return o


@pytest.mark.parametrize("L, decay", [(64, 2.0), (100, 0.1), (192, 3.0)], ids=["a-chunk", "a-padded-tail", "fast-decay"])
def test_a_broadcast_gate_through_the_kda_kernels_is_the_scalar_gate_rule(L, decay):
    """What the linear layer runs: ``kda_chunked`` with the head's one decay
    broadcast over its channels, against ``kda_recurrent`` under the same
    broadcast AND against the scalar-gate rule written out above."""
    q, k, v, g, beta = _gdn_inputs(L, seed=L, decay=decay)
    wide = np.broadcast_to(g[..., None], q.shape)
    got = kda.kda_chunked(q, k, v, wide, beta)
    assert _rel(got, kda.kda_recurrent(q, k, v, wide, beta)) < 2e-5
    assert _rel(np.asarray(got, np.float64), _scalar_gate_rule(q, k, v, g, beta)) < 2e-5


def _scalar_gate_grads(q, k, v, g, beta, cot):
    """The gradients of ``sum(o * cot)`` through the scalar-gate rule, ``g``
    ONE number a head and token: the rule as a ``lax.scan`` in float32, which
    :func:`_scalar_gate_rule` holds to the numpy loop."""
    def rule(q, k, v, g, beta):
        def step(S, x):
            q_t, k_t, v_t, g_t, b_t = x
            S = jnp.exp(g_t)[..., None, None] * S
            S = S + b_t[..., None, None] * k_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

        xs = tuple(jnp.moveaxis(jnp.asarray(x), 2, 0) for x in (q, k, v, g, beta))
        _, o = jax.lax.scan(step, jnp.zeros((*q.shape[:2], q.shape[3], v.shape[3]), jnp.float32), xs)
        return jnp.moveaxis(o, 0, 2)

    assert _rel(np.asarray(rule(q, k, v, g, beta), np.float64), _scalar_gate_rule(q, k, v, g, beta)) < 2e-5
    return jax.grad(lambda *a: (rule(*a) * cot).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)


@pytest.mark.parametrize("L, decay", [(64, 2.0), (100, 0.1), (192, 3.0)], ids=["a-chunk", "a-padded-tail", "fast-decay"])
def test_the_gradient_through_a_broadcast_gate_is_the_scalar_gate_rules(L, decay):
    """``jax.grad`` through what the linear layer runs (``kda_fwd`` and ONE
    launch of ``kda_bwd``, the head's decay broadcast over its channels)
    against ``jax.grad`` through the scalar-gate rule: the head's gradient is
    the sum of its channels', which is what the broadcast's transpose gives."""
    q, k, v, g, beta = _gdn_inputs(L, seed=L, decay=decay)
    cot = np.random.default_rng(L + 1).normal(size=v.shape).astype(np.float32)
    want = _scalar_gate_grads(q, k, v, g, beta, cot)
    through = lambda q, k, v, g, beta: kda.kda_chunked(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)  # noqa: E731
    got = jax.grad(lambda *a: (through(*a) * cot).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for name, a, b, limit in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want, (5e-6, 5e-6, 5e-6, 2e-5, 5e-6)):
        assert a.shape == b.shape and _rel(a, b) < limit, (name, _rel(a, b))


def test_grouped_key_heads_are_explicitly_repeated_keys(tiny_params):
    """Two value heads read one key head: the mixer with 2 key heads under 4
    value heads gives what a mixer with 4 key heads gives whose q and k
    projections (and their convolution channels) are the 2 heads' repeated."""
    lp = jax.tree.map(np.asarray, tiny_params["encoder"]["layer_0"]["gdn"])
    Hk, Hv, dk, dv = TINY.linear_key_heads, TINY.linear_value_heads, TINY.linear_key_dim, TINY.linear_value_dim
    qk = Hk * dk

    def repeat_heads(cols):  # [..., Hk * dk] -> [..., Hv * dk], head h -> heads 2h, 2h + 1
        return np.repeat(cols.reshape(cols.shape[:-1] + (Hk, dk)), Hv // Hk, axis=-2).reshape(cols.shape[:-1] + (Hv * dk,))

    def widen(a):
        return np.concatenate([repeat_heads(a[..., :qk]), repeat_heads(a[..., qk : 2 * qk]), a[..., 2 * qk :]], axis=-1)

    wide = {**lp, "qkvz_proj": {"kernel": widen(lp["qkvz_proj"]["kernel"])}, "conv": widen(lp["conv"])}
    x = np.random.default_rng(2).normal(size=(2, 64, TINY.dim)).astype(np.float32)
    got = GatedDeltaNet(TINY).apply({"params": lp}, x)
    want = GatedDeltaNet(TINY.replace(linear_key_heads=Hv)).apply({"params": wide}, x)
    assert wide["qkvz_proj"]["kernel"].shape[1] == 2 * Hv * dk + 2 * Hv * dv
    assert _rel(got, want) < 1e-6
    # and the reference's mixer reads the same leaves to the same result
    with jax.default_matmul_precision("highest"):
        plain = jnp.stack([ref._gdn(x[b], lp, _model_dict(TINY), _same) for b in range(2)])
    assert _rel(got, plain) < 1e-5


def test_a_quarter_of_a_head_is_rotated_and_the_rest_untouched():
    cut = Qwen3NextConfig.ep16_cut()
    rot = int(cut.head_dim * cut.rotary_share)
    cos, sin = rope_tables(32, rot, cut.rope_theta)
    assert rot == 64 and cos.shape == (32, 32) and cos[0, 0] == 1.0
    assert np.isclose(cos[1, 1], np.cos(1e7 ** (-2 / 64)))
    x = np.random.default_rng(0).normal(size=(1, 32, 2, cut.head_dim)).astype(np.float32)
    y = np.asarray(apply_rope(x, cos, sin))
    assert np.array_equal(y[..., 64:], x[..., 64:])  # dimensions 64-255 pass through
    assert np.array_equal(y[:, 0], x[:, 0]) and not np.allclose(y[:, 1:, :, :64], x[:, 1:, :, :64])
    # rotate-half: dimension i turns with dimension i + 32
    i, t = 3, 5
    angle = t * 1e7 ** (-2 * i / 64)
    assert np.allclose(y[0, t, 0, i], x[0, t, 0, i] * np.cos(angle) - x[0, t, 0, i + 32] * np.sin(angle), atol=1e-5)


def test_the_softmax_routers_top_ten_weights_sum_to_one():
    """The published router: softmax over 512, the top 10, their
    probabilities renormalised, no scale."""
    cut = Qwen3NextConfig.ep16_cut()
    logits = np.random.default_rng(1).normal(size=(50, cut.n_experts)).astype(np.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    idx, w = route_topk(scores, jnp.zeros((), jnp.float32), cut.experts_per_token, cut.routed_scale)
    assert idx.shape == w.shape == (50, 10) and cut.routed_scale == 1.0
    assert np.allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6) and float(w.min()) > 0.0
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.argsort(-logits, -1)[:, :10], -1)).all()
    want_idx, want_w = ref.route(jnp.asarray(logits), {"router": jnp.eye(cut.n_experts)}, {"experts_per_token": 10})
    assert np.array_equal(np.asarray(want_idx), np.asarray(idx)) and np.allclose(np.asarray(want_w), np.asarray(w), atol=1e-6)


def test_the_zero_centred_norm_is_one_plus_its_weight():
    x = np.random.default_rng(0).normal(size=(3, 5, 32)).astype(np.float32) * 3.0
    norm = rms(TINY, "n", True)
    params = norm.init(jax.random.key(0), x)["params"]
    assert float(jnp.abs(params["scale"]).max()) == 0.0  # w starts at 0: the layer starts as x / rms(x)
    plain = x / np.sqrt((x * x).mean(-1, keepdims=True) + TINY.rms_norm_eps)
    assert _rel(norm.apply({"params": params}, x), plain) < 1e-6
    w = np.linspace(-0.5, 0.5, 32).astype(np.float32)
    assert _rel(norm.apply({"params": {"scale": w}}, x), plain * (1.0 + w)) < 1e-6
    assert _rel(ref._norm(jnp.asarray(x), w, TINY.rms_norm_eps), plain * (1.0 + w)) < 1e-6
    # the other classes' norm is untouched: its leaf starts at 1 and multiplies
    assert float(rms(TINY, "n").init(jax.random.key(0), x)["params"]["scale"].min()) == 1.0


@pytest.mark.parametrize(
    "length, dqk, dv, takes_the_kernels",
    [(16384, 256, 256, False), (8192, 256, 256, False), (4096, 256, 256, True), (8192, 128, 128, True), (16384, 128, 128, False)],
    ids=["this-cell", "256-wide-at-8k", "256-wide-at-4k", "laguna-cell", "128-wide-at-16k"],
)
def test_which_rows_the_causal_kernels_take_is_pinned(length, dqk, dv, takes_the_kernels):
    """``causal_tile`` sends a head to the XLA blocks when a whole head's row
    passes the kernels' VMEM budget. Pinned to what the code does: the day a
    kernel walks a long row tile by tile through HBM, this says so, and the
    new cell's ``attn_kernel_share`` leaves 0."""
    assert (causal_tile(length, dqk, dv, 2) is not None) is takes_the_kernels
    assert (_vmem_bytes(length, length, dqk, dv, 2) <= VMEM_BUDGET) is takes_the_kernels
    if (length, dqk) in ((16384, 256), (8192, 256)):  # the two readings ROADMAP.md quotes
        assert round(_vmem_bytes(length, length, dqk, dv, 2) / 2**20) == {16384: 186, 8192: 97}[length]


# ------------------------------------------- the program and the reference
@pytest.mark.parametrize("layer", [0, 3], ids=["gated-deltanet", "gated-attention"])
def test_each_kind_of_layer_against_the_reference(tiny_params, layer):
    """One block of the program on a random residual stream against the
    reference's layer, float32, and the gradient with respect to its input."""
    rng = np.random.default_rng(layer)
    _, mask = _rows(TINY, [64, 41])
    x = rng.normal(size=(2, TINY.max_len, TINY.dim)).astype(np.float32)
    lp = tiny_params["encoder"][f"layer_{layer}"]
    assert ("attn" in lp) == (TINY.mixer(layer) == "full") and ("gdn" in lp) == (TINY.mixer(layer) == "linear")
    block = Qwen3NextBlock(TINY, layer)
    got = block.apply({"params": lp}, x, mask)
    model = _model_dict(TINY)
    w = mask[..., None]
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._layer(model, layer, _same)(x[b], mask[b], lp, None)[0] for b in range(2)])
        assert _rel(got * w, want * w) < 1e-5
        g = jax.grad(lambda x: ((block.apply({"params": lp}, x, mask) * w) ** 2).sum())(x)
        g_want = jax.grad(
            lambda x: sum(((ref._layer(model, layer, _same)(x[b], mask[b], lp, None)[0] * w[b]) ** 2).sum() for b in range(2))
        )(x)
    assert _rel(g * w, g_want * w) < 1e-4


def test_program_against_the_reference_fp32(tiny_params):
    """Hidden states, logits, loss and gradients at the tiny preset in
    float32: the chunked, blocked program and the token-by-token, dense
    reference agree to rounding; a forced choice of experts is computed, and
    handed back."""
    ids, mask = _rows(TINY, [64, 50, 37, 33])
    labels = np.array([0, 1, 1, 0], np.int32)
    model = _model_dict(TINY)
    hidden, logits = jax.jit(family.program(TINY))(tiny_params, ids, mask)
    want_h, want_z = ref.forward(tiny_params, ids, mask, model)
    w = mask[..., None]
    assert _rel(hidden * w, want_h * w) < 1e-5
    assert float(jnp.abs(logits - want_z).max()) < 1e-5
    head = tiny_params["classifier"]
    assert float(jnp.abs(logits[1] - (hidden[1, 49] @ head["kernel"] + head["bias"])).max()) < 1e-5
    batch = {"input_ids": ids, "attention_mask": mask, "labels": labels}
    classifier = build_classifier(TINY)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: loss_fn(classifier, p, batch, jax.random.key(0))))(tiny_params)
    want_loss, want_grads, routes = ref.loss_and_grads(tiny_params, ids, mask, labels, model)
    assert abs(float(loss) - want_loss) < 1e-5
    rel = jax.tree.map(_rel, grads, want_grads)
    norms = jax.tree.map(lambda b: float(jnp.linalg.norm(b.ravel())), want_grads)
    top = max(jax.tree.leaves(norms))
    worst = max(r for r, n in zip(jax.tree.leaves(rel), jax.tree.leaves(norms)) if n > 1e-3 * top)
    assert worst < 1e-4, worst
    assert min(jax.tree.leaves(norms)) > 0.0  # a gradient reaches every leaf, the shared expert's gate too
    forced = [np.roll(np.asarray(idx), 1, axis=-2) for idx, _ in routes]  # every token gets its neighbour's experts
    h_forced, _ = ref.forward(tiny_params, ids, mask, model, forced=forced)
    assert _rel(h_forced * w, want_h * w) > 1e-3
    own = [np.asarray(idx) for idx, _ in routes]
    h_own, z_own = ref.forward(tiny_params, ids, mask, model, forced=own)
    assert _rel(h_own * w, want_h * w) < 1e-6 and float(jnp.abs(z_own - want_z).max()) < 1e-6
    got = jax.jit(family.routing(TINY))(tiny_params, ids, mask)
    assert len(got) == len(routes) == TINY.n_layers
    for g, (want_idx, _) in zip(got, routes):
        assert (np.sort(np.asarray(g)[mask > 0], -1) == np.sort(np.asarray(want_idx)[mask > 0], -1)).all()


def test_the_references_query_blocks_are_the_dense_mask(tiny_params, monkeypatch):
    """The reference scores a head query block by query block; with blocks
    shorter than the row (and a last block past its end) it gives what one
    block over the whole row gives."""
    lp = tiny_params["encoder"]["layer_3"]["attn"]
    _, mask = _rows(TINY, [50])
    x = np.random.default_rng(4).normal(size=(TINY.max_len, TINY.dim)).astype(np.float32)
    model = _model_dict(TINY)
    with jax.default_matmul_precision("highest"):
        whole = ref._attention(jnp.asarray(x), mask[0], lp, model, _same)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 24)
        assert _rel(ref._attention(jnp.asarray(x), mask[0], lp, model, _same), whole) < 1e-6


def test_the_sixteen_shares_add_up_to_the_uncut_layer(tiny_params):
    """The guide's share test through the program's own layer: the 16 chips of
    the deployment each hold a sixteenth of the experts (here 1 of 16); the
    routed parts all the shares give, with the gated shared expert (whole on
    every chip) counted once, add up to what the uncut reference gives for the
    whole layer; every token-slot lands on exactly one share."""
    cfg = TINY
    E = cfg.n_experts
    rng = np.random.default_rng(5)
    lp = jax.tree.map(np.asarray, tiny_params["encoder"]["layer_1"]["moe"])
    D, F = cfg.dim, cfg.expert_dim
    experts = {
        "experts_gate": rng.normal(size=(E, D, F)).astype(np.float32) * 0.2,
        "experts_up": rng.normal(size=(E, D, F)).astype(np.float32) * 0.2,
        "experts_down": rng.normal(size=(E, F, D)).astype(np.float32) * 0.2,
    }
    full = {**lp, **experts, "router": lp["router"] * 20.0}  # scores far enough apart that the choice matters
    x = rng.normal(size=(1, 60, D)).astype(np.float32)
    ones = np.ones((1, 60), np.int32)
    m = {**_model_dict(cfg), "experts_held": E}
    with jax.default_matmul_precision("highest"):
        want, (idx, _) = ref._moe(jnp.asarray(x[0]), full, m, _same)
        shared = ref._shared(jnp.asarray(x[0]), full, _same)
    assert _rel(shared, 0.5 * ref._swiglu(jnp.asarray(x[0]), full["shared"], _same)) > 1e-3  # the gate is not a constant half

    def share(lo):
        held = cfg.replace(experts_held=1, expert_offset=lo)
        layer = SparseMoE(held, select_bias=False, score="softmax", shared_gate=True)
        own = {**full, **{k: v[lo : lo + 1] for k, v in experts.items()}}
        y, sown = layer.apply({"params": own}, x, ones, mutable=["route", "intermediates"])
        return y[0], int(sown["route"]["slots"].sum()), int(sown["route"]["overflow"])

    total, seen = shared, 0
    for lo in range(E):
        y, slots, overflow = share(lo)
        assert overflow == 0
        total, seen = total + (y - shared), seen + slots
    assert seen == 60 * cfg.experts_per_token == int(np.asarray(idx).size)
    assert float(jnp.abs(total - want).max()) < 1e-5
    # and one share alone is what the reference gives when it is given that share
    part, _ = ref._moe(
        jnp.asarray(x[0]), {**full, **{k: v[5:6] for k, v in experts.items()}}, {**m, "experts_held": 1, "expert_offset": 5}, _same
    )
    assert float(jnp.abs(share(5)[0] - part).max()) < 1e-5


def test_the_other_classes_expert_layer_is_what_it_was(tiny_params):
    """``SparseMoE``'s new switches default to what the Kimi and Laguna
    classes run: a sigmoid score and an ungated shared expert, no new leaf."""
    layer = SparseMoE(TINY, select_bias=False)
    assert (layer.score, layer.shared_gate) == ("sigmoid", False)
    x = np.random.default_rng(0).normal(size=(1, 20, TINY.dim)).astype(np.float32)
    params = layer.init(jax.random.key(0), x, np.ones((1, 20), np.int32))["params"]
    assert set(params) == {"router", "experts_gate", "experts_up", "experts_down", "shared"}
    mine = SparseMoE(TINY, select_bias=False, score="softmax", shared_gate=True)
    assert set(mine.init(jax.random.key(0), x, np.ones((1, 20), np.int32))["params"]) == set(params) | {"shared_gate"}


def test_program_in_bf16_is_within_the_familys_limits(tiny_params):
    cfg = TINY.replace(compute_dtype="bfloat16", remat=True)
    ids, mask = _rows(cfg, [64, 60, 51, 40], seed=2)
    hidden, logits = jax.jit(family.program(cfg))(tiny_params, ids, mask)
    model = _model_dict(cfg)
    chosen = jax.jit(family.routing(cfg))(tiny_params, ids, mask)
    want_h, want_z = ref.forward(tiny_params, ids, mask, model, forced=chosen)
    w = mask[..., None].astype(np.float32)
    err = max(_rel(np.asarray(hidden[i], np.float32) * w[i], want_h[i] * w[i]) for i in range(4))
    tol = family.TOLERANCES
    assert 1e-4 < err < tol["hidden_rel"], err
    assert float(jnp.abs(logits - want_z).max()) / family.logit_scale(tiny_params, np.asarray(want_z)) < tol["logit_rel"]


def test_remat_changes_no_number_and_keeps_the_routers_choice(tiny_params):
    ids, mask = _rows(TINY, [64, 40])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([1, 0], np.int32)}
    grad_of = lambda cfg: jax.grad(lambda p: loss_fn(build_classifier(cfg), p, batch, jax.random.key(0)))  # noqa: E731
    cfg = TINY.replace(remat=True)
    for a, b in zip(jax.tree.leaves(jax.jit(grad_of(TINY))(tiny_params)), jax.tree.leaves(jax.jit(grad_of(cfg))(tiny_params))):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * (1.0 + float(jnp.abs(b).max()))
    jaxpr = jax.make_jaxpr(grad_of(cfg))(tiny_params)
    top_ks = ["remat2" in outer for _, outer, eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "top_k"]
    assert top_ks == [False] * cfg.n_layers


def test_the_step_names_its_scopes_and_none_of_them_is_kda(tiny_params):
    """The scopes the benchmark's readers look for are in ``engine.train_step``;
    the linear layers run ``ops/kda.py``'s kernels under ``gdn/chunks``, and
    nothing lands under a scope ``kda`` (the Kimi cell's metrics read that)."""
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, [64, 30])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([0, 1], np.int32)}
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    eqns = list(_eqns(jax.make_jaxpr(trainer.train_step.__wrapped__)(state, batch).jaxpr))
    paths = {path for path, _, _ in eqns}
    for scope in ("gdn/conv", "gdn/chunks", "gdn/norm_gate", "attn/gated/scores", "moe/router", "moe/experts", "moe/shared"):
        assert any(f"/{scope}" in p for p in paths), scope
    assert not any("/kda/" in f"{p}/" for p in paths)
    kernels = {eqn.params["name"] for path, _, eqn in eqns if eqn.primitive.name == "pallas_call" and "/gdn/chunks" in path}
    assert kernels == {"kda_fwd", "kda_bwd"}, kernels
    # one launch for the row: nothing under the scope loops, over groups of heads or over chunks
    assert not [path for path, outer, eqn in eqns if "/gdn/chunks" in path and "pallas_call" not in outer
                and (eqn.primitive.name in ("scan", "while") or "scan" in outer or "while" in outer)]
    # the attention's scores are the XLA blocks at this length (no kernel under the scope)
    assert not any(eqn.primitive.name == "pallas_call" for path, _, eqn in eqns if "/attn/gated" in path)


# --------------------------------------------------- the engine's normal path
def test_trainer_fit_evaluate_and_checkpoint_round_trip(tmp_path, tiny_params):
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, np.random.default_rng(0).integers(30, 64, size=12))
    split = TokenizedSplit(ids, mask, (np.arange(12) % 2).astype(np.int32))
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    assert type(trainer.model) is Qwen3NextClassifier
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    assert state.route["slots"].shape == (cfg.experts_held,)
    state, losses = trainer.fit(state, split, batch_size=4, epochs=2)
    assert np.isfinite(losses).all() and int(state.step) == 6
    route = trainer.last_route
    assert route["overflow"] == 0 and 0 < int(route["slots"].sum()) <= 2 * int(mask.sum()) * cfg.n_layers * 4
    assert int(state.route["slots"].sum()) == 0  # read and started again
    metrics = trainer.evaluate(state.params, split, batch_size=4)
    assert 0.0 <= metrics["Accuracy"] <= 100.0 and len(metrics["probs"]) == 12 and metrics["routed_overflow"] == 0
    with Checkpointer(str(tmp_path / "ckpt")) as ckpt:
        ckpt.save(int(state.step), state, meta={"config": {"model": _model_dict(cfg)}})
        ckpt.wait()
        back = ckpt.restore(trainer.init_state(seed=0))
    for a, b in zip(jax.tree.leaves(back.params), jax.tree.leaves(state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(back.step) == 6


def test_config_round_trips_and_presets():
    exp = ExperimentConfig(model=TINY, data=DataConfig(max_len=TINY.max_len, window_flows=2))
    back = ExperimentConfig.from_dict(exp.to_dict())
    assert back.model == TINY and hash(back.model) == hash(TINY) and back.data.window_flows == 2
    assert ExperimentConfig.from_checkpoint_dict(exp.to_dict()).model == TINY
    cut = model_preset("qwen3-next-ep16", vocab_size=148)
    assert (cut.n_layers, cut.experts_held, cut.vocab_size, cut.remat, cut.max_len) == (4, 32, 18992, True, 16384)
    assert [cut.mixer(i) for i in range(4)] == ["linear", "linear", "linear", "full"] and all(cut.is_moe(i) for i in range(4))
    assert model_preset("qwen3-next-tiny", vocab_size=148).vocab_size == 148
    whole = Qwen3NextConfig()
    assert whole.n_layers == 48 and sum(whole.mixer(i) == "full" for i in range(48)) == 12
    assert (whole.n_experts, whole.experts_per_token, whole.head_dim, whole.n_heads, whole.n_kv_heads) == (512, 10, 256, 16, 2)
    for bad in (dict(linear_value_heads=3), dict(n_heads=3), dict(experts_held=32), dict(n_layers=0)):
        with pytest.raises(ValueError):
            Qwen3NextConfig.tiny(**bad)


def test_the_cli_resolves_the_preset_to_windows_of_its_length():
    import argparse

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.common import (
        resolve_config,
    )

    cfg = resolve_config(argparse.Namespace(preset="qwen3-next-ep16"), vocab_size=148)
    assert type(cfg.model) is Qwen3NextConfig and cfg.data.max_len == 16384 and cfg.data.window_flows == 112
    tiny = resolve_config(argparse.Namespace(preset="qwen3-next-tiny"), vocab_size=148)
    assert tiny.model.vocab_size == 148 and tiny.data.window_flows == 2
