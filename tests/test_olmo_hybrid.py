"""The fifth model class (``models/olmo_hybrid.py``): the delta rule's two
kernels at keys of 96, values of 192 and a write strength up to 2 against the
token recurrence, the Gated DeltaNet mixer it shares with the Qwen3-Next class
against that class as it was, the whole program against the plain float32
reference (``benchmark/reference/olmo_hybrid_fp32.py``: the token recurrence,
every key scored head by head, the norms after each sublayer), four faults in
what this architecture adds that the benchmark's own comparison catches, and
the engine's normal path on it. CPU, small sizes, seeded random weights."""

import dataclasses
from typing import Any

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid_fp32 as ref
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    OlmoHybridConfig,
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
    SwiGLU,
    causal_conv,
    conv_init,
    dense,
    dt_bias_init,
    rms,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.olmo_hybrid import (
    OlmoHybridClassifier,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.qwen3_next import (
    GatedDeltaNet,
    _a_log_init,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import kda
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
    causal_path,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.rope import (
    apply_rope,
    rope_tables,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
    loss_fn,
)
from tests.test_benchmark_families import OLMO_CELL, _family_context, rehearse
from tests.test_kimi_linear import _rel

TINY = OlmoHybridConfig.tiny(max_len=64)
CONFIG = "olmo-hybrid-7b-4l"


def _model_dict(cfg):
    return {**dataclasses.asdict(cfg), "layer_types": list(cfg.layer_types)}


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
@pytest.mark.parametrize(
    "L, decay", [(128, 0.5), (100, 20.0), (128, 80.0)], ids=["two-chunks", "a-padded-tail-fast", "decay-80-a-token"]
)
def test_the_kernels_hold_to_the_recurrence_at_96_192_a_write_strength_to_two_and_any_decay(L, decay):
    """``kda_chunked`` with a SCALAR gate (``kda_fwd``, and under
    ``jax.grad`` ``kda_bwd``, interpreted) against ``kda_recurrent`` under the
    head's decay broadcast over its channels, at the configuration's widths,
    keys of 96 and values of 192, with ``beta`` drawn up to 1.99 (``I - beta
    k k^T`` up to a reflection) and decays of up to 80 a token, as the cell's
    layers reach at the seed: forward and every gradient (the gate's is its
    channels' sum). The channel-wise blocks, handed the same decay broadcast,
    are exact while a head forgets less than ``e^{-80}`` in 16 tokens and not
    past it (their clip, ``ops/kda.py::MAX_BLOCK_DECAY``)."""
    rng = np.random.default_rng(L)
    B, H, dk, dv = 1, 2, 96, 192
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(rng.normal(size=(B, H, L, dk))) * dk**-0.5).astype(np.float32)
    k = unit(rng.normal(size=(B, H, L, dk))).astype(np.float32)
    v = rng.normal(size=(B, H, L, dv)).astype(np.float32)
    g = (-np.abs(rng.normal(size=(B, H, L))) * decay).astype(np.float32)
    beta = rng.uniform(0.01, 1.99, size=(B, H, L)).astype(np.float32)
    assert beta.max() > 1.9
    wide = lambda g: jnp.broadcast_to(g[..., None], (B, H, L, dk))  # noqa: E731
    want = kda.kda_recurrent(q, k, v, wide(g), beta)
    assert _rel(kda.kda_chunked(q, k, v, g, beta), want) < 1e-5
    channel_wise = _rel(kda.kda_chunked(q, k, v, wide(g), beta), want)
    assert channel_wise < 2e-6 if decay < 1 else channel_wise > 0.1
    cot = rng.normal(size=v.shape).astype(np.float32)
    grads = lambda fn: jax.grad(lambda *a: (fn(*a) * cot).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)  # noqa: E731
    recurrent = lambda q, k, v, g, beta: kda.kda_recurrent(q, k, v, wide(g), beta)  # noqa: E731
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), grads(kda.kda_chunked), grads(recurrent)):
        assert a.shape == b.shape and _rel(a, b) < 1e-4, (name, _rel(a, b))


class _ParentGatedDeltaNet(nn.Module):
    """``models/qwen3_next.py::GatedDeltaNet`` as it was before the mixer was
    shared (PR 34-37), verbatim: what the shared class must still give."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, L, _ = x.shape
        Hk, Hv, dk, dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim
        pd = jnp.dtype(cfg.param_dtype)
        qk, vz = Hk * dk, Hv * dv
        with jax.named_scope("proj"):
            proj = dense(cfg, 2 * qk + 2 * vz, "qkvz_proj")(x)
            ba = dense(cfg, 2 * Hv, "ba_proj")(x).astype(jnp.float32)
        with jax.named_scope("conv"):
            kernel = self.param("conv", conv_init, (cfg.conv_kernel, 2 * qk + vz), pd)
            qkv = jax.nn.silu(causal_conv(proj[..., : 2 * qk + vz], kernel))

        def heads(t, H):
            return t.reshape(B, L, H, -1).transpose(0, 2, 1, 3)

        def l2(t):
            t = t.astype(jnp.float32)
            return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

        a_log = self.param("A_log", _a_log_init, (Hv,), pd)
        dt_bias = self.param("dt_bias", dt_bias_init, (Hv,), pd)
        with jax.named_scope("prep"):
            q = jnp.repeat(l2(heads(qkv[..., :qk], Hk)) * dk**-0.5, Hv // Hk, axis=1)
            k = jnp.repeat(l2(heads(qkv[..., qk : 2 * qk], Hk)), Hv // Hk, axis=1)
            v = heads(qkv[..., 2 * qk :], Hv)
            beta = jax.nn.sigmoid(ba[..., :Hv]).transpose(0, 2, 1)
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(ba[..., Hv:] + dt_bias.astype(jnp.float32))
            g = jnp.broadcast_to(g.transpose(0, 2, 1)[..., None], (B, Hv, L, dk))
        o = kda.kda_chunked(q, k, v, g, beta, dtype=jnp.dtype(cfg.compute_dtype))
        with jax.named_scope("norm_gate"):
            scale = self.param("o_norm", nn.initializers.ones, (dv,), pd)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_norm_eps) * scale
            z = proj[..., 2 * qk + vz :].astype(jnp.float32)
            o = o.transpose(0, 2, 1, 3).reshape(B, L, vz) * jax.nn.silu(z)
        with jax.named_scope("proj"):
            return dense(cfg, cfg.dim, "o_proj")(o.astype(x.dtype))


def test_the_shared_mixer_gives_the_qwen3_next_preset_what_it_gave_bit_for_bit():
    """At the Qwen3-Next preset, in the cell's bfloat16, the shared class
    makes the same leaves from the same key and gives the same output and
    gradients, bit for bit."""
    cfg = Qwen3NextConfig.tiny(max_len=64, compute_dtype="bfloat16")
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 64, cfg.dim)), jnp.bfloat16)
    new, old = GatedDeltaNet(cfg), _ParentGatedDeltaNet(cfg)
    p_new, p_old = new.init(jax.random.key(7), x)["params"], old.init(jax.random.key(7), x)["params"]
    assert jax.tree.structure(p_new) == jax.tree.structure(p_old)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(p_new), jax.tree.leaves(p_old)))
    assert np.array_equal(new.apply({"params": p_new}, x), old.apply({"params": p_old}, x))
    cot = jnp.asarray(np.random.default_rng(4).normal(size=x.shape), x.dtype)
    grad = lambda m: jax.grad(lambda p, x: (m.apply({"params": p}, x) * cot).astype(jnp.float32).sum(), (0, 1))  # noqa: E731
    for a, b in zip(jax.tree.leaves(grad(new)(p_new, x)), jax.tree.leaves(grad(old)(p_old, x))):
        assert np.array_equal(a, b)


def test_the_olmo_mixer_has_a_projection_and_a_convolution_each_and_doubles_beta(tiny_params):
    """FLA's layout (q, k, v, the gate, b and a projected apart; a convolution
    over each of q, k, v), as the reference reads it, and ``beta = 2
    sigmoid(b)``, which the reference's mixer gives and the Qwen3-Next
    class's ``sigmoid(b)`` does not."""
    lp = jax.tree.map(np.asarray, tiny_params["encoder"]["layer_0"]["gdn"])
    assert set(lp) == {
        "q_proj", "k_proj", "v_proj", "g_proj", "b_proj", "a_proj", "o_proj", "q_conv", "k_conv", "v_conv",
        "A_log", "dt_bias", "o_norm",
    }
    assert lp["k_proj"]["kernel"].shape == (TINY.dim, 4 * 8) and lp["v_conv"].shape == (4, 4 * 16)
    x = np.random.default_rng(2).normal(size=(2, 64, TINY.dim)).astype(np.float32)
    got = GatedDeltaNet(TINY, fused=False, beta_scale=2.0).apply({"params": lp}, x)
    with jax.default_matmul_precision("highest"):
        plain = jnp.stack([ref._gdn(x[b], lp, _model_dict(TINY), ref._same) for b in range(2)])
    assert _rel(got, plain) < 1e-5
    assert _rel(GatedDeltaNet(TINY, fused=False).apply({"params": lp}, x), plain) > 1e-2  # Qwen3-Next's sigmoid(b)


def test_the_cells_attention_takes_the_causal_kernels():
    """30 heads of 128 at 4,096 tokens tile and fit: the full layer runs
    ``flash_fwd`` / ``flash_bwd`` under ``causal_flash`` on the chip."""
    cut = OlmoHybridConfig.pp8_cut()
    assert causal_path(cut.max_len, cut.head_dim, cut.head_dim, 2)[0] == "resident"


# ------------------------------------------- the program and the reference
def test_program_against_the_reference_fp32(tiny_params):
    """Hidden states, logits, loss and gradients at the tiny preset in
    float32: the chunked, blocked program and the token-by-token, dense
    reference agree to rounding; a gradient reaches every leaf."""
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
    assert routes == [] and jax.jit(family.routing(TINY))(tiny_params, ids, mask) == []
    assert abs(float(loss) - want_loss) < 1e-5
    rel = jax.tree.map(_rel, grads, want_grads)
    norms = jax.tree.map(lambda b: float(jnp.linalg.norm(b.ravel())), want_grads)
    top = max(jax.tree.leaves(norms))
    worst = max(r for r, n in zip(jax.tree.leaves(rel), jax.tree.leaves(norms)) if n > 1e-3 * top)
    assert worst < 1e-4, worst
    assert min(jax.tree.leaves(norms)) > 0.0  # every leaf, the query and key norms' weights too


def test_program_in_bf16_is_within_the_familys_limits(tiny_params):
    cfg = TINY.replace(compute_dtype="bfloat16", remat=True)
    ids, mask = _rows(cfg, [64, 60, 51, 40], seed=2)
    hidden, logits = jax.jit(family.program(cfg))(tiny_params, ids, mask)
    want_h, want_z = ref.forward(tiny_params, ids, mask, _model_dict(cfg))
    w = mask[..., None].astype(np.float32)
    err = max(_rel(np.asarray(hidden[i], np.float32) * w[i], want_h[i] * w[i]) for i in range(4))
    tol = family.TOLERANCES
    assert 1e-4 < err < tol["hidden_rel"], err
    assert float(jnp.abs(logits - want_z).max()) / family.logit_scale(tiny_params, np.asarray(want_z)) < tol["logit_rel"]


# ------------------------------------- faults that the comparison must catch
class _PreNormBlock(nn.Module):
    """The OLMo block's own leaves in the other decoders' order: each norm
    BEFORE its sublayer (``x + Mixer(N(x))``)."""

    cfg: Any
    layer: int

    @nn.compact
    def __call__(self, x, attention_mask):
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models import (
            olmo_hybrid,
        )

        cfg = self.cfg
        n = rms(cfg, "post_mixer_norm")(x)
        if cfg.mixer(self.layer) == "full":
            x = x + olmo_hybrid.FullAttention(cfg, name="attn")(n, attention_mask)
        else:
            x = x + GatedDeltaNet(cfg, fused=False, beta_scale=2.0, name="gdn")(n)
        return x + SwiGLU(cfg, cfg.hidden_dim, name="ffn")(rms(cfg, "post_ffn_norm")(x))


class _PerHeadNorm(nn.Module):
    """An RMSNorm over each head of ``d`` with the whole width's weight (the
    Qwen3-Next class's statistics, this class's leaf)."""

    d: int
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        h = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, self.d)
        h = h * jax.lax.rsqrt((h * h).mean(-1, keepdims=True) + self.eps)
        return (h.reshape(x.shape) * w).astype(x.dtype)


@pytest.mark.parametrize("fault", ["none", "beta-not-doubled", "pre-norm", "rotary", "per-head-qk-norm"])
def test_a_fault_in_what_olmo_hybrid_adds_is_not_correct(monkeypatch, fault):
    """Four faults in the mechanisms this family brought, each planted in the
    program's own forward at the tiny preset: a write strength of ``sigmoid``
    where it is ``2 sigmoid``, norms before the sublayers in place of after,
    positions rotated into the full layer's q and k, and the query/key norm's
    statistics per head in place of over the whole projection. Each leaves
    the parameter tree, shapes and finiteness alone and fails
    ``harness.check_model`` against the reference, which the program as it is
    passes on the same weights."""
    model_mod = harness.pkg("models.olmo_hybrid")
    # The weights first, from the program as it is; heads of the attention made unequal in size (a
    # whole-width norm keeps them so, a per-head norm does not) and the write strength spread over (0, 2).
    ctx, params, split = _family_context(monkeypatch, family, CONFIG, f"standin_o{fault[:4]}")

    def widen(path, x):
        names = [getattr(k, "key", "") for k in path]
        if "attn" in names and names[-2] in ("q_proj", "k_proj"):
            return x * jnp.repeat(jnp.array([0.5, 2.0, 8.0, 30.0]), x.shape[1] // 4)[None, :]
        if "b_proj" in names:
            return x * 30.0
        return x

    params = jax.tree_util.tree_map_with_path(widen, params)
    if fault == "beta-not-doubled":
        real = model_mod.GatedDeltaNet
        monkeypatch.setattr(model_mod, "GatedDeltaNet", lambda cfg, **kw: real(cfg, **{**kw, "beta_scale": 1.0}))
    elif fault == "pre-norm":
        monkeypatch.setattr(model_mod, "OlmoHybridBlock", _PreNormBlock)
    elif fault == "rotary":
        real = model_mod.causal_attention

        def rotated(q, k, v, mask, *rest):
            cos, sin = rope_tables(q.shape[2], q.shape[3], 10000.0)
            t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
            return real(t(apply_rope(t(q), cos, sin)), t(apply_rope(t(k), cos, sin)), v, mask, *rest)

        monkeypatch.setattr(model_mod, "causal_attention", rotated)
    elif fault == "per-head-qk-norm":
        real = model_mod.rms
        monkeypatch.setattr(
            model_mod, "rms",
            lambda cfg, name, *a: _PerHeadNorm(cfg.head_dim, cfg.rms_norm_eps, name=name) if name in ("q_norm", "k_norm") else real(cfg, name, *a),
        )
    family.program.cache_clear()
    try:
        harness.check_model(ctx, params, split, what=fault, key="o", bind=True, n=3)
        if fault == "none":
            assert not ctx.problems and ctx.compared["o.hidden_rel"][0] < 1e-4, (ctx.problems, ctx.compared)
        else:
            assert any("hidden states differ" in p for p in ctx.problems), (ctx.problems, ctx.compared)
    finally:
        family.program.cache_clear()


# --------------------------------------------------- the engine's normal path
def test_the_cells_rehearsal_ends_in_a_result_line():
    rehearse(OLMO_CELL)


def test_trainer_fit_and_evaluate_route_nothing(tiny_params):
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, np.random.default_rng(0).integers(30, 64, size=4))
    split = TokenizedSplit(ids, mask, (np.arange(4) % 2).astype(np.int32))
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    assert type(trainer.model) is OlmoHybridClassifier
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    assert state.route is None
    state, losses = trainer.fit(state, split, batch_size=2, epochs=1)
    assert np.isfinite(losses).all() and int(state.step) == 2 and trainer.last_route is None
    metrics = trainer.evaluate(state.params, split, batch_size=2)
    assert 0.0 <= metrics["Accuracy"] <= 100.0 and len(metrics["probs"]) == 4 and "routed_overflow" not in metrics


def test_config_round_trips_and_presets():
    exp = ExperimentConfig(model=TINY, data=DataConfig(max_len=TINY.max_len, window_flows=2))
    back = ExperimentConfig.from_dict(exp.to_dict())
    assert back.model == TINY and hash(back.model) == hash(TINY)
    cut = model_preset("olmo-hybrid-pp8", vocab_size=148)
    assert (cut.n_layers, cut.vocab_size, cut.remat, cut.max_len) == (4, 12544, True, 4096)
    assert [cut.mixer(i) for i in range(4)] == ["linear", "linear", "linear", "full"]
    assert model_preset("olmo-hybrid-tiny", vocab_size=148).vocab_size == 148
    whole = OlmoHybridConfig()
    assert whole.n_layers == 32 and sum(whole.mixer(i) == "full" for i in range(32)) == 8
    assert (whole.linear_key_dim, whole.linear_value_dim, whole.head_dim * whole.n_heads) == (96, 192, whole.dim)
    for bad in (dict(layer_types=()), dict(layer_types=("linear", "sliding")), dict(linear_value_heads=3), dict(n_heads=5)):
        with pytest.raises(ValueError):
            OlmoHybridConfig.tiny(**bad)
