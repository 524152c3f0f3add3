"""The third model class (``models/laguna.py``): the window, the grouped heads
and the rotary tables against their plain forms, the whole program and each
kind of layer against the plain float32 reference
(``benchmark/reference/laguna_fp32.py``: dense ``[L, L]`` masks head by head,
every held expert on every token), and the engine's normal path on it. CPU,
small sizes, seeded random weights."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import laguna as family
from benchmark.reference import laguna_fp32 as ref
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    LagunaConfig,
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
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.laguna import (
    LagunaBlock,
    LagunaClassifier,
    rotary_tables,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import (
    causal_attention as blocked,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.causal_attention import (
    BLOCK,
    GROUP,
    causal_attention,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
    NEG_INF,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.rope import (
    apply_rope,
    inv_frequencies,
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

TINY = LagunaConfig.tiny(max_len=64)


def _model_dict(cfg):
    d = dataclasses.asdict(cfg)
    return {k: list(v) if isinstance(v, tuple) else v for k, v in d.items()}


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
def _plain_attention(q, k, v, mask, window=None):
    """Every key scored under a dense ``[L, L]`` mask, the keys of a group
    repeated explicitly."""
    L, G = q.shape[2], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    ok = (j <= i) if window is None else (j <= i) & (i - j < window)
    ok = ok[None, None] & (np.asarray(mask)[:, None, None, :] > 0)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(ok, s, -1e9), -1), v)


def _qkv(L, H, Hkv, seed=3, B=2, dqk=24, dv=16):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    mask = (np.arange(L)[None, :] < np.array([L, L - 21])[:, None]).astype(np.int32)
    return normal(B, H, L, dqk), normal(B, Hkv, L, dqk), normal(B, Hkv, L, dv), mask


@pytest.mark.parametrize(
    "L, window",
    [(BLOCK, 100), (2 * BLOCK, 100), (300, 100), (700, 512), (96, 24), (2 * BLOCK, BLOCK), (3 * BLOCK, BLOCK + 1), (200, 512)],
    ids=["one-block", "two-blocks", "padded-tail", "window-512", "short-row", "window-a-block", "window-a-block-and-one", "window-over-the-row"],
)
def test_the_windowed_blocks_are_the_dense_window_mask(L, window):
    """Lengths that are and are not multiples of ``BLOCK``, windows under, at
    and over a block, a row shorter than its window, padding at the end of a
    row: outputs over the real tokens and all three gradients."""
    q, k, v, mask = _qkv(L, 4, 4)
    w = mask[:, None, :, None]
    got = causal_attention(q, k, v, mask, window)
    assert got.shape == (2, 4, L, 16)
    assert float(jnp.abs((got - _plain_attention(q, k, v, mask, window)) * w).max()) < 1e-5
    f = lambda fn: (lambda *a: ((fn(*a) * w) ** 2).sum())  # noqa: E731
    for a, b in zip(
        jax.grad(f(lambda *a: causal_attention(*a, mask, window)), (0, 1, 2))(q, k, v),
        jax.grad(f(lambda *a: _plain_attention(*a, mask, window)), (0, 1, 2))(q, k, v),
    ):
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("H, Hkv", [(6, 2), (8, 2), (4, 1), (4, 4)], ids=["3-a-key", "4-a-key", "one-key-head", "a-key-a-head"])
@pytest.mark.parametrize("window", [None, 100], ids=["full", "window"])
def test_grouped_heads_are_explicitly_repeated_keys(H, Hkv, window):
    """Query head ``h`` reads key head ``h // (H / Hkv)``: the blocked form,
    which folds a group's heads into a block's rows and repeats no key,
    against keys and values repeated head for head, over two groups of blocks
    (the second's last block padded)."""
    L = BLOCK * GROUP + 76
    q, k, v, mask = _qkv(L, H, Hkv, seed=5)
    w = mask[:, None, :, None]
    want = _plain_attention(q, k, v, mask, window)
    assert float(jnp.abs((causal_attention(q, k, v, mask, window) - want) * w).max()) < 1e-5
    f = lambda fn: (lambda *a: ((fn(*a) * w) ** 2).sum())  # noqa: E731
    got = jax.grad(f(lambda *a: causal_attention(*a, mask, window)), (1, 2))(q, k, v)
    for a, b in zip(got, jax.grad(f(lambda *a: _plain_attention(*a, mask, window)), (1, 2))(q, k, v)):
        assert a.shape == b.shape == (2, Hkv, L, a.shape[-1]) and _rel(a, b) < 1e-5


@pytest.mark.parametrize("mask_kind", ["right-padded", "holes"])
@pytest.mark.parametrize(
    "L, H, Hkv, dqk, dv",
    [(384, 4, 4, 24, 16), (384, 6, 1, 16, 16), (384, 8, 1, 16, 16), (1024, 4, 2, 16, 8), (BLOCK * GROUP + 76, 6, 1, 16, 16)],
    ids=["one-a-key-two-widths", "6-a-key", "8-a-key", "two-tiles-of-512", "no-tile-takes-the-blocks"],
)
def test_the_causal_kernels_are_the_plain_softmax(L, H, Hkv, dqk, dv, mask_kind):
    """A length that tiles takes the Pallas kernels (interpreted here): three
    tiles of 128 or two of 512, so that a query tile has key tiles it skips, a
    masked diagonal one and full ones before it; one group with ``dqk != dv``,
    six and eight query heads over one shared key head, a right-padded and a
    non-contiguous key mask; result over the real tokens and all three
    gradients. A length that does not tile takes the XLA blocks and agrees."""
    rng = np.random.default_rng(7)
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    q, k, v = normal(2, H, L, dqk), normal(2, Hkv, L, dqk), normal(2, Hkv, L, dv)
    if mask_kind == "holes":
        mask = (rng.random((2, L)) > 0.3).astype(np.int32)
    else:
        mask = (np.arange(L)[None, :] < np.array([L, L - 21])[:, None]).astype(np.int32)
    w = mask[:, None, :, None]
    took_the_kernels = "pallas_call" in str(jax.make_jaxpr(lambda *a: causal_attention(*a, mask))(q, k, v))
    assert took_the_kernels == (L % 128 == 0)
    got = causal_attention(q, k, v, mask)
    assert got.shape == (2, H, L, dv)
    assert float(jnp.abs((got - _plain_attention(q, k, v, mask)) * w).max()) < 1e-5
    f = lambda fn: (lambda *a: ((fn(*a) * w) ** 2).sum())  # noqa: E731
    for a, b in zip(
        jax.grad(f(lambda *a: causal_attention(*a, mask)), (0, 1, 2))(q, k, v),
        jax.grad(f(lambda *a: _plain_attention(*a, mask)), (0, 1, 2))(q, k, v),
    ):
        assert a.shape == b.shape and _rel(a, b) < 1e-5


def _causal_attention_of_pr_31(q, k, v, key_mask):
    """``ops/causal_attention.py::causal_attention`` as the latent attention
    called it before it took grouped heads and a window (PR 31's tree)."""
    B, H, L, _ = q.shape
    scale = q.shape[-1] ** -0.5
    pad_bias = (1.0 - key_mask.astype(jnp.float32)) * NEG_INF

    @jax.checkpoint
    def one_block(q_blk, start, k_seen, v_seen, bias):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_seen, preferred_element_type=jnp.float32) * scale
        q_pos = start + jnp.arange(q_blk.shape[2])[:, None]
        later = jnp.arange(k_seen.shape[2])[None, :] > q_pos
        scores = scores + bias[:, None, None, :] + jnp.where(later, NEG_INF, 0.0)
        weights = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", weights, v_seen)

    out = []
    for first in range(0, L, BLOCK * GROUP):
        end = min(first + BLOCK * GROUP, L)
        block = min(BLOCK, end - first)
        n = -(-(end - first) // block)
        q_grp = jnp.pad(q[:, :, first:end], ((0, 0), (0, 0), (0, n * block - (end - first)), (0, 0)))
        q_grp = jnp.moveaxis(q_grp.reshape(B, H, n, block, -1), 2, 0)
        k_seen, v_seen, bias = k[:, :, :end], v[:, :, :end], pad_bias[:, :end]
        o = jax.lax.map(
            lambda x: one_block(x[0], x[1], k_seen, v_seen, bias),  # noqa: B023
            (q_grp, first + block * jnp.arange(n)),
        )
        out.append(jnp.moveaxis(o, 0, 2).reshape(B, H, n * block, -1)[:, :, : end - first])
    return jnp.concatenate(out, axis=2)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_the_latent_attentions_call_lowers_to_the_program_it_lowered_to(grad):
    """One group, 192-wide keys and 128-wide values, no window, a length with
    a padded tail: the lowered text of the edited function is, character for
    character, that of the function the Kimi cell was measured with until
    PR 33. Since then the cell's 4,096 tokens tile and take the Pallas
    kernels; this length does not, so this is the guard of the fallback: the
    XLA blocks a length that does not tile takes are those that were
    measured."""
    B, H, L = 2, 4, BLOCK * GROUP + 300
    bf16 = jnp.bfloat16
    args = (
        jax.ShapeDtypeStruct((B, H, L, 192), bf16), jax.ShapeDtypeStruct((B, H, L, 192), bf16),
        jax.ShapeDtypeStruct((B, H, L, 128), bf16), jax.ShapeDtypeStruct((B, L), jnp.int32),
    )

    def text(fn):
        def attention(q, k, v, mask):
            if grad:
                return jax.grad(lambda *a: fn(*a, mask).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
            return fn(q, k, v, mask)

        return jax.jit(attention).lower(*args).as_text()

    was, now = text(_causal_attention_of_pr_31), text(blocked.causal_attention)
    assert was == now


def _closed_form(length, rot, theta, factor=1.0, original=0, beta_fast=32.0, beta_slow=1.0):
    """Angles ``[length, rot / 2]`` pair by pair in Python floats."""
    out = np.zeros((length, rot // 2))
    for i in range(rot // 2):
        f = theta ** (-2.0 * i / rot)
        if factor != 1.0:
            at = lambda turns: rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
            low, high = max(math.floor(at(beta_fast)), 0), min(math.ceil(at(beta_slow)), rot - 1)
            blend = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
            f = f * (1.0 - blend) + f / factor * blend
        out[:, i] = np.arange(length) * f
    return out


@pytest.mark.parametrize("kind", ["default", "partial", "yarn"])
def test_rotary_tables_are_the_closed_form(kind):
    """The default kind over a whole head, the same over half of it, and the
    published full-attention setting (theta 500,000, YaRN of 64 over 4,096
    positions, ``beta_fast`` 64, ``beta_slow`` 1, attention factor 1.41589
    over the first 64 of 128 dimensions): tables, the reference's own tables,
    and the rotation of a vector pair by pair."""
    d, L = 128, 300
    rot, theta, yarn, scale = {
        "default": (128, 10000.0, {}, 1.0),
        "partial": (64, 10000.0, {}, 1.0),
        "yarn": (64, 500000.0, dict(factor=64.0, original_len=4096, beta_fast=64.0, beta_slow=1.0), 1.4158883083359672),
    }[kind]
    angle = _closed_form(L, rot, theta, yarn.get("factor", 1.0), yarn.get("original_len", 0), yarn.get("beta_fast", 32.0), yarn.get("beta_slow", 1.0))
    cos, sin = rope_tables(L, rot, theta, *yarn.values(), attention_factor=scale) if yarn else rope_tables(L, rot, theta)
    assert cos.shape == sin.shape == (L, rot // 2) and cos.dtype == np.float32
    assert np.abs(cos - np.cos(angle) * scale).max() < 1e-6 and np.abs(sin - np.sin(angle) * scale).max() < 1e-6
    r_cos, r_sin = ref.rotary_tables(L, rot, {"theta": theta, **yarn, "attention_factor": scale})
    assert np.abs(np.asarray(r_cos) - cos).max() < 1e-6 and np.abs(np.asarray(r_sin) - sin).max() < 1e-6
    if kind == "yarn":
        inv = inv_frequencies(rot, theta, **yarn)
        plain = theta ** (-np.arange(0, rot, 2) / rot)
        # the fastest pairs turn as trained, the slowest 64 times slower, and the blend lies between
        assert inv[0] == plain[0] and np.isclose(inv[-1], plain[-1] / 64.0) and np.all(inv <= plain) and np.all(inv >= plain / 64.0)
        assert np.isclose(scale, 0.1 * math.log(64.0) + 1.0)
    x = np.random.default_rng(0).normal(size=(2, L, 3, d)).astype(np.float32)
    got = np.asarray(apply_rope(jnp.asarray(x), cos, sin))
    want = x.copy() * 1.0
    for i in range(rot // 2):
        a, b = x[..., i], x[..., i + rot // 2]
        c, s = (np.cos(angle[:, i]) * scale)[None, :, None], (np.sin(angle[:, i]) * scale)[None, :, None]
        want[..., i], want[..., i + rot // 2] = a * c - b * s, b * c + a * s
    assert np.abs(got - want).max() < 1e-5
    assert np.array_equal(got[..., rot:], x[..., rot:])  # the dimensions past the rotated share pass through


def test_the_configurations_tables_are_the_published_settings():
    cut = LagunaConfig.ep8_cut()
    cos, _ = rotary_tables(cut, "full", 16)
    assert cos.shape == (16, 32) and np.isclose(cos[0, 0], 1.4158883083359672)
    cos, _ = rotary_tables(cut, "sliding", 16)
    assert cos.shape == (16, 64) and cos[0, 0] == 1.0


# ------------------------------------------- the program and the reference
@pytest.mark.parametrize("layer", [0, 1, 2], ids=["full-dense", "sliding-experts", "full-experts"])
def test_each_kind_of_layer_against_the_reference(tiny_params, layer):
    """One block of the program on a random residual stream against the
    reference's layer, float32: full attention with 4 heads and the dense
    FFN, a window of 24 with 6 heads and experts, full attention and
    experts; and the gradient with respect to the layer's input."""
    rng = np.random.default_rng(layer)
    ids, mask = _rows(TINY, [64, 41])
    x = rng.normal(size=(2, TINY.max_len, TINY.dim)).astype(np.float32)
    lp = tiny_params["encoder"][f"layer_{layer}"]
    block = LagunaBlock(TINY, layer)
    got = block.apply({"params": lp}, x, mask)
    model = _model_dict(TINY)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref._layer(model, layer, lambda a: a)(x[b], mask[b], lp, None)[0] for b in range(2)])
        w = mask[..., None]
        assert _rel(got * w, want * w) < 1e-5
        g = jax.grad(lambda x: ((block.apply({"params": lp}, x, mask) * w) ** 2).sum())(x)
        g_want = jax.grad(
            lambda x: sum(((ref._layer(model, layer, lambda a: a)(x[b], mask[b], lp, None)[0] * w[b]) ** 2).sum() for b in range(2))
        )(x)
    assert _rel(g * w, g_want * w) < 1e-4


def test_program_against_the_reference_fp32(tiny_params):
    """Hidden states, logits, loss and gradients at the tiny preset in
    float32: the blocked program and the dense-mask reference agree to
    rounding; a forced choice of experts is computed, and handed back."""
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
    forced = [np.roll(np.asarray(idx), 1, axis=-2) for idx, _ in routes]  # every token gets its neighbour's experts
    h_forced, _ = ref.forward(tiny_params, ids, mask, model, forced=forced)
    assert _rel(h_forced * w, want_h * w) > 1e-3
    own = [np.asarray(idx) for idx, _ in routes]
    h_own, z_own = ref.forward(tiny_params, ids, mask, model, forced=own)
    assert _rel(h_own * w, want_h * w) < 1e-6 and float(jnp.abs(z_own - want_z).max()) < 1e-6
    got = jax.jit(family.routing(TINY))(tiny_params, ids, mask)
    assert len(got) == len(routes) == 2
    for g, (want_idx, _) in zip(got, routes):
        assert (np.sort(np.asarray(g)[mask > 0], -1) == np.sort(np.asarray(want_idx)[mask > 0], -1)).all()


def test_a_row_longer_than_its_window_forgets_what_lies_outside_it(tiny_params):
    """The window is in the model, not only in the op: with every layer
    sliding, a token more than ``layers * (window - 1)`` positions after a
    changed token keeps its hidden state; with a full layer among them it
    does not."""
    cfg = TINY.replace(layer_types=("sliding",) * 3, heads_per_layer=(6, 6, 6), sliding_window=8)
    params = init_params(build_classifier(cfg), cfg, jax.random.key(2))
    ids, mask = _rows(cfg, [64])
    other = ids.copy()
    other[0, 3] = (ids[0, 3] % (cfg.vocab_size - 1)) + 1
    hidden = lambda c, p, i: np.asarray(jax.jit(family.program(c))(p, i, mask)[0])  # noqa: E731
    a, b = hidden(cfg, params, ids), hidden(cfg, params, other)
    reach = 3 + 3 * (cfg.sliding_window - 1)
    assert np.abs(a[0, 3] - b[0, 3]).max() > 1e-3 and np.abs(a[0, reach] - b[0, reach]).max() > 0
    assert np.array_equal(a[0, reach + 1 :], b[0, reach + 1 :])
    full = hidden(TINY, init_params(build_classifier(TINY), TINY, jax.random.key(2)), ids)
    assert np.abs(full[0, -1] - hidden(TINY, init_params(build_classifier(TINY), TINY, jax.random.key(2)), other)[0, -1]).max() > 0


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


@pytest.mark.parametrize("program", ["train_step", "eval_step"])
@pytest.mark.parametrize("which", ["laguna", "kimi_linear"])
def test_a_causal_layer_launches_one_forward_and_one_backward_kernel(which, program):
    """Did the mechanism engage, and only once: at a length that tiles (128)
    ``engine.train_step`` holds, for every full-attention layer of the Laguna
    class (a sliding layer none) and for the Kimi class's latent attention,
    ONE ``flash_fwd`` and ONE ``flash_bwd`` under the scope ``causal_flash``
    (under ``attn/full/scores`` and ``mla``, where the benchmark's shares
    look). The backward kernel
    is inside the layer's recomputation, the forward one is not: the result
    and the rows' log-sum-exp are kept by name across it
    (``ATTENTION_RESULT``), so no layer runs its forward kernel twice.
    ``engine.eval_step`` holds the forward kernel alone."""
    import collections

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        KimiLinearConfig,
    )

    if which == "laguna":
        cfg = LagunaConfig.tiny(max_len=128).replace(remat=True)
        under = {
            i: f"layer_{i}/attn/full/scores/causal_flash/" for i, kind in enumerate(cfg.layer_types) if kind == "full"
        }
        assert set(cfg.layer_types) == {"full", "sliding"}
    else:
        cfg = KimiLinearConfig.tiny(max_len=128).replace(remat=True)
        under = {i: f"layer_{i}/mla/mla/causal_flash/" for i in range(cfg.n_layers) if cfg.mixer(i) == "mla"}
    ids, mask = _rows(cfg, [128, 100, 80, 70])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([0, 1, 0, 1], np.int32)}
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=init_params(build_classifier(cfg), cfg, jax.random.key(1)))
    if program == "train_step":
        jaxpr = jax.make_jaxpr(trainer.train_step.__wrapped__)(state, batch)
    else:
        jaxpr = jax.make_jaxpr(trainer.eval_step.__wrapped__)(state.params, batch, np.ones(len(ids), bool))
    launches = [
        (eqn.params["name"], path, "remat2" in outer)
        for path, outer, eqn in _eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call" and eqn.params["name"].startswith("flash_")
    ]
    n = len(under)
    grad = program == "train_step"
    kernels = collections.Counter(name for name, _, _ in launches)
    assert n and kernels == ({"flash_fwd": n, "flash_bwd": n} if grad else {"flash_fwd": n}), kernels
    for scope in under.values():
        assert sum(scope in path for _, path, _ in launches) == (2 if grad else 1), (scope, launches)
    if grad:
        assert all(recomputed == (name == "flash_bwd") for name, _, recomputed in launches), launches


def test_remat_changes_no_number_and_keeps_the_routers_choice(tiny_params):
    ids, mask = _rows(TINY, [64, 40])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([1, 0], np.int32)}
    grad_of = lambda cfg: jax.grad(lambda p: loss_fn(build_classifier(cfg), p, batch, jax.random.key(0)))  # noqa: E731
    cfg = TINY.replace(remat=True)
    for a, b in zip(jax.tree.leaves(jax.jit(grad_of(TINY))(tiny_params)), jax.tree.leaves(jax.jit(grad_of(cfg))(tiny_params))):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * (1.0 + float(jnp.abs(b).max()))
    jaxpr = jax.make_jaxpr(grad_of(cfg))(tiny_params)
    top_ks = ["remat2" in outer for _, outer, eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "top_k"]
    assert top_ks == [False] * sum(cfg.is_moe(i) for i in range(cfg.n_layers))


def test_the_step_cuts_the_window_in_the_program_and_names_its_scopes(tiny_params):
    """Did the mechanism engage: in ``engine.train_step`` every score product
    under ``attn/window/scores`` meets the window's keys (the block's own and
    the blocks before it that the window reaches), not the row's, whatever the
    row's length; those under ``attn/full/scores`` meet the keys up to their
    group's end; grouped heads are folded into a block's rows (no key is
    repeated); and the scopes the benchmark's readers look for are there."""
    cfg = TINY.replace(max_len=2 * BLOCK + 40, sliding_window=24, remat=True)
    ids, mask = _rows(cfg, [cfg.max_len, 300])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([0, 1], np.int32)}
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    jaxpr = jax.make_jaxpr(trainer.train_step.__wrapped__)(state, batch)
    eqns = list(_eqns(jaxpr.jaxpr))
    paths = {path for path, _, _ in eqns}
    for scope in ("attn/window/qkv", "attn/window/rope", "attn/window/scores", "attn/window/out", "attn/full/qkv",
                  "attn/full/rope", "attn/full/scores", "attn/full/out", "moe/router", "moe/experts", "moe/shared", "ffn_dense"):
        assert any(f"/{scope}" in p for p in paths), scope
    d, Hkv = cfg.head_dim, cfg.n_kv_heads

    def score_products(scope):
        """(rows, keys) of every forward q k^T under the scope: two operands ``[B, Hkv, rows|keys, d]``."""
        out = set()
        for path, _, eqn in eqns:
            if f"/{scope}" in path and eqn.primitive.name == "dot_general":
                a, b = (v.aval.shape for v in eqn.invars)
                if len(a) == len(b) == 4 and a[-1] == b[-1] == d and a[1] == b[1] == Hkv and eqn.params["dimension_numbers"][0] == ((3,), (3,)):
                    out.add((a[2], b[2]))
        return out

    window = score_products("attn/window/scores")
    assert window and {keys for _, keys in window} == {BLOCK + BLOCK}  # 23 keys before a block, in whole blocks
    assert {rows for rows, _ in window} == {(6 // Hkv) * BLOCK}  # a group's heads folded into the rows
    full = score_products("attn/full/scores")
    assert {keys for _, keys in full} == {cfg.max_len} and {rows for rows, _ in full} == {(4 // Hkv) * BLOCK}
    assert not any(eqn.primitive.name == "dynamic_slice" for path, _, eqn in eqns if "/attn/full/scores" in path)


# --------------------------------------------------- the engine's normal path
def test_trainer_fit_evaluate_and_checkpoint_round_trip(tmp_path, tiny_params):
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, np.random.default_rng(0).integers(30, 64, size=12))
    split = TokenizedSplit(ids, mask, (np.arange(12) % 2).astype(np.int32))
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    assert type(trainer.model) is LagunaClassifier
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    assert state.route["slots"].shape == (cfg.experts_held,)
    state, losses = trainer.fit(state, split, batch_size=4, epochs=2)
    assert np.isfinite(losses).all() and int(state.step) == 6
    route = trainer.last_route
    moe_layers = sum(cfg.is_moe(i) for i in range(cfg.n_layers))
    assert route["overflow"] == 0 and 0 < int(route["slots"].sum()) <= 2 * int(mask.sum()) * moe_layers * 4
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


def test_the_routing_counters_are_published_under_the_held_experts_labels(tiny_params):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.metrics import (
        default_registry,
    )

    cfg = TINY.replace(expert_offset=8, remat=True)
    ids, mask = _rows(cfg, [64, 50, 40, 33])
    split = TokenizedSplit(ids, mask, np.array([0, 1, 0, 1], np.int32))
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    trainer.fit(trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params)), split, batch_size=4, epochs=1)
    text = default_registry().render()
    for expert in range(8, 8 + cfg.experts_held):
        assert f'fedtpu_moe_routed_slots_total{{expert="{expert}"}}' in text
    assert "fedtpu_moe_overflow_slots_total" in text


def test_config_round_trips_and_presets():
    exp = ExperimentConfig(model=TINY, data=DataConfig(max_len=TINY.max_len, window_flows=2))
    back = ExperimentConfig.from_dict(exp.to_dict())
    assert back.model == TINY and hash(back.model) == hash(TINY) and back.data.window_flows == 2
    assert ExperimentConfig.from_checkpoint_dict(exp.to_dict()).model == TINY
    cut = model_preset("laguna-xs2-ep8", vocab_size=148)
    assert (cut.n_layers, cut.experts_held, cut.vocab_size, cut.remat, cut.max_len) == (5, 32, 12544, True, 8192)
    assert cut.layer_types == ("full", "sliding", "sliding", "sliding", "full") and cut.heads_per_layer == (48, 64, 64, 64, 48)
    assert model_preset("laguna-xs2-tiny", vocab_size=148).vocab_size == 148
    whole = LagunaConfig()
    assert whole.n_layers == 40 and whole.layer_types.count("full") == 10 and whole.ffn_types.count("dense") == 1
    assert {(k, h) for k, h in zip(whole.layer_types, whole.heads_per_layer)} == {("full", 48), ("sliding", 64)}
    for bad in (dict(heads_per_layer=(4, 6)), dict(heads_per_layer=(4, 5, 4)), dict(layer_types=("full", "linear", "full")), dict(experts_held=32)):
        with pytest.raises(ValueError):
            LagunaConfig.tiny(**bad)


def test_the_cli_resolves_the_preset_to_windows_of_its_length():
    import argparse

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.common import (
        resolve_config,
    )

    cfg = resolve_config(argparse.Namespace(preset="laguna-xs2-ep8"), vocab_size=148)
    assert type(cfg.model) is LagunaConfig and cfg.data.max_len == 8192 and cfg.data.window_flows == 56
    tiny = resolve_config(argparse.Namespace(preset="laguna-xs2-tiny"), vocab_size=148)
    assert tiny.model.vocab_size == 148 and tiny.data.window_flows == 2
