"""The second model class (``models/kimi_linear.py``): its ops against their
plain forms, the whole program against the plain float32 reference
(``benchmark/reference/kimi_linear_fp32.py``: the token recurrence), the
share test of expert parallelism, and the engine's normal path on it. CPU,
small sizes, seeded random weights."""

import collections
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import kimi_linear as family
from benchmark.reference import kimi_linear_fp32 as ref
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ExperimentConfig,
    KimiLinearConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import (
    TokenizedSplit,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models import (
    DDoSClassifier,
    build_classifier,
    init_params,
    model_preset,
    param_count,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.kimi_linear import (
    KimiLinearClassifier,
    KimiLinearEncoder,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.causal_attention import (
    causal_attention,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.kda import (
    CHUNK,
    HEADS,
    MAX_BLOCK_DECAY,
    kda_chunked,
    kda_recurrent,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.moe import (
    ROUTE_CHOICE,
    expert_capacity,
    expert_rungs,
    held_experts_ffn,
    route_topk,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.checkpoint import (
    Checkpointer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
    loss_fn,
)

TINY = KimiLinearConfig.tiny(max_len=64)


def _model_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["full_attn_layers"] = list(d["full_attn_layers"])
    return d


def _rows(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    L = cfg.max_len
    mask = (np.arange(L)[None, :] < np.asarray(lens)[:, None]).astype(np.int32)
    ids = rng.integers(1, cfg.vocab_size, (len(lens), L)).astype(np.int32) * mask
    return ids, mask


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(build_classifier(TINY), TINY, jax.random.key(1))


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / (jnp.linalg.norm(b.ravel()) + 1e-30))


def _eqns(jaxpr, path="", outer=()):
    """Every equation of a jaxpr and of the jaxprs inside it, with the named
    scopes down to it and the primitives it is nested in."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        yield here, outer, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, here, (*outer, eqn.primitive.name))


# ------------------------------------------------------------------ the ops
def _kda_inputs(L, seed=0, B=2, H=2, d=16, decay=2.0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(B, H, L, d))).astype(np.float32) * d**-0.5
    k = unit(rng.normal(size=(B, H, L, d))).astype(np.float32)
    v = rng.normal(size=(B, H, L, d)).astype(np.float32)
    g = (-np.abs(rng.normal(size=(B, H, L, d))) * decay).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, size=(B, H, L)).astype(np.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize(
    "L, decay",
    [(128, 2.0), (150, 2.0), (47, 2.0), (200, 2.0), (64, 2.0), (100, 2.0), (160, 2.0), (256, 2.0), (160, 3.0)],
    ids=["L128", "L150", "L47", "L200", "one-chunk", "padded-tail", "L160", "L256", "forgets-within-a-chunk"],
)
def test_chunked_kda_is_the_token_recurrence(L, decay):
    """The outputs of the one kernel that runs where nobody differentiates
    (``kda_fwd``) and all five gradients through the reverse kernel ``kda_bwd``
    (both under the interpreter here) over one to four chunks, lengths that are
    no multiple of the chunk, a decay (e^-100 a chunk) that a whole-chunk
    factorisation would overflow on, and one (e^-150) under which a chunk's
    last tokens see nothing of the state it started from."""
    x = _kda_inputs(L, decay=decay)
    want = kda_recurrent(*x)
    got = kda_chunked(*x)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.abs(got - want).max()) < 2e-6
    loss = lambda fn: (lambda *a: (fn(*a) ** 2).sum())  # noqa: E731
    g_want = jax.grad(loss(kda_recurrent), argnums=(0, 1, 2, 3, 4))(*x)
    g_got = jax.grad(loss(kda_chunked), argnums=(0, 1, 2, 3, 4))(*x)
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) < 1e-5


def test_chunked_kda_in_bf16_is_near_its_float32():
    """The products read bfloat16, the sums, the decays and the state stay
    float32: outputs and gradients lie within bfloat16's rounding of the
    float32 ones, not at it (something was rounded) and not far from it
    (0.35% the outputs, 0.5% the gradients and 3.5% the log-decay's, whose
    terms cancel)."""
    x = _kda_inputs(160)
    loss = lambda dtype: (lambda *a: (kda_chunked(*a, dtype=dtype) ** 2).sum())  # noqa: E731
    err = _rel(kda_chunked(*x, dtype=jnp.bfloat16), kda_chunked(*x))
    assert 1e-4 < err < 2e-2, err
    for a, b, limit in zip(
        jax.grad(loss(jnp.bfloat16), argnums=(0, 1, 2, 3, 4))(*x), jax.grad(loss(jnp.float32), argnums=(0, 1, 2, 3, 4))(*x),
        (1.5e-2, 1.5e-2, 1.5e-2, 6e-2, 1.5e-2),
    ):
        assert 1e-4 < _rel(a, b) < limit, _rel(a, b)


@pytest.mark.parametrize(
    "L, H",
    [(160, 3), (100, 2), (64, 2), (192, 2 * HEADS)],
    ids=["a-head-a-step", "padded-tail", "one-chunk", "two-steps-of-8-heads"],
)
def test_the_undifferentiated_forward_is_the_token_recurrence(L, H):
    """``kda_chunked`` where nobody asks for a gradient (one launch of
    ``kda_fwd``: pair matrices, inverse and recurrence in the kernel) against
    the recurrence token by token, for a count of heads that :data:`HEADS`
    does not divide and one that fills two grid steps: the same numbers in
    float32, and with bfloat16 products within bfloat16's rounding of them
    (something was rounded, nothing is far)."""
    x = _kda_inputs(L, H=H, seed=3)
    want = kda_recurrent(*x)
    got = kda_chunked(*x)
    assert got.shape == want.shape == (2, H, L, 16) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 2e-6
    err = _rel(kda_chunked(*x, dtype=jnp.bfloat16), want)
    assert 1e-4 < err < 2e-2, err


# name: (L, B, H, decay, one decay a head); every row has a cotangent of its own
_GRADIENT_CASES = {
    "one-chunk": (64, 2, 2, 2.0, False),
    "padded-tail": (100, 2, 2, 2.0, False),
    "several-chunks": (200, 2, 2, 2.0, False),
    "three-heads": (160, 1, 3, 2.0, False),
    "two-steps-of-8-heads": (128, 1, 2 * HEADS, 2.0, False),
    "three-rows": (150, 3, 2, 2.0, False),
    "slow-decay": (160, 2, 2, 0.05, False),
    "fast-decay": (192, 2, 2, 3.0, False),  # e^-150 a chunk: a whole-chunk e^{-G} overflows
    "one-decay-a-head": (150, 2, 2, 1.0, True),
}


def _gradient_case(name):
    """The five gradients of ``sum(fn(q, k, v, g, beta) * cot)`` on the case's inputs, as a function of ``fn``."""
    L, B, H, decay, per_head = _GRADIENT_CASES[name]
    q, k, v, g, beta = _kda_inputs(L, seed=len(name), B=B, H=H, decay=decay)
    if per_head:
        g = np.ascontiguousarray(np.broadcast_to(g[..., :1], g.shape))
    cot = jnp.asarray(np.random.default_rng(L).normal(size=v.shape), jnp.float32)
    return lambda fn: jax.grad(lambda *a: (fn(*a) * cot).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)


@pytest.mark.parametrize("case", list(_GRADIENT_CASES))
def test_the_gradient_is_the_token_recurrences(case):
    """``jax.grad`` through ``kda_chunked`` (the ``fwd`` rule's ``kda_fwd``,
    which also writes every chunk's starting state and inverse, and ONE launch
    of ``kda_bwd`` for the batch: the pair matrices built again, the three lines', the
    system's, the pair products' and the running sum's transposes) against
    ``jax.grad`` through ``kda_recurrent`` in float32: one chunk, a padded
    tail, several chunks; heads that :data:`HEADS` does not divide and two
    grid steps of them; three rows, each under a cotangent of its own; a decay
    so slow that nothing is forgotten and one that a whole-chunk ``e^{-G}``
    overflows on; one decay a head broadcast over its channels. Each of the
    five gradients has its own bound (the log-decay's terms cancel)."""
    grads = _gradient_case(case)
    got, want = grads(kda_chunked), grads(kda_recurrent)
    for name, a, b, limit in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want, (4e-6, 4e-6, 4e-6, 1.5e-5, 4e-6)):
        assert a.shape == b.shape and a.dtype == b.dtype and np.isfinite(np.asarray(a)).all(), name
        assert _rel(a, b) < limit, (name, _rel(a, b))


@pytest.mark.parametrize("case", ["padded-tail", "several-chunks", "three-rows", "fast-decay"])
def test_the_gradient_in_bf16_is_near_the_token_recurrences(case):
    """The same with bfloat16 products (float32 sums, running sums, inverse
    and state): within bfloat16's rounding of the recurrence's float32
    gradients, not at them and not far (1.5% but the log-decay's 6%, the
    bounds ``test_chunked_kda_in_bf16_is_near_its_float32`` holds the kernels
    to against their own float32)."""
    grads = _gradient_case(case)
    got, want = grads(functools.partial(kda_chunked, dtype=jnp.bfloat16)), grads(kda_recurrent)
    for name, a, b, limit in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want, (1.5e-2, 1.5e-2, 1.5e-2, 6e-2, 1.5e-2)):
        assert 1e-4 < _rel(a, b) < limit, (name, _rel(a, b))


def test_a_decay_past_the_clip_gives_finite_gradients():
    """Where a channel forgets more than ``e^-MAX_BLOCK_DECAY`` inside a block
    of 16 tokens the diagonal block's inverse factor is clipped (the entry it
    stands in is under ``e^-80`` of its neighbours; the published
    initialisation never comes near), and no gradient passes through the
    clipped exponent, as ``jnp.minimum`` gave the XLA path: the output and all
    five gradients stay finite where the unclipped factor would be ``inf``."""
    q, k, v, g, beta = _kda_inputs(64, decay=12.0)  # a mean log-decay of 10 a token
    inside_a_block = -g.reshape(2, 2, 4, 16, 16)[:, :, :, 1:].sum(3)
    assert inside_a_block.max() > 88 > MAX_BLOCK_DECAY  # float32's e^88 is the last finite one
    got = jax.grad(lambda *a: (kda_chunked(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert np.isfinite(np.asarray(kda_chunked(q, k, v, g, beta))).all()
    assert all(np.isfinite(np.asarray(a)).all() for a in got)


def test_kda_padding_after_the_real_tokens_changes_no_real_state():
    q, k, v, g, beta = _kda_inputs(160)
    short = kda_chunked(q[:, :, :100], k[:, :, :100], v[:, :, :100], g[:, :, :100], beta[:, :, :100])
    full = kda_chunked(q, k, v, g, beta)
    assert float(jnp.abs(full[:, :, :100] - short).max()) < 1e-6


@pytest.mark.parametrize("L", [96, 300, 1100])
def test_blocked_causal_attention_is_the_plain_softmax(L):
    """One short block, two blocks of one group, and two groups (the
    second's last block padded)."""
    rng = np.random.default_rng(3)
    B, H, dqk, dv = 2, 2, 24, 16
    q, k = (rng.normal(size=(B, H, L, dqk)).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(B, H, L, dv)).astype(np.float32)
    mask = (np.arange(L)[None, :] < np.array([L, L - 21])[:, None]).astype(np.int32)

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dqk)
        ok = (np.arange(L)[None, :] <= np.arange(L)[:, None])[None, None] & (mask[:, None, None, :] > 0)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(ok, s, -1e9), -1), v)

    got = causal_attention(q, k, v, mask)
    assert got.shape == (B, H, L, dv)
    assert float(jnp.abs(got - plain(q, k, v)).max()) < 1e-5
    f = lambda fn: (lambda *a: (fn(*a) ** 2).sum())  # noqa: E731
    for a, b in zip(
        jax.grad(f(lambda *a: causal_attention(*a, mask)), (0, 1, 2))(q, k, v),
        jax.grad(f(plain), (0, 1, 2))(q, k, v),
    ):
        assert _rel(a, b) < 1e-5


def _experts(rng, held, D=8, F=12):
    return tuple(rng.normal(size=s).astype(np.float32) * 0.3 for s in ((held, D, F), (held, D, F), (held, F, D)))


def test_no_token_is_dropped_when_every_token_goes_to_held_experts():
    """A routing that names only held experts, all tokens on the same two:
    the worst case for a buffer. With the capacity that holds for every
    routing nothing overflows and the result is the dense sum; the buffer is
    shared, so two experts may fill what four were sized for; with a smaller
    one the overflow is counted, never silent."""
    rng = np.random.default_rng(0)
    T, D, held, k = 40, 8, 4, 2
    x = rng.normal(size=(T, D)).astype(np.float32)
    wg, wu, wd = _experts(rng, held)
    idx = np.tile(np.array([[1, 2]], np.int32), (T, 1))
    w = rng.uniform(0.2, 1.0, size=(T, k)).astype(np.float32)
    valid = np.ones(T, bool)
    dense = sum(
        w[:, j : j + 1] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]) for j, e in enumerate((1, 2))
    )
    cap = expert_capacity(T, k, 16, held)  # 4 x the mean total of 20 rows
    assert cap == T * k  # never more than a token's slots that can be held
    assert expert_capacity(16384, 8, 256, 8) == 16384 and expert_capacity(T, k, 64, held) == 24
    y, slots, overflow, _ = held_experts_ffn(x, idx, w, valid, wg, wu, wd, offset=0, capacity=cap, dtype=jnp.float32)
    assert int(overflow) == 0 and slots.tolist() == [0, T, T, 0]
    assert float(jnp.abs(y - dense).max()) < 1e-5
    y, slots, overflow, _ = held_experts_ffn(x, idx, w, valid, wg, wu, wd, offset=0, capacity=56, dtype=jnp.float32)
    assert int(overflow) == 2 * T - 56 and slots.tolist() == [0, T, T, 0]
    # expert 1's 40 slots are all in; of expert 2's the first 16 tokens'
    only_1 = w[:, :1] * ((jax.nn.silu(x @ wg[1]) * (x @ wu[1])) @ wd[1])
    assert float(jnp.abs(y[16:] - only_1[16:]).max()) < 1e-5 and float(jnp.abs(y[:16] - dense[:16]).max()) < 1e-5
    # padding is routed nowhere
    valid[30:] = False
    y, slots, _, _ = held_experts_ffn(x, idx, w, valid, wg, wu, wd, offset=0, capacity=cap, dtype=jnp.float32)
    assert slots.tolist() == [0, 30, 30, 0] and float(jnp.abs(y[30:]).max()) == 0.0
    # gradients reach the experts' weights, the rows and the gate weights
    f = lambda x, w, wg: held_experts_ffn(x, idx, w, np.ones(T, bool), wg, wu, wd, offset=0, capacity=cap, dtype=jnp.float32)[0].sum()  # noqa: E731
    g = lambda x, w, wg: sum((w[:, j : j + 1] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])).sum() for j, e in enumerate((1, 2)))  # noqa: E731
    for a, b in zip(jax.grad(f, (0, 1, 2))(x, w, wg), jax.grad(g, (0, 1, 2))(x, w, wg)):
        assert _rel(a, b) < 1e-5


def test_the_ladder_is_the_capacity_at_one_and_four_times_the_mean():
    """The three window cells' ladders, a bound that cuts the top short, and a
    shape whose two rungs are one."""
    assert expert_rungs(16384, 8, 256, 8) == (4096, 16384)
    assert expert_rungs(16384, 8, 256, 32) == (16384, 65536)
    assert expert_rungs(16384, 10, 512, 32) == (10240, 40960)
    for args in ((16384, 8, 256, 8), (64, 2, 32, 4), (40, 2, 16, 4), (3, 2, 4, 4)):
        assert expert_rungs(*args)[-1] == expert_capacity(*args)
    assert expert_rungs(40, 2, 16, 4) == (24, 80) and expert_rungs(40, 2, 8, 4) == (40, 80)  # T * k bounds the top
    assert expert_rungs(3, 2, 4, 4) == (6,)


#: 64 tokens' two slots over 4 held of 32 experts, a mean total of 16 rows, with a rung between the program's two:
#: ``held_experts_ffn`` takes whatever prefixes it is handed.
LADDER = (16, 32, 64)


def _filling(n, T=64):
    """A routing whose first ``n`` token-slots name held experts (a token's
    two name two experts) and whose others name absent ones."""
    idx = np.empty((T, 2), np.int32)
    for t in range(T):
        for j in range(2):
            idx[t, j] = (t % 2) * 2 + j if 2 * t + j < n else 8 + j
    return idx


@pytest.mark.parametrize("rung", range(3))
@pytest.mark.parametrize("beside", [-1, 0, 1])
def test_a_call_moves_the_shortest_rung_that_holds_its_slots(rung, beside):
    """Filled to one row under a rung, to the rung and to one row over it: the
    call takes the shortest rung that holds the slots (``rows`` says which),
    and the result, the counts and the gradients in the rows, the slots'
    weights and the experts' weights are the top rung's alone; one row over
    the top is the overflow, counted as ever."""
    assert (LADDER[0], LADDER[-1]) == expert_rungs(64, 2, 32, 4)
    rng = np.random.default_rng(3)
    T, D = 64, 8
    filled = LADDER[rung] + beside
    idx = _filling(filled)
    x = rng.normal(size=(T, D)).astype(np.float32)
    w = rng.uniform(0.2, 1.0, size=(T, 2)).astype(np.float32)
    wg, wu, wd = _experts(rng, 4)
    cot = rng.normal(size=(T, D)).astype(np.float32)

    def layer(rungs):
        return lambda x, w, wg: held_experts_ffn(  # noqa: E731
            x, idx, w, np.ones(T, bool), wg, wu, wd, offset=0, capacity=LADDER[-1], dtype=jnp.float32, rungs=rungs
        )

    y, slots, overflow, rows = layer(LADDER)(x, w, wg)
    top_y, top_slots, top_overflow, top_rows = layer(())(x, w, wg)
    assert int(top_rows) == LADDER[-1]
    assert int(rows) == next((r for r in LADDER if filled <= r), LADDER[-1])
    assert int(slots.sum()) == filled and slots.tolist() == top_slots.tolist()
    assert int(overflow) == int(top_overflow) == max(0, filled - LADDER[-1])
    assert float(jnp.abs(y - top_y).max()) <= 1e-6 and float(jnp.abs(top_y).max()) > 0.1
    grads = lambda rungs: jax.grad(lambda *a: (layer(rungs)(*a)[0] * cot).sum(), (0, 1, 2))(x, w, wg)  # noqa: E731
    for a, b in zip(grads(LADDER), grads(())):
        assert 1e-3 < float(jnp.abs(b).max()) and float(jnp.abs(a - b).max()) <= 1e-6 * max(1.0, float(jnp.abs(b).max()))


def test_an_overflowing_call_moves_the_top_rung_and_counts_what_it_left_out():
    """The overflowing case above (every token on experts 1 and 2, a buffer
    of 56 rows) with a ladder beneath it: the whole buffer moves, the slots it
    cannot take are counted, and what it took is what the top alone took."""
    rng = np.random.default_rng(0)
    T, held = 40, 4
    x = rng.normal(size=(T, 8)).astype(np.float32)
    wg, wu, wd = _experts(rng, held)
    idx = np.tile(np.array([[1, 2]], np.int32), (T, 1))
    w = rng.uniform(0.2, 1.0, size=(T, 2)).astype(np.float32)
    call = lambda rungs: held_experts_ffn(  # noqa: E731
        x, idx, w, np.ones(T, bool), wg, wu, wd, offset=0, capacity=56, dtype=jnp.float32, rungs=rungs
    )
    y, slots, overflow, rows = call((16, 32, 56, 80))  # a rung that is no prefix of the buffer is left out
    assert int(rows) == 56 and int(overflow) == 2 * T - 56 and slots.tolist() == [0, T, T, 0]
    assert float(jnp.abs(y - call(())[0]).max()) <= 1e-6
    # and half the tokens padding: 40 slots, the middle rung
    valid = np.arange(T) < 20
    _, slots, overflow, rows = held_experts_ffn(
        x, idx, w, valid, wg, wu, wd, offset=0, capacity=56, dtype=jnp.float32, rungs=(16, 40)
    )
    assert int(rows) == 40 and int(overflow) == 0 and slots.tolist() == [0, 20, 20, 0]


def test_a_recomputed_layer_takes_the_rung_its_forward_pass_took():
    """Under ``jax.checkpoint`` that keeps the router's choice by name (what
    ``models/blocks.py::decoder`` does to a block) the gradients are the
    un-checkpointed layer's: the rung is chosen again from the kept ``idx``."""
    rng = np.random.default_rng(4)
    T, D, E, held, k = 64, 8, 32, 4, 2
    x = rng.normal(size=(T, D)).astype(np.float32)
    router = rng.normal(size=(D, E)).astype(np.float32)
    wg, wu, wd = _experts(rng, held)
    rungs = expert_rungs(T, k, E, held)

    def layer(x, router, wg):
        idx, w = route_topk(jax.nn.sigmoid(x @ router), 0.0, k, 1.0)
        y, _, _, rows = held_experts_ffn(
            x, idx, w, np.ones(T, bool), wg, wu, wd, offset=0, capacity=rungs[-1], dtype=jnp.float32, rungs=rungs
        )
        return (y**2).sum(), rows

    kept = jax.checkpoint(layer, policy=jax.checkpoint_policies.save_only_these_names(ROUTE_CHOICE))
    (_, rows), plain = jax.value_and_grad(layer, (0, 1, 2), has_aux=True)(x, router, wg)
    (_, again), recomputed = jax.jit(jax.value_and_grad(kept, (0, 1, 2), has_aux=True))(x, router, wg)
    assert int(rows) == int(again) == rungs[0] < rungs[-1]  # the lower rung, or the test tests nothing
    for a, b in zip(recomputed, plain):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max()) and float(jnp.abs(b).max()) > 0


@pytest.mark.parametrize("which, shares", [("kimi_linear", 4), ("laguna", 8)])
def test_the_shares_add_up_to_the_uncut_layer(tiny_params, which, shares):
    """The guide's share test, for both decoder classes (they share the
    layer, ``models/blocks.py``): 4 shares of 4 of the 16 experts, and the 8
    shares of 2 that the 8 chips of the Laguna deployment hold. The routed
    parts all the shares give, with the shared expert counted once, add up to
    what the uncut reference gives for the whole layer."""
    from benchmark.reference import laguna_fp32
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import LagunaConfig

    cfg, reference = {"kimi_linear": (TINY, ref), "laguna": (LagunaConfig.tiny(max_len=64), laguna_fp32)}[which]
    E, held = cfg.n_experts, cfg.n_experts // shares
    m = {**dataclasses.asdict(cfg), "experts_held": E}
    rng = np.random.default_rng(5)
    params = tiny_params if cfg is TINY else init_params(build_classifier(cfg), cfg, jax.random.key(1))
    lp = jax.tree.map(np.asarray, params["encoder"]["layer_1"]["moe"])
    D, F = cfg.dim, cfg.expert_dim
    full = {
        **lp,
        **dict(zip(("experts_gate", "experts_up", "experts_down"), _experts(rng, E, D, F))),
    }
    x = rng.normal(size=(60, D)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._moe(jnp.asarray(x), full, m, lambda a: a)
        shared = reference._swiglu(jnp.asarray(x), full["shared"], lambda a: a)
    scores = jax.nn.sigmoid(x @ full["router"])
    idx, w = route_topk(scores, full.get("select_bias", 0.0), cfg.experts_per_token, cfg.routed_scale)
    part_of = lambda lo: held_experts_ffn(  # noqa: E731
        x, idx, w, np.ones(60, bool), full["experts_gate"][lo : lo + held], full["experts_up"][lo : lo + held],
        full["experts_down"][lo : lo + held], offset=lo, capacity=60 * cfg.experts_per_token, dtype=jnp.float32,
    )
    total, seen = shared, 0
    for share in range(shares):
        y, slots, overflow, _ = part_of(held * share)
        assert int(overflow) == 0
        total, seen = total + y, seen + int(slots.sum())
    assert seen == 60 * cfg.experts_per_token  # every slot lands on exactly one share
    assert float(jnp.abs(total - want).max()) < 1e-5
    # and one share alone is what the reference gives when it is given that share
    part, _ = reference._moe(
        jnp.asarray(x), {**full, **{k: full[k][held : 2 * held] for k in ("experts_gate", "experts_up", "experts_down")}},
        {**m, "experts_held": held, "expert_offset": held}, lambda a: a,
    )
    assert float(jnp.abs(shared + part_of(held)[0] - part).max()) < 1e-5


# ------------------------------------------- the program and the reference
def test_program_against_the_reference_fp32(tiny_params):
    """Hidden states, logits, loss and gradients at the tiny preset in
    float32: the chunked program and the token recurrence agree to rounding."""
    ids, mask = _rows(TINY, [64, 50, 37, 33])
    labels = np.array([0, 1, 1, 0], np.int32)
    model = _model_dict(TINY)
    hidden, logits = jax.jit(family.program(TINY))(tiny_params, ids, mask)
    want_h, want_z = ref.forward(tiny_params, ids, mask, model)
    w = mask[..., None]
    assert _rel(hidden * w, want_h * w) < 1e-5
    assert float(jnp.abs(logits - want_z).max()) < 1e-5
    # last-real-token pooling: the logits follow the row's own last token
    assert float(jnp.abs(logits[1] - (hidden[1, 49] @ tiny_params["classifier"]["kernel"] + tiny_params["classifier"]["bias"])).max()) < 1e-5
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
    # under a forced choice of experts the reference computes THAT choice, and hands back its own
    forced = [np.roll(np.asarray(idx), 1, axis=-2) for idx, _ in routes]  # every token gets its neighbour's experts
    h_forced, _ = ref.forward(tiny_params, ids, mask, model, forced=forced)
    assert _rel(h_forced * w, want_h * w) > 1e-3
    h_own, z_own = ref.forward(tiny_params, ids, mask, model, forced=[np.asarray(idx) for idx, _ in routes])
    assert _rel(h_own * w, want_h * w) < 1e-6 and float(jnp.abs(z_own - want_z).max()) < 1e-6
    _, g_own, r_own = ref.loss_and_grads(tiny_params, ids, mask, labels, model, forced=[np.asarray(idx) for idx, _ in routes])
    assert max(jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), g_own, want_grads))) < 1e-6
    assert all(np.array_equal(np.asarray(a[0]), np.asarray(b[0])) for a, b in zip(r_own, routes))
    # the router's choices: the same experts as the reference's
    got = jax.jit(family.routing(TINY))(tiny_params, ids, mask)
    for g, (want_idx, _) in zip(got, routes):
        assert (np.sort(np.asarray(g)[mask > 0], -1) == np.sort(np.asarray(want_idx)[mask > 0], -1)).all()


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


def test_remat_changes_no_number(tiny_params):
    ids, mask = _rows(TINY, [64, 40])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([1, 0], np.int32)}
    grad = lambda cfg: jax.jit(jax.grad(lambda p: loss_fn(build_classifier(cfg), p, batch, jax.random.key(0))))(tiny_params)  # noqa: E731
    for a, b in zip(jax.tree.leaves(grad(TINY)), jax.tree.leaves(grad(TINY.replace(remat=True)))):
        assert float(jnp.abs(a - b).max()) <= 1e-6 * (1.0 + float(jnp.abs(b).max()))


def test_the_recomputation_keeps_the_routers_choice(tiny_params):
    """Under ``remat`` the backward pass recomputes a block from its input,
    and a top-k chosen AGAIN from a residual stream that the recomputation
    rounds elsewhere falls otherwise on some token-slots: the backward pass
    would differentiate experts the forward pass did not run. The choice is
    kept (``ops/moe.py::ROUTE_CHOICE``): no ``top_k`` is inside a
    recomputation, one a layer is outside."""
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, [64, 40])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([1, 0], np.int32)}
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: loss_fn(build_classifier(cfg), p, batch, jax.random.key(0)))
    )(tiny_params)

    top_ks = [
        "remat2" in outer for _, outer, eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "top_k"
    ]  # inside a recomputation?

    n_moe = sum(cfg.is_moe(i) for i in range(cfg.n_layers))
    assert top_ks == [False] * n_moe


# --------------------------------------------------- the engine's normal path
def test_trainer_fit_evaluate_and_checkpoint_round_trip(tmp_path, tiny_params):
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, np.random.default_rng(0).integers(30, 64, size=12))
    split = TokenizedSplit(ids, mask, (np.arange(12) % 2).astype(np.int32))
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    assert state.route["slots"].shape == (cfg.experts_held,)
    state, losses = trainer.fit(state, split, batch_size=4, epochs=2)
    assert np.isfinite(losses).all() and int(state.step) == 6
    route = trainer.last_route
    moe_layers = sum(cfg.is_moe(i) for i in range(cfg.n_layers))
    assert route["overflow"] == 0 and 0 < int(route["slots"].sum()) <= 2 * int(mask.sum()) * moe_layers * 4
    assert int(state.route["slots"].sum()) == 0  # read and started again
    metrics = trainer.evaluate(state.params, split, batch_size=4)
    assert 0.0 <= metrics["Accuracy"] <= 100.0 and len(metrics["probs"]) == 12
    with Checkpointer(str(tmp_path / "ckpt")) as ckpt:
        ckpt.save(int(state.step), state, meta={"config": {"model": _model_dict(cfg)}})
        ckpt.wait()
        back = ckpt.restore(trainer.init_state(seed=0))
    for a, b in zip(jax.tree.leaves(back.params), jax.tree.leaves(state.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(back.step) == 6


@pytest.mark.parametrize("program", ["train_step", "eval_step"])
def test_the_train_step_runs_the_recurrence_in_its_two_kernels(tiny_params, program):
    """Did the mechanism engage: ``engine.train_step`` for the tiny
    configuration (two chunks a row, four rows) holds, under every KDA layer's
    scope ``kda/chunks``, ``kda_fwd`` twice (the pass and the per-layer
    recomputation, each one launch for the batch under ``chunks/fwd``; in a
    differentiated step both are the ``fwd`` rule's launch, which also writes
    the chunks' starting states and inverses) and ``kda_bwd`` once (under ``chunks/bwd``), by their
    names, and nothing else: no loop of any kind (no ``scan`` over the rows or
    the chunks, no ``while``), no substitution, no scatter. ``engine.eval_step``
    holds one launch of ``kda_fwd`` a layer."""
    cfg = TINY.replace(max_len=2 * CHUNK, remat=True)
    ids, mask = _rows(cfg, [128, 100, 80, 70])
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([0, 1, 0, 1], np.int32)}
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    if program == "train_step":
        jaxpr = jax.make_jaxpr(trainer.train_step.__wrapped__)(state, batch)
    else:
        jaxpr = jax.make_jaxpr(trainer.eval_step.__wrapped__)(state.params, batch, np.ones(len(ids), bool))

    # (path, the primitives around it, equation), outside the kernels' own bodies
    eqns = [x for x in _eqns(jaxpr.jaxpr) if "kda/chunks" in x[0] and "pallas_call" not in x[1]]
    launches = [(path, eqn.params["name"], eqn) for path, _, eqn in eqns if eqn.primitive.name == "pallas_call"]
    kernels = collections.Counter(name for _, name, _ in launches)
    kda_layers = [i for i in range(cfg.n_layers) if cfg.mixer(i) == "kda"]
    n = len(kda_layers)
    grad = program == "train_step"
    assert n and kernels == ({"kda_fwd": 2 * n, "kda_bwd": n} if grad else {"kda_fwd": n}), kernels
    for layer in kda_layers:
        assert any(f"layer_{layer}/kda/kda/chunks" in path for path, _, _ in launches)
    assert all(("/chunks/fwd/" in path) == (name == "kda_fwd") for path, name, _ in launches), launches
    assert all(("/chunks/bwd/" in path) == (name == "kda_bwd") for path, name, _ in launches), launches
    # The fwd rule's launch writes two more results, the states and the inverses. Under ``jax.grad`` a checkpointed
    # block's first pass is the rule's launch too (JAX drops the residuals after it; a kernel's unused result stays).
    assert collections.Counter(len(eqn.outvars) for _, name, eqn in launches if name == "kda_fwd") == (
        {3: 2 * n} if grad else {1: n}
    )
    assert not [(path, eqn.primitive.name) for path, _, eqn in eqns if eqn.primitive.name in ("scan", "while")]
    assert all("scan" not in outer and "while" not in outer for _, outer, _ in eqns)
    assert not [eqn.primitive.name for _, _, eqn in eqns if eqn.primitive.name in ("triangular_solve", "scatter", "dot_general")]


def test_overflow_is_counted_and_said_loudly_by_fit_and_by_evaluate(monkeypatch):
    """4 of 32 experts held and a selection bias that sends every token's 4
    slots to them: the shared buffer (4 x the mean total = 2 slots a token)
    takes half. The step and the evaluation count the rest, publish it and
    log an error; neither carries on in silence."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import engine

    cfg = TINY.replace(n_experts=32, remat=True)
    params = init_params(build_classifier(cfg), cfg, jax.random.key(3))
    moe_layers = [i for i in range(cfg.n_layers) if cfg.is_moe(i)]
    for i in moe_layers:
        moe = params["encoder"][f"layer_{i}"]["moe"]
        moe["select_bias"] = moe["select_bias"].at[: cfg.experts_held].set(10.0)
    ids, mask = _rows(cfg, [64, 50, 40, 33])
    split = TokenizedSplit(ids, mask, np.array([0, 1, 0, 1], np.int32))
    said = []
    monkeypatch.setattr(engine.log, "error", said.append)
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state, _ = trainer.fit(trainer.init_state(seed=0, params=params), split, batch_size=4, epochs=1)
    routed = int(mask.sum()) * cfg.experts_per_token * len(moe_layers)
    buffer = 4 * cfg.max_len * 2 * len(moe_layers)  # rows of the step's buffers
    assert trainer.last_route == {"slots": trainer.last_route["slots"], "overflow": routed - buffer, "rows": buffer} and routed > buffer
    assert int(trainer.last_route["slots"].sum()) == routed
    assert len(said) == 1 and said[0].startswith(f"fit: {routed - buffer} of {routed} token-slots") and "NOT computed" in said[0]
    metrics = trainer.evaluate(state.params, split, batch_size=4, collect_probs=False)
    assert metrics["routed_overflow"] == routed - buffer
    assert len(said) == 2 and said[1].startswith(f"evaluate: {routed - buffer} of")
    # a model without expert layers says nothing of routing
    bert = Trainer(ModelConfig.tiny(), TrainConfig(log_every=0), pad_id=0)
    b_ids, b_mask = _rows(ModelConfig.tiny(), [32, 20])
    out = bert.evaluate(bert.init_state(seed=0).params, TokenizedSplit(b_ids, b_mask, np.array([0, 1], np.int32)), batch_size=2)
    assert "routed_overflow" not in out and len(said) == 2


def test_head_only_scope_reads_the_trees_top_level(tiny_params):
    cfg = TINY.replace(remat=True)
    ids, mask = _rows(cfg, [64, 50, 40, 33])
    split = TokenizedSplit(ids, mask, np.array([0, 1, 0, 1], np.int32))
    head = Trainer(cfg, TrainConfig(log_every=0, trainable="head"), pad_id=0)
    s0 = head.init_state(seed=0, params=jax.tree.map(jnp.copy, tiny_params))
    s1, _ = head.fit(s0, split, batch_size=4, epochs=1)
    assert np.array_equal(
        np.asarray(s1.params["encoder"]["layer_0"]["kda"]["q_proj"]["kernel"]),
        np.asarray(tiny_params["encoder"]["layer_0"]["kda"]["q_proj"]["kernel"]),
    )
    assert not np.array_equal(np.asarray(s1.params["classifier"]["kernel"]), np.asarray(tiny_params["classifier"]["kernel"]))


def test_the_one_constructor_returns_todays_classifier_for_a_model_config():
    cfg = ModelConfig.tiny()
    model = build_classifier(cfg)
    assert type(model) is DDoSClassifier and model.cfg == cfg
    ids, mask = _rows(cfg, [32, 20])
    a = init_params(model, cfg, jax.random.key(2))
    b = init_params(DDoSClassifier(cfg), cfg, jax.random.key(2))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert np.array_equal(
        np.asarray(model.apply({"params": a}, ids, mask)), np.asarray(DDoSClassifier(cfg).apply({"params": b}, ids, mask))
    )
    assert type(build_classifier(TINY)) is KimiLinearClassifier
    # a BERT state carries no routing accumulator: its step is the program it was
    state = Trainer(cfg, TrainConfig(log_every=0), pad_id=0).init_state(seed=0, params=a)
    assert state.route is None and len(jax.tree.leaves(state)) == len(jax.tree.leaves(state[:4]))


def test_config_round_trips_and_presets():
    exp = ExperimentConfig(model=TINY, data=ExperimentConfig().data.__class__(max_len=TINY.max_len, window_flows=2))
    back = ExperimentConfig.from_dict(exp.to_dict())
    assert back.model == TINY and hash(back.model) == hash(TINY) and back.data.window_flows == 2
    assert ExperimentConfig.from_checkpoint_dict(exp.to_dict()).model == TINY
    cut = model_preset("kimi-linear-ep32", vocab_size=148)
    assert (cut.n_layers, cut.experts_held, cut.vocab_size, cut.remat) == (5, 8, 20480, True)
    assert model_preset("kimi-linear-tiny", vocab_size=148).vocab_size == 148
    # the trap PERF.md section 4 kept from PR 21: a published preset keeps its published table
    assert model_preset("distilbert", vocab_size=148).vocab_size == 30522
    assert model_preset("bert-large", vocab_size=148).vocab_size == 30522
    assert model_preset("tiny", vocab_size=148).vocab_size == 148
    with pytest.raises(ValueError, match="30522-row"):
        model_preset("distilbert", vocab_size=40000)
    n = param_count(jax.eval_shape(lambda: init_params(build_classifier(ModelConfig.distilbert_base()), ModelConfig.distilbert_base(), jax.random.key(0))))
    assert n == 66_364_418


def test_window_renderer_joins_consecutive_flows_and_labels_a_burst():
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        SplitArrays,
        default_tokenizer,
        tokenize_split,
        window_split,
    )

    texts = [f"Destination port is {i}." for i in range(10)]
    labels = np.array([0, 0, 0, 0, 1, 1, 0, 0, 0, 0], np.int32)
    w = window_split(SplitArrays(texts, labels), 3)
    assert len(w) == 3 and w.texts[1] == " ".join(texts[3:6]) and w.labels.tolist() == [0, 1, 0]
    enc = tokenize_split(w, default_tokenizer(), 64)
    assert enc.input_ids.shape == (3, 64) and (enc.attention_mask.sum(-1) > 12).all()
    assert len(window_split(SplitArrays(texts[:2], labels[:2]), 3)) == 1
