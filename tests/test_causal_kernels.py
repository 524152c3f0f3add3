"""The causal attention's three paths (``ops/causal_attention.py``,
``ops/flash_attention.py``): which one a row takes, read from its shapes, and
the streamed kernels (a head's keys and values a tile at a time from HBM)
against a dense float32 masked softmax and against the XLA query blocks.
CPU, the kernels under the Pallas interpreter, small sizes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops import flash_attention
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.causal_attention import (
    causal_attention,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
    causal_path,
)

STREAMED = ("flash_fwd_streamed", "flash_dkv_streamed", "flash_dq_streamed")


@pytest.mark.parametrize(
    "length, dqk, dv, itemsize, window, path",
    [
        (16384, 256, 256, 2, None, "streamed"),
        (8192, 128, 128, 2, None, "resident"),
        (4096, 192, 128, 2, None, "resident"),
        (4096, 128, 128, 2, None, "resident"),
        (8192, 128, 128, 2, 512, "blocks"),
        (16384, 256, 256, 2, 512, "blocks"),
        (4172, 256, 256, 2, None, "blocks"),
    ],
    ids=["qwen3next-gated", "laguna-full", "kimi-latent", "olmohybrid-full", "laguna-window", "window-at-16k", "no-tile"],
)
def test_the_path_is_read_from_the_shapes(length, dqk, dv, itemsize, window, path):
    """The Qwen3-Next cell's 256-wide heads at 16,384 tokens stream; the full
    layers of Laguna and Olmo-Hybrid and Kimi's latent attention fit a whole
    head in VMEM and stay on the resident kernels; a window, or a length no
    tile divides, takes the XLA blocks."""
    got, tile = causal_path(length, dqk, dv, itemsize, window)
    assert got == path
    assert (tile is None) == (path == "blocks") and (tile is None or length % tile == 0)


def _dense(q, k, v, mask):
    """Every key scored under a dense ``[L, L]`` causal and key mask in
    float32, the keys of a group repeated explicitly."""
    L, G = q.shape[2], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    ok = np.tril(np.ones((L, L), bool))[None, None] & (np.asarray(mask)[:, None, None, :] > 0)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(ok, s, -1e9), -1), v)


def _launches(jaxpr, path="", outer=()):
    """The Pallas kernels a jaxpr launches, nested ones too, in order: each
    one's name, the named scopes down to it and whether a recomputation
    (``remat``) holds it."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield str(eqn.params["name"]), here, "remat2" in outer
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _launches(sub, here, (*outer, eqn.primitive.name))


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.mark.parametrize(
    "B, H, Hkv, L, dqk, dv, real",
    [
        (1, 8, 1, 384, 256, 256, (384,)),
        (2, 4, 2, 384, 192, 128, (384, 150)),
        (2, 8, 1, 640, 64, 64, (500, 100)),
    ],
    ids=["eight-a-key-256-wide", "dqk-192-dv-128-padded", "eight-a-key-five-tiles-padded"],
)
def test_the_streamed_kernels_are_the_dense_softmax_and_the_blocks(monkeypatch, B, H, Hkv, L, dqk, dv, real):
    """With ``VMEM_BUDGET`` at 0 no head fits, so every length that tiles
    streams: tiles of 128, three or five to a row, so a query tile meets key
    tiles it skips, the masked diagonal one and full ones before it; eight
    query heads over one key head, two over each of two, 256-wide heads, ``dqk !=
    dv``; right-padded key masks whose second row has query tiles of padding
    only after its last real token (150 and 100 real tokens). The result over
    the real tokens and ``dq``, ``dk``, ``dv`` against the dense float32
    softmax and against the XLA blocks."""
    rng = np.random.default_rng(11)
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    q, k, v = normal(B, H, L, dqk), normal(B, Hkv, L, dqk), normal(B, Hkv, L, dv)
    mask = (np.arange(L)[None, :] < np.asarray(real)[:, None]).astype(np.int32)
    w = mask[:, None, :, None]
    loss = lambda fn: (lambda *a: ((fn(*a) * w) ** 2).sum())  # noqa: E731

    monkeypatch.setattr(flash_attention, "VMEM_BUDGET", 0)
    assert causal_path(L, dqk, dv, 4) == ("streamed", 128)
    launched = lambda fn: [name for name, _, _ in _launches(jax.make_jaxpr(fn)(q, k, v).jaxpr)]  # noqa: E731
    assert launched(lambda *a: causal_attention(*a, mask)) == [STREAMED[0]]
    assert launched(jax.grad(loss(lambda *a: causal_attention(*a, mask)), (0, 1, 2))) == list(STREAMED)
    got = causal_attention(q, k, v, mask)
    got_grads = jax.grad(loss(lambda *a: causal_attention(*a, mask)), (0, 1, 2))(q, k, v)

    monkeypatch.setattr(flash_attention, "CAUSAL_TILES", ())  # no tile divides a length: the XLA blocks
    blocks = causal_attention(q, k, v, mask)
    blocks_grads = jax.grad(loss(lambda *a: causal_attention(*a, mask)), (0, 1, 2))(q, k, v)
    dense_grads = jax.grad(loss(lambda *a: _dense(*a, mask)), (0, 1, 2))(q, k, v)

    assert got.shape == (B, H, L, dv)
    assert float(jnp.abs((got - _dense(q, k, v, mask)) * w).max()) < 1e-5
    assert float(jnp.abs((got - blocks) * w).max()) < 1e-5
    for a, b, c in zip(got_grads, dense_grads, blocks_grads):
        assert a.shape == b.shape and _rel(a, b) < 1e-5 and _rel(a, c) < 1e-5


@pytest.mark.parametrize("program", ["train_step", "eval_step"])
def test_the_gated_attention_launches_each_streamed_kernel_once(monkeypatch, program):
    """Did the mechanism engage, and only once: with ``VMEM_BUDGET`` at 0 the
    Qwen3-Next class's gated attention at a length that tiles (128) streams,
    and ``engine.train_step`` holds ONE ``flash_fwd_streamed``, ONE
    ``flash_dkv_streamed`` and ONE ``flash_dq_streamed`` for its layer, under
    ``attn/gated/scores/causal_flash``, where the benchmark's shares look. The
    backward kernels are inside the layer's recomputation, the forward one is
    not: the result and the rows' log-sum-exp are kept by name across it
    (``ATTENTION_RESULT``), so the layer does not compute its scores again.
    ``engine.eval_step`` holds the forward kernel alone."""
    import collections

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        Qwen3NextConfig,
        TrainConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models import (
        build_classifier,
        init_params,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        Trainer,
    )

    monkeypatch.setattr(flash_attention, "VMEM_BUDGET", 0)
    cfg = Qwen3NextConfig.tiny(max_len=128).replace(remat=True)
    layers = [i for i in range(cfg.n_layers) if cfg.mixer(i) == "full"]
    rng = np.random.default_rng(0)
    mask = (np.arange(128)[None, :] < np.array([128, 90])[:, None]).astype(np.int32)
    ids = rng.integers(1, cfg.vocab_size, mask.shape).astype(np.int32) * mask
    batch = {"input_ids": ids, "attention_mask": mask, "labels": np.array([0, 1], np.int32)}
    trainer = Trainer(cfg, TrainConfig(log_every=0), pad_id=0)
    state = trainer.init_state(seed=0, params=init_params(build_classifier(cfg), cfg, jax.random.key(1)))
    if program == "train_step":
        jaxpr = jax.make_jaxpr(trainer.train_step.__wrapped__)(state, batch)
    else:
        jaxpr = jax.make_jaxpr(trainer.eval_step.__wrapped__)(state.params, batch, np.ones(2, bool))
    launches = [launch for launch in _launches(jaxpr.jaxpr) if launch[0].startswith("flash_")]
    grad = program == "train_step"
    assert layers and collections.Counter(name for name, _, _ in launches) == {
        name: len(layers) for name in (STREAMED if grad else STREAMED[:1])
    }, launches
    for i in layers:
        scope = f"layer_{i}/attn/gated/scores/causal_flash/"
        assert sum(scope in path for _, path, _ in launches) == (3 if grad else 1), (scope, launches)
    if grad:
        assert all(recomputed == (name != STREAMED[0]) for name, _, recomputed in launches), launches
