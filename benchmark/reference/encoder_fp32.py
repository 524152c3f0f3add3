"""The plain reference: a BERT-family encoder and the paper's head in
straightforward float32 ``jax.numpy``.

Follows the published description of DistilBERT / BERT (Sanh et al. 2019;
Devlin et al. 2018): word + learned position embeddings, LayerNorm
(eps 1e-12), N post-LayerNorm blocks (multi-head self-attention with an
additive key mask, residual, LayerNorm, GELU feed-forward, residual,
LayerNorm), then the paper's head (client1.py:53-65): CLS token ->
Dropout (off at inference) -> Linear(dim, n_classes). No kernels, no
batching tricks, no bf16, no dropout. Departures from the published
models, because the program under test makes the same ones and the
comparison is of arithmetic, not of architecture: no token-type embedding
and no pooler (BERT has both; DistilBERT neither), and the GELU form is the
configuration's (``exact`` = erf as published, ``tanh`` = the
approximation).

On a TPU a float32 matmul runs at reduced precision unless
``default_matmul_precision("highest")`` is set, so ``logits`` sets it.
Reads the parameter tree by the names ``models/distilbert.py`` gives its
leaves; nothing else of the program is imported.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _forward(params, input_ids, attention_mask, *, n_layers, n_heads, eps, gelu, rnd):
    """Last hidden states ``[B, L, D]`` and logits. ``rnd`` rounds every
    weight and every sub-layer's output (identity in the reference itself;
    a coarser type in tools/tolerance_probe.py, which shows what the
    tolerances of harness.check_model let through)."""
    params = jax.tree.map(lambda a: rnd(jnp.asarray(a, jnp.float32)), params)
    enc = params["encoder"]
    emb = enc["embeddings"]
    B, L = input_ids.shape
    x = emb["word_embeddings"]["embedding"][input_ids]
    x = x + emb["position_embeddings"]["embedding"][jnp.arange(L)][None]
    x = rnd(_ln(x, emb["ln"], eps))
    bias = ((1.0 - attention_mask.astype(jnp.float32)) * NEG_INF)[:, None, None, :]
    D = x.shape[-1]
    d = D // n_heads
    split = lambda t: t.reshape(B, L, n_heads, d).transpose(0, 2, 1, 3)  # noqa: E731
    for i in range(n_layers):
        lp = enc[f"layer_{i}"]
        a = lp["attn"]
        q, k, v = (split(rnd(_dense(x, a[n]))) for n in "qkv")
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(d)) + bias
        w = rnd(jax.nn.softmax(s, axis=-1))
        ctx = jnp.einsum("bhqk,bhkd->bhqd", w, v).transpose(0, 2, 1, 3).reshape(B, L, D)
        x = rnd(_ln(x + _dense(rnd(ctx), a["o"]), lp["sa_ln"], eps))
        h = rnd(jax.nn.gelu(_dense(x, lp["lin1"]), approximate=(gelu == "tanh")))
        x = rnd(_ln(x + _dense(h, lp["lin2"]), lp["out_ln"], eps))
    return x, _dense(x[:, 0, :], params["classifier"])


def forward(params, input_ids, attention_mask, model: dict, rnd=lambda a: a):
    """Float32 last hidden states ``[B, L, dim]`` and logits
    ``[B, n_classes]`` of the configuration ``model`` (the ``model`` object
    of a ``benchmark/configs/<config>.json``)."""
    fn = jax.jit(
        lambda p, i, m: _forward(
            p, i, m,
            n_layers=model["n_layers"], n_heads=model["n_heads"],
            eps=model["layer_norm_eps"], gelu=model["gelu"], rnd=rnd,
        )
    )
    with jax.default_matmul_precision("highest"):
        return fn(params, jnp.asarray(input_ids), jnp.asarray(attention_mask))
