"""The plain reference: the Laguna decoder and the paper's head in
straightforward float32 ``jax.numpy``: forward, loss and gradients.

Follows the published ``config.json`` of poolside/Laguna-XS.2 (``model_type:
laguna``): pre-norm RMSNorm blocks ``h = x + Attn(RMSNorm(x)); y = h +
FFN(RMSNorm(h))``, a final RMSNorm.

- *Attention* of layer ``l`` with ``H_l`` query heads (48 where full, 64 where
  sliding) over 8 key/value heads of 128: ``q = n Wq``, ``k = n Wk``, ``v = n
  Wv``, no bias; ``q`` and ``k`` rotated over their first ``r_l * 128``
  dimensions in pairs ``(x_i, x_{i + rot/2})`` (rotate-half) by ``position *
  inv_freq_i``: sliding layers every dimension with ``inv_freq_i =
  10000^(-2i/128)``; full layers the first 64 at theta 500,000 under YaRN
  (factor 64 over 4,096 original positions, ``beta_fast`` 64, ``beta_slow``
  1: a pair that turns more than ``beta_fast`` times in 4,096 positions keeps
  ``theta^(-2i/64)``, one that turns fewer than ``beta_slow`` times has it
  divided by 64, a linear blend by index between), cos and sin times the
  ``attention_factor`` 1.41589; query head ``h`` reads key head ``h // (H_l /
  8)``; scores ``q k^T / sqrt(128)`` over the real keys ``j <= i`` and, in a
  sliding layer, ``i - j < 512``; softmax; ``o = P v``; ``g = sigmoid(n Wg)``
  as ``[T, H_l]`` multiplies head ``h`` of ``o``; ``Wo``. Token by token in its
  masks: a dense ``[L, L]`` mask, HEAD BY HEAD (``lax.map``, each head
  checkpointed, so that 8,192 tokens fit), every key scored.
- *FFN*: SwiGLU; in a sparse layer ``Shared(x) + sum over the chosen experts
  THAT THIS SHARE HOLDS of w_e Expert_e(x)``: ``s = sigmoid(n Wr)`` over all
  the experts, the top k by ``s``, ``w = s_chosen / sum(s_chosen) * scale``.
  Every held expert is applied to every token and weighted (zero where not
  chosen): no buffer, no capacity, nothing dropped.
- Head: the hidden state of each row's last real token -> Linear.
- ``forced``: a choice of experts to compute under, as
  ``kimi_linear_fp32`` takes it (a top-k is a discrete decision; continuous
  numbers are compared under ONE choice, and the reference's own choice is
  handed back beside them, to be compared as a choice).

Three readings are inferences from ``config.json`` alone (the published
modelling code is not on this machine), each one line here and in
``models/laguna.py``, listed under ``assumed`` in
benchmark/configs/laguna-xs2-ep8.json: (a) ``gating: true`` is the per-head
sigmoid gate above (:func:`_attention`'s ``gate``); (b) the router is a
sigmoid with the top 8 renormalised and no selection bias (:func:`route`); (c)
the activation is SiLU and neither queries nor keys are normalised.

Departures from the published model, because the program under test makes the
same ones (the configuration's ``departures``): no LM head; only the held
experts' part.

Each layer is checkpointed (the backward pass of one window then holds one
layer's intermediates), which changes no number. On a TPU a float32 matmul
runs at reduced precision unless ``default_matmul_precision("highest")`` is
set, so every entry point sets it. Reads the parameter tree by the names
``models/laguna.py`` gives its leaves; nothing of the program is imported.
Rows are processed one window at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .kimi_linear_fp32 import adam_first_step  # noqa: F401  (the optimizer's first step is no architecture's)

NEG_INF = -1e9


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _softmax(s):
    """``exp(s - max) / sum`` over the last axis. The barrier changes no
    number: it keeps the TPU compiler from fusing a row's maximum and its
    broadcast into one windowed reduction as wide as the row, which costs the
    square of the row's length (14 s a window of 8,192 tokens instead of
    about 1; my chip runs, PR 32)."""
    top = jax.lax.optimization_barrier(jax.lax.stop_gradient(s.max(-1, keepdims=True)))
    e = jnp.exp(s - top)
    return e / e.sum(-1, keepdims=True)


def inv_frequencies(rot: int, rope: dict) -> np.ndarray:
    """The ``rot / 2`` angular frequencies of a layer kind's ``rope`` (its
    ``theta`` and, for YaRN, ``factor``, ``original_len``, ``beta_fast``,
    ``beta_slow``), from the closed form, float64."""
    i = np.arange(rot // 2, dtype=np.float64)
    plain = rope["theta"] ** (-2.0 * i / rot)
    factor = rope.get("factor", 1.0)
    if factor == 1.0:
        return plain
    # the index of the pair that turns ``turns`` times over the original length
    at = lambda turns: rot * math.log(rope["original_len"] / (turns * 2 * math.pi)) / (2 * math.log(rope["theta"]))  # noqa: E731
    low, high = max(math.floor(at(rope["beta_fast"])), 0), min(math.ceil(at(rope["beta_slow"])), rot - 1)
    blend = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)  # 0: as trained, 1: divided by the factor
    return plain * (1.0 - blend) + plain / factor * blend


def rotary_tables(length: int, rot: int, rope: dict):
    """``(cos, sin)`` ``[length, rot / 2]`` float32, times the attention factor."""
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_frequencies(rot, rope)[None, :]
    scale = rope.get("attention_factor", 1.0)
    return jnp.asarray(np.cos(angle) * scale, jnp.float32), jnp.asarray(np.sin(angle) * scale, jnp.float32)


def _rotate(x, cos, sin):
    """``x`` ``[L, H, d]``: the first ``2 * cos.shape[-1]`` dimensions
    rotated, the rest as they are."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def layer_rope(m: dict, kind: str) -> tuple[int, dict]:
    """(rotated dimensions, rope parameters) of the layer kind, from the
    model's flat keys."""
    if kind == "sliding":
        return int(m["head_dim"] * m["sliding_rotary_share"]), {"theta": m["sliding_rope_theta"]}
    return int(m["head_dim"] * m["full_rotary_share"]), {
        "theta": m["full_rope_theta"], "factor": m["full_rope_factor"],
        "original_len": m["full_rope_original_len"], "beta_fast": m["full_rope_beta_fast"],
        "beta_slow": m["full_rope_beta_slow"], "attention_factor": m["full_rope_attention_factor"],
    }


def _attention(x, mask, p, m, layer, rnd):
    L = x.shape[0]
    kind, H = m["layer_types"][layer], m["heads_per_layer"][layer]
    Hkv, d = m["n_kv_heads"], m["head_dim"]
    q = rnd(x @ p["q_proj"]["kernel"]).reshape(L, H, d)
    k = rnd(x @ p["k_proj"]["kernel"]).reshape(L, Hkv, d)
    v = rnd(x @ p["v_proj"]["kernel"]).reshape(L, Hkv, d)
    gate = jax.nn.sigmoid(x @ p["g_proj"]["kernel"])  # [L, H]: (a) one gate a head
    cos, sin = rotary_tables(L, *layer_rope(m, kind))
    q, k = rnd(_rotate(q, cos, sin)), rnd(_rotate(k, cos, sin))
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = (j <= i) & (mask[None, :] > 0)
    if kind == "sliding":
        allowed = allowed & (i - j < m["sliding_window"])
    bias = jnp.where(allowed, 0.0, NEG_INF)
    group = H // Hkv

    @jax.checkpoint
    def head(args):
        q_h, h = args
        k_h, v_h = k[:, h // group], v[:, h // group]
        s = q_h @ k_h.T / jnp.sqrt(jnp.float32(d)) + bias
        return rnd(_softmax(s)) @ v_h

    o = jax.lax.map(head, (q.transpose(1, 0, 2), jnp.arange(H)))  # [H, L, d]
    o = o.transpose(1, 0, 2) * gate[..., None]
    return rnd(o.reshape(L, H * d)) @ p["o_proj"]["kernel"]


def _swiglu(x, p, rnd):
    h = rnd(jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"]))  # (c) SiLU
    return h @ p["down_proj"]["kernel"]


def route(x, p, m, forced=None):
    """The router's choice for tokens ``x`` ``[L, D]``: ``(idx [L, k], w [L,
    k])``, over ALL the layer's experts; with ``forced`` ``[L, k]`` the
    weights of THOSE experts (the choice is given, the scores are the
    reference's own). (b): sigmoid scores, the top k renormalised, no bias."""
    s = jax.nn.sigmoid(x @ p["router"])
    idx = jax.lax.top_k(s, m["experts_per_token"])[1] if forced is None else forced
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True) * m["routed_scale"]


def _moe(x, p, m, rnd, forced=None):
    """The layer's result and the router's OWN choice; with ``forced`` the
    result is computed under that choice instead."""
    own = route(x, p, m)
    idx, w = own if forced is None else route(x, p, m, forced)

    @jax.checkpoint
    def expert(args):  # one of this share's experts on every token, weighted
        e, w_gate, w_up, w_down = args
        w_e = jnp.where(idx == m["expert_offset"] + e, w, 0.0).sum(-1)
        h = rnd(jax.nn.silu(x @ w_gate) * (x @ w_up))
        return w_e[:, None] * (h @ w_down)

    held = jnp.arange(m["experts_held"])
    y, _ = jax.lax.scan(
        lambda y, args: (y + expert(args), None), _swiglu(x, p["shared"], rnd),
        (held, p["experts_gate"], p["experts_up"], p["experts_down"]),
    )
    return y, own


def _layer(m, i, rnd):
    """Layer ``i`` as ``(x, mask, its parameters, a forced choice or None) ->
    (x, the router's own choice or None)``."""
    eps = m["rms_norm_eps"]

    @jax.checkpoint
    def layer(x, mask, lp, choice):
        h = rnd(_rms(x, lp["attn_norm"]["scale"], eps))
        x = rnd(x + _attention(h, mask, lp["attn"], m, i, rnd))
        h = rnd(_rms(x, lp["ffn_norm"]["scale"], eps))
        if m["ffn_types"][i] == "dense":
            return rnd(x + _swiglu(h, lp["ffn"], rnd)), None
        y, chosen = _moe(h, lp["moe"], m, rnd, choice)
        return rnd(x + y), chosen

    return layer


def _window(params, ids, mask, m, rnd, forced=None):
    """One row: last hidden states ``[L, D]``, logits ``[n_classes]`` and
    every expert layer's own routing ``[(idx [L, k], w [L, k]), ...]``;
    ``forced``: a choice of experts ``[L, k]`` for every expert layer, to
    compute under."""
    enc = params["encoder"]
    eps = m["rms_norm_eps"]
    x = enc["word_embeddings"]["embedding"][ids]
    routes = []
    for i, ffn in enumerate(m["ffn_types"]):
        choice = forced[len(routes)] if forced is not None and ffn == "sparse" else None
        x, chosen = _layer(m, i, rnd)(x, mask, enc[f"layer_{i}"], choice)
        if ffn == "sparse":
            routes.append(chosen)
    x = rnd(_rms(x, enc["final_norm"]["scale"], eps))
    last = jnp.maximum(mask.sum() - 1, 0)
    head = params["classifier"]
    return x, x[last] @ head["kernel"] + head["bias"], routes


def _f32(params, rnd):
    return jax.tree.map(lambda a: rnd(jnp.asarray(a, jnp.float32)), params)


def _same(a):
    return a


#: (entry point, the model's items, the rounding) -> its jitted function, so
#: that a second call with the same configuration (the comparison runs on two
#: sets of weights) compiles nothing again.
_JITTED: dict = {}


def _jitted(kind: str, model: dict, rnd, make):
    key = (kind, repr(sorted(model.items())), rnd)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(make())
    return _JITTED[key]


def _rows(input_ids, attention_mask, forced):
    """Per row: ``(ids, mask, that row's forced choices or None)``."""
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask)
    per_row = [None] * len(ids) if forced is None else zip(*(jnp.asarray(layer) for layer in forced))
    return [(i, a, None if f is None else list(f)) for i, a, f in zip(ids, mask, per_row)]


def forward(params, input_ids, attention_mask, model: dict, rnd=_same, forced=None):
    """Float32 last hidden states ``[B, L, dim]`` and logits ``[B,
    n_classes]`` of the configuration ``model`` (the ``model`` object of a
    ``benchmark/configs/<config>.json``), one window at a time. ``rnd``
    rounds every weight and every sub-layer's output
    (tools/window_probe.py's lower precision). ``forced``: for every expert
    layer a choice of experts ``[B, L, k]`` to compute under."""
    fn = _jitted(
        f"forward {forced is not None}", model, rnd,
        lambda: lambda p, i, a, f: _window(_f32(p, rnd), i, a, model, rnd, f)[:2],
    )
    hidden, logits = [], []
    with jax.default_matmul_precision("highest"):
        for i, a, f in _rows(input_ids, attention_mask, forced):
            h, z = fn(params, i, a, f)
            hidden.append(h)
            logits.append(z)
    return jnp.stack(hidden), jnp.stack(logits)


def _row_loss(params, ids, mask, label, m, rnd, forced=None):
    _, z, routes = _window(_f32(params, rnd), ids, mask, m, rnd, forced)
    return jax.nn.logsumexp(z) - z[label], routes


def loss_and_grads(params, input_ids, attention_mask, labels, model: dict, rnd=_same, forced=None):
    """The mean cross-entropy over the rows, its gradient with respect to
    every parameter (a tree like ``params``), float32, row by row, and the
    router's own choices on the way, per expert layer ``[(idx [B, L, k], w
    [B, L, k]), ...]``. ``rnd`` and ``forced`` as in :func:`forward` (the
    gradient passes through a rounding as through the identity)."""
    fn = _jitted(
        f"loss_and_grads {forced is not None}", model, rnd,
        lambda: jax.value_and_grad(lambda p, i, a, y, f: _row_loss(p, i, a, y, model, rnd, f), has_aux=True),
    )
    n = len(input_ids)
    total, grads, rows = 0.0, None, []
    with jax.default_matmul_precision("highest"):
        for (i, a, f), y in zip(_rows(input_ids, attention_mask, forced), jnp.asarray(labels)):
            (value, routes), g = fn(params, i, a, y, f)
            total += float(value)
            rows.append(routes)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    routes = [tuple(jnp.stack(part) for part in zip(*layer)) for layer in zip(*rows)]
    return total / n, jax.tree.map(lambda g: g / n, grads), routes
