"""Rotary positions: the default kind and YaRN, over all or part of a head.

A head's first ``rot`` dimensions are rotated in pairs ``(x_i, x_{i + rot/2})``
(the rotate-half convention) by the angle ``position * inv_freq_i``; the rest
pass through. The default kind has ``inv_freq_i = theta^(-2i / rot)``. YaRN
(Peng et al. 2023, arXiv:2309.00071) stretches a model trained on
``original_len`` positions by ``factor``: a pair that turns more than
``beta_fast`` times over the original length keeps its frequency, one that
turns fewer than ``beta_slow`` times has it divided by ``factor``, and the
pairs between are blended linearly by their index; cos and sin are then
multiplied by ``attention_factor`` (published with the model, or ``0.1
ln(factor) + 1``), which scales the attention logits by its square.

The tables depend on shapes and on the configuration's constants only: they
are computed once per shape on the host in float64, kept in float32, and enter
a program as constants.
"""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np


def inv_frequencies(
    rot: int, theta: float, *, factor: float = 1.0, original_len: int = 0,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> np.ndarray:
    """The ``rot / 2`` angular frequencies (float64). ``factor`` 1: the
    default kind; otherwise YaRN's blend of ``theta^(-2i/rot)`` and the same
    divided by ``factor``."""
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if factor == 1.0:
        return plain

    def pair_turning(turns: float) -> float:
        """The (fractional) index of the pair that turns ``turns`` times over
        the original length."""
        return rot * math.log(original_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


@functools.lru_cache(maxsize=16)  # a few shapes a configuration; 2 MB a table at 8,192 positions
def rope_tables(
    length: int, rot: int, theta: float, factor: float = 1.0, original_len: int = 0,
    beta_fast: float = 32.0, beta_slow: float = 1.0, attention_factor: float = 1.0,
) -> tuple[np.ndarray, np.ndarray]:
    """``(cos, sin)``, each ``[length, rot / 2]`` float32, for positions
    ``0 .. length - 1``, times ``attention_factor``."""
    inv = inv_frequencies(
        rot, theta, factor=factor, original_len=original_len, beta_fast=beta_fast, beta_slow=beta_slow
    )
    angle = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    return (
        (np.cos(angle) * attention_factor).astype(np.float32),
        (np.sin(angle) * attention_factor).astype(np.float32),
    )


def apply_rope(x, cos, sin):
    """``x``: ``[B, L, H, d]``; ``cos``, ``sin``: ``[L, rot / 2]``. The first
    ``rot`` of the ``d`` dimensions rotated in float32, the result in ``x``'s
    type."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half : 2 * half], x32[..., 2 * half :]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1).astype(x.dtype)
