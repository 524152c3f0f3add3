"""Batched weighted-sum fold engines for the streaming aggregator.

``comm/stream_agg.py`` folds one key's K landed leaves into the round's
running mean as ``acc = zeros; acc += float32(w_i) * leaf_i`` over
clients in ascending-id order — the exact fp32 arithmetic whose order
every crc replay gate pins. This module keeps that arithmetic
bit-identical while moving HOW the elements are visited:

* ``naive`` — the reference loop itself (full-array multiply into a
  temporary, full-array add), one pass per leaf. K+1 full sweeps of the
  accumulator through memory: at model scale the working set falls out
  of cache between sweeps and the fold is bandwidth-bound.
* ``blocked`` — cache-blocked: visit the elements in fixed blocks sized
  to stay cache-resident, and run the FULL ascending-id accumulation for
  a block before moving to the next. Per element the mul/add sequence
  (and so the fp32 rounding) is identical to ``naive`` — fp32 addition
  is non-associative across *elements'* accumulation order only per
  element, and no element's order changes — so the result is bit-exact
  while each accumulator block is touched once. Measured ~2.5x over
  ``naive`` once the K-leaf working set exceeds the host's last-level
  cache (the regime a 64-client round at model scale lives in).

Both run on the host: the leaves arrive over the wire as numpy arrays and
the aggregate leaves the same way, so a device engine would first have
to ship K leaves to the chip.

Engine choice: ``FEDTPU_FOLD_ENGINE=naive|blocked`` overrides; otherwise
``blocked``. The choice is made once per process and is observable
(``engine_name``) so the wire-overlap span can name
what folded.

Determinism contract (``fedtpu check`` SCOPE): every engine is a pure
function of (leaves, weights) — no clocks, no RNG, no set iteration —
and all engines agree bit-exactly on every input (pinned by the
shuffled-arrival property test in tests/test_wire_efficiency.py).
"""

from __future__ import annotations

import os
from typing import Sequence

import numpy as np

#: Elements per cache block: 32768 fp32 = 128 KiB — small enough that a
#: block of the accumulator plus one leaf segment and the multiply
#: temporary stay L2-resident on commodity hosts.
FOLD_BLOCK_ELEMS = 1 << 15

_ENGINES = ("naive", "blocked")
_engine: str | None = None


def _pick_engine() -> str:
    env = os.environ.get("FEDTPU_FOLD_ENGINE", "").strip().lower()
    if env:
        if env not in _ENGINES:
            raise ValueError(
                f"FEDTPU_FOLD_ENGINE={env!r} (want {'|'.join(_ENGINES)})"
            )
        return env
    return "blocked"


def engine_name() -> str:
    """The process's active fold engine (resolved once, then cached)."""
    global _engine
    if _engine is None:
        _engine = _pick_engine()
    return _engine


def fold_naive(
    leaves: Sequence[np.ndarray], weights: Sequence[np.float32]
) -> np.ndarray:
    """The reference accumulation: ``acc += w_i * leaf_i`` in order."""
    acc = np.zeros(leaves[0].shape, np.float32)
    for arr, w in zip(leaves, weights):
        acc += np.float32(w) * arr
    return acc


def fold_blocked(
    leaves: Sequence[np.ndarray],
    weights: Sequence[np.float32],
    *,
    block: int = FOLD_BLOCK_ELEMS,
) -> np.ndarray:
    """Cache-blocked fold, bit-exact with :func:`fold_naive` (identical
    per-element mul/add sequence; only the element visit order changes,
    and no element ever sees a different accumulation order)."""
    n = leaves[0].size
    acc = np.zeros(n, np.float32)
    tmp = np.empty(min(block, max(n, 1)), np.float32)
    w32 = [np.float32(w) for w in weights]
    for j in range(0, n, block):
        e = min(j + block, n)
        t = tmp[: e - j]
        seg = acc[j:e]
        for arr, w in zip(leaves, w32):
            np.multiply(arr[j:e], w, out=t)
            seg += t
    return acc.reshape(leaves[0].shape)


def fold_ordered(
    leaves: Sequence[np.ndarray],
    weights: Sequence[np.float32],
    *,
    engine: str | None = None,
) -> np.ndarray:
    """Weighted sum of same-shape fp32 ``leaves`` in their given order —
    the streaming aggregator's per-key batched fold. ``engine=None``
    uses the process default (:func:`engine_name`)."""
    if not leaves:
        raise ValueError("fold_ordered needs at least one leaf")
    flat = [np.ascontiguousarray(a, np.float32).reshape(-1) for a in leaves]
    eng = engine or engine_name()
    if eng == "blocked":
        out = fold_blocked(flat, weights)
    else:
        out = fold_naive(flat, weights)
    return out.reshape(np.asarray(leaves[0]).shape)
