"""Windows of consecutive flows: a long-context model's rows.

``data/textualize.py`` renders one flow to one sentence and the reference
classifies each alone (a row of at most 128 tokens). A detector with context
reads a WINDOW: ``k`` consecutive flows of one source (one site's capture, in
the order the split holds them) joined into one document and tokenised once,
labelled by whether the window holds an attack flow. Everything downstream
(``tokenize_client``, ``TokenizedSplit``, the trainers) takes the windows as
it takes single flows: only the texts are longer.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .cicids import ClientSplits, SplitArrays


def render_windows(
    texts: Sequence[str], labels: np.ndarray, bounds: Sequence[tuple[int, int]]
) -> tuple[list[str], np.ndarray]:
    """One document per ``(start, stop)`` of ``bounds``: the flow sentences
    ``texts[start:stop]`` joined by a space, labelled 1 if any of them is an
    attack."""
    docs = [" ".join(texts[a:b]) for a, b in bounds]
    held = np.array([int(np.any(labels[a:b] != 0)) for a, b in bounds], np.int32)
    return docs, held


def window_split(split: SplitArrays, flows: int) -> SplitArrays:
    """Consecutive, non-overlapping windows of ``flows`` flows (a shorter
    tail is dropped; a split with fewer flows than one window gives one
    window of all it has)."""
    n = len(split)
    bounds = [(a, a + flows) for a in range(0, n - flows + 1, flows)] or [(0, n)]
    return SplitArrays(*render_windows(split.texts, np.asarray(split.labels), bounds))


def window_client(splits: ClientSplits, flows: int) -> ClientSplits:
    return ClientSplits(
        splits.client_id,
        window_split(splits.train, flows),
        window_split(splits.val, flows),
        window_split(splits.test, flows),
    )
