"""Seeded synthetic CICIDS2017 flows and the sentences they are rendered to.

Traffic generation belongs to the yardstick, so this is the benchmark's own
copy of the two pieces of the program that make a flow sentence: the
separable BENIGN/DDoS generator (``data/synthetic.py::make_synthetic_flows``,
the ten rendered columns only, no inf/NaN sprinkle, so that no request is
malformed) and the reference's English template
(``data/textualize.py``, byte-identical to client1.py:68-81). ``selftest``
checks that the template here still renders what the program's does.
Numpy only: the load generator's child process imports this file.
"""

from __future__ import annotations

import numpy as np

#: (prefix, column, suffix); the last fragment ends the sentence.
TEMPLATE = (
    ("Destination port is ", "Destination Port", ". "),
    ("Flow duration is ", "Flow Duration", " microseconds. "),
    ("Total forward packets are ", "Total Fwd Packets", ". "),
    ("Total backward packets are ", "Total Backward Packets", ". "),
    ("Total length of forward packets is ", "Total Length of Fwd Packets", " bytes. "),
    ("Total length of backward packets is ", "Total Length of Bwd Packets", " bytes. "),
    ("Maximum forward packet length is ", "Fwd Packet Length Max", ". "),
    ("Minimum forward packet length is ", "Fwd Packet Length Min", ". "),
    ("Flow bytes per second is ", "Flow Bytes/s", ". "),
    ("Flow packets per second is ", "Flow Packets/s", "."),
)

# column -> (benign sampler, ddos sampler); each takes (rng, n).
_INT = lambda lo, hi: (lambda rng, n: rng.integers(lo, hi, size=n))  # noqa: E731
_CHOICE = lambda xs: (lambda rng, n: rng.choice(xs, size=n))  # noqa: E731
_UNI = lambda lo, hi: (lambda rng, n: np.round(rng.uniform(lo, hi, size=n), 4))  # noqa: E731
_COLUMNS = (
    ("Destination Port", _CHOICE([53, 443, 8080, 22, 3389]), _CHOICE([80, 443])),
    ("Flow Duration", _INT(1_000, 10_000_000), _INT(1, 5_000)),
    ("Total Fwd Packets", _INT(1, 30), _INT(100, 2_000)),
    ("Total Backward Packets", _INT(1, 30), _INT(0, 3)),
    ("Total Length of Fwd Packets", _INT(0, 5_000), _INT(50_000, 500_000)),
    ("Total Length of Bwd Packets", _INT(0, 5_000), _INT(0, 200)),
    ("Fwd Packet Length Max", _INT(0, 1_500), _INT(1_000, 1_500)),
    ("Fwd Packet Length Min", _INT(0, 100), _INT(500, 1_000)),
    ("Flow Bytes/s", _UNI(10, 1e5), _UNI(1e6, 5e7)),
    ("Flow Packets/s", _UNI(0.1, 1e3), _UNI(1e4, 1e6)),
)


def make_flows(n: int, seed: int, ddos_fraction: float = 0.5) -> tuple[list[str], np.ndarray]:
    """``n`` distinct seeded flows as (sentences, labels), shuffled so the
    classes do not come in blocks. The same seed gives the same flows."""
    rng = np.random.default_rng(seed)
    n_ddos = int(n * ddos_fraction)
    n_benign = n - n_ddos
    cols = {
        name: np.concatenate([benign(rng, n_benign), ddos(rng, n_ddos)])
        for name, benign, ddos in _COLUMNS
    }
    labels = np.concatenate([np.zeros(n_benign, np.int32), np.ones(n_ddos, np.int32)])
    perm = rng.permutation(n)
    parts = [
        [f"{prefix}{v}{suffix}" for v in cols[col][perm].tolist()]
        for prefix, col, suffix in TEMPLATE
    ]
    return ["".join(row) for row in zip(*parts)], labels[perm]
