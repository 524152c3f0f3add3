"""What is of the chip and not of a model: the table of published peaks and
the roofline's floor. The operations and bytes a step of one architecture
requires are its family's (``benchmark/families/<family>.py``).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"benchmark/peaks.json has no entry for device kind {device_kind!r}: "
            "add one with its source before reporting a utilisation"
        )
    return table[device_kind]


def roofline_floor_s(flops: float, nbytes: float, peaks: dict, chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` chips could take, and which roof sets it."""
    t_c = flops / (peaks["bf16_flops_per_s"] * chips)
    t_m = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
