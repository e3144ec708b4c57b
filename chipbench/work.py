"""The work a step requires, counted from the physics and not from what
the program happens to move, and the chip's peaks to set it against.

The field-free push of an alive particle reads its position and velocity
and writes them once: 4 + 12 bytes each way in float32. Dead slots and the
grid tables (a few hundred kB) are not counted, so a program that stops
touching dead slots cannot push a share past 100%.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
PUSH_BYTES_PER_PARTICLE = 2 * (4 + 3 * 4)


def chip_peaks(device_kind: str) -> dict:
    """Peaks of a chip by its JAX ``device_kind``; an unknown kind is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def push_bytes(alive: int) -> int:
    """Bytes the push of ``alive`` particles has to move."""
    return PUSH_BYTES_PER_PARTICLE * int(alive)


def push_floor_s(alive: int, peaks: dict) -> float:
    """Least time the chip needs for that push: it is bound by bandwidth
    (one add and one multiply per 32 bytes is far below the compute
    roof)."""
    return push_bytes(alive) / peaks["hbm_bytes_per_s"]
