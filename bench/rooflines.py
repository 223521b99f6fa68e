"""Least bytes each measured program must move, from the configuration's
shapes. The sketch's counters are int32 and it does no arithmetic worth
counting against a FLOP peak, so the HBM bytes bound is the roofline:
a program's share is ``bytes / hbm_bytes_per_s`` over its measured device
time. Each count leaves out what the program could avoid, so a share can
only be understated.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

I32 = 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for this device; a device not in it is an
    error, not a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def walk_bytes(sk, block_a: np.ndarray, block_b: np.ndarray) -> int:
    """One launch of the insert key walk on one batch: the key plane of
    every storage block ``(m_a, m_b)`` the batch touches, read and written
    once, plus the batch's probe arrays (``s`` rows, cols and keys per
    edge, and its weight) read once. ``sk``: ``reference.Sketch``."""
    tiles = len(np.unique(block_a * sk.n_blocks + block_b))
    tile = 2 * sk.b * sk.b * I32
    probes = len(block_a) * (3 * sk.s + 1) * I32
    return 2 * tiles * tile + probes
