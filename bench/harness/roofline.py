"""The chip's peaks and the bytes the estimator must move.

Peaks are one table keyed by ``device_kind`` (``peaks.json``, with its
source); a device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["peak", "estimator_bytes", "STATE_BYTES_PER_END"]

_PEAKS = Path(__file__).with_name("peaks.json")

# Algorithm-1 state per end, float32/int32 words: the window (32), the
# q-bar and response histories (16 + 16), the last two sigmas (2), and
# 8 scalars (fill, count, mean, M2, epoch, estimate, periods, blocked)
STATE_BYTES_PER_END = 4 * (32 + 16 + 16 + 2 + 8)


def peak(device_kind: str, what: str) -> float:
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][what])


def estimator_bytes(ends: int, periods: int) -> float:
    """Bytes one dispatch must move whatever form computes it: the
    counts (float32) and blocked flags (1 byte) read once, and the state
    read and written once."""
    return ends * periods * (4 + 1) + 2 * ends * STATE_BYTES_PER_END
