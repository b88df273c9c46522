"""The arithmetic of the end-to-end metrics and of the plain references' grouped
sums, apart from the harness so that tests can hold it to hand-computed
values."""
from __future__ import annotations

import math
from typing import Sequence


def percentile_nearest_rank(values: Sequence[float], p: float) -> float:
    """The p-th percentile by nearest rank: the smallest value with at least
    p% of the sample at or below it. No interpolation, so a stall that one
    query in twenty feels shows in p95 at full size."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rows_per_second(rows_scanned: Sequence[int], window_s: float) -> float:
    """Input rows scanned by all queries completed in the window over ALL of
    the window's seconds (the time a stalled query took counts)."""
    if window_s <= 0:
        raise ValueError("window of no length")
    return sum(rows_scanned) / window_s


#: rows to a block of the ``float32_blocked`` control
CONTROL_BLOCK_ROWS = 1 << 16


def grouped_float_sum(keys, values, n_groups: int, float_dtype="float64"):
    """sum(values) by small non-negative integer key, for a plain
    reference. ``"float64"`` is the reference. ``"float32"`` is the
    lower-precision control: every addend cast to float32 and accumulated
    in float32 in row order. ``"float32_blocked"`` is a second reading of
    it: the same within blocks of 65,536 rows, the blocks' partial sums
    added in float32 (what a float32 path on the device would do)."""
    import numpy as np

    if float_dtype == "float64":
        return np.bincount(keys, weights=values, minlength=n_groups)
    if float_dtype not in ("float32", "float32_blocked"):
        raise ValueError(f"no control in {float_dtype!r}")
    n = len(keys)
    block = CONTROL_BLOCK_ROWS if float_dtype.endswith("_blocked") else n
    values = values.astype(np.float32, copy=False)
    out = np.zeros(n_groups, np.float32)
    for start in range(0, n, max(block, 1)):
        part = np.zeros(n_groups, np.float32)
        np.add.at(part, keys[start:start + block],
                  values[start:start + block])
        out += part
    return out
