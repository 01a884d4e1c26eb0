"""How a training throughput is read: all the items of the window over all of
its time, counted in blocks of whole steps.

A block is a fixed number of steps closed by a host read of the last step's
metrics and timed on the host clock from the previous block's close. The
window is a whole number of consecutive blocks, and the metric is the
quotient: the items of every block over the sum of their times, so a stall
anywhere in the window (a checkpoint, a collection, a loader hiccup, the
per-epoch reshuffle) moves it. The median of the blocks' rates and every
block's rate are printed beside it: they say whether a run that reads low
was slow in its first blocks (warm-up too short), in one block anywhere
(the host stalled once) or in all of them.
"""

from __future__ import annotations

import statistics
from typing import List, Sequence

# warm-up: at least this many whole blocks after the last compilation or
# cache load, then on until a block is no more than SETTLE faster than the
# one before it ("the block time has stopped falling"), at most MAX_BLOCKS
MIN_BLOCKS = 2
MAX_BLOCKS = 8
SETTLE = 0.005


def settled(times: Sequence[float], blocks_since_compile: int) -> bool:
    """True when warm-up may end after the block that closed last."""
    if blocks_since_compile < MIN_BLOCKS or len(times) < 2:
        return False
    if len(times) >= MAX_BLOCKS:
        return True
    return times[-1] >= times[-2] * (1.0 - SETTLE)


def block_rates(items_per_block: float, times: Sequence[float]) -> List[float]:
    return [items_per_block / t for t in times]


def window_rate(items_per_block: float, times: Sequence[float]) -> float:
    """The end-to-end metric: all the work over all the time."""
    if not times:
        raise ValueError("no block closed inside the window")
    return items_per_block * len(times) / sum(times)


def median_rate(items_per_block: float, times: Sequence[float]) -> float:
    """The explanation beside it: what most blocks ran at."""
    return statistics.median(block_rates(items_per_block, times))


def summary(items_per_block: float, unit: str, times: Sequence[float]) -> dict:
    """The line printed before the result, so that a run that reads low can
    be explained: first blocks slow, warm-up too short; one anywhere, host."""
    return {
        "blocks": len(times),
        "unit": unit,
        "window_rate": window_rate(items_per_block, times),
        "median_of_blocks": median_rate(items_per_block, times),
        "block_rates": block_rates(items_per_block, times),
        "window_s": sum(times),
    }
