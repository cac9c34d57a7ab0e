"""The 95th percentile of the calls' wall times (nearest rank), or, where
the window holds fewer than 200 calls, the highest percentile that still
has ten samples beyond it; the median where that would lie under it (fewer
than 21 samples)."""

import math
import statistics


def tail_rank(n: int) -> int:
    """0-based index into the sorted samples."""
    return min(math.ceil(0.95 * n) - 1, n - 11)


def compute(run):
    samples = sorted(run["samples_ms"])
    rank = tail_rank(len(samples))
    return samples[rank] if rank >= len(samples) // 2 else statistics.median(samples)
