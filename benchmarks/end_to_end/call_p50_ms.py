"""Median wall time of one public call in the window."""

import statistics


def compute(run):
    return statistics.median(run["samples_ms"])
