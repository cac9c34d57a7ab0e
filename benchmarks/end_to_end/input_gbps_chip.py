"""Bytes of input turned into results in the window (the op's
``work_bytes`` of every completed call) per second per chip. 1 GB = 1e9 B."""


def compute(run):
    return run["work_bytes"] / 1e9 / run["window_s"] / run["chips"]
