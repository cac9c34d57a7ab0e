"""Self time of the ``ht.call.*`` and ``ht.op.*`` spans inside ``bench.call``
(the public wrappers: checks, casts, sketch-size arithmetic, ``DNDarray``
constructions), mean a call. Time under a program or a communication span
is theirs. Layer: dispatch."""

from benchmarks import spans as S


def reduce(events, run):
    shares = S.mean_shares_ns(events)
    return None if shares is None else shares[S.WRAPPER] / 1e6
