"""Host time under ``ht.program.*`` spans inside ``bench.call`` (cache
lookups, and the jitted call from argument handling to enqueue), mean a
call. Layer: program cache."""

from benchmarks import spans as S


def reduce(events, run):
    shares = S.mean_shares_ns(events)
    return None if shares is None else shares[S.LAUNCH] / 1e6
