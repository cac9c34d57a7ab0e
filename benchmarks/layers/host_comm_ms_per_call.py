"""Self time of the ``ht.comm.*`` spans (``place``, ``shard``, ``reshard``)
inside ``bench.call`` that are under no program span, mean a call.
Layer: communication."""

from benchmarks import spans as S


def reduce(events, run):
    shares = S.mean_shares_ns(events)
    return None if shares is None else shares[S.COMMS] / 1e6
