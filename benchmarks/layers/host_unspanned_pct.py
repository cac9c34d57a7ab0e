"""Share of the ``bench.call`` wall under no ``ht.*`` span (the op file's
own call, the interpreter between spans). With ``host_wrapper``,
``host_launch`` and ``host_comm_ms_per_call`` it partitions that wall.
Layer: dispatch."""

from benchmarks import spans as S


def reduce(events, run):
    shares = S.mean_shares_ns(events)
    if shares is None or not shares["wall"]:
        return None
    return 100.0 * shares[S.UNSPANNED] / shares["wall"]
