"""``ht.program.launch`` and ``ht.program.compile`` spans inside
``bench.call``, mean a call: the programs heat_tpu's builders launched. A
count that repeats exactly. Layer: program cache."""

from benchmarks import spans as S


def reduce(events, run):
    return S.launches_per_call(events)
