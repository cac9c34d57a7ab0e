"""Programs the first device ran a call (``XLA Modules`` events in the
traced window) less ``launches_per_call``: what eager ``jax.numpy`` code
launched outside any builder (a transpose, an ``astype``, a slice, a
``device_put`` that copies). Layer: dispatch."""

from benchmarks import spans as S


def reduce(events, run):
    modules, launches = S.modules_per_call(events), S.launches_per_call(events)
    if modules is None or launches is None:
        return None
    return modules - launches
