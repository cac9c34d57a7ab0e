"""Programs JAX compiled, or loaded from its persistent cache, between the
start and the end of the whole measured window (a ``jax.monitoring``
listener in ``run.py`` counts them). Expected 0. Layer: program cache."""


def reduce(events, run):
    return run.get("compiles_in_window")
