"""Process start to the start of the window, less the TPU runtime's own
start (the first ``jax.devices()``): interpreter and imports, data made on
the device, the plain reference, the warm-up call (compile or cache load)
and its check."""


def compute(run):
    return run["setup_s"]
