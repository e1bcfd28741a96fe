"""Set-up seconds: process start to the window's open (loading JAX,
weights from the seed, executables compiled or read from the cache, and
the warm-up batches)."""


def read(run):
    return run.setup_s
