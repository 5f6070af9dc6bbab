"""Peak bytes in use on the fullest of the cell's chips, read from the JAX
runtime's allocator when the window ends: the process's peak so far, fill
and warm-up included."""


def read(run):
    return run.extras["peak_bytes"] / 2 ** 30
