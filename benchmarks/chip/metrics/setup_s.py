"""Process start to the first due request: JAX start-up, grid and broker
from the seed, the snapshot, the kernel compiles and the warm-up flush."""


def read(run):
    return float(run.setup_s)
