"""setup_s: start of the process to the end of the warm-up build: JAX and
the program loaded, the point set made, every program compiled or loaded
from the persistent cache."""


def read(run):
    return run.setup_s
