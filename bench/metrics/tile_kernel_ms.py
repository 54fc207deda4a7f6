"""tile_kernel_ms: summed device time of the fused distance tile kernel's
operations in the traced window, per chip and per build."""
from bench.kernels import is_tile, per_build_ms


def read(run):
    return per_build_ms(run, is_tile)
