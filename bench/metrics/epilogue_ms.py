"""epilogue_ms: summed device time of the bitmask epilogue's operations
(``bits_to_cols``) in the traced window, per chip and per build."""
from bench.kernels import is_epilogue, per_build_ms


def read(run):
    return per_build_ms(run, is_epilogue)
