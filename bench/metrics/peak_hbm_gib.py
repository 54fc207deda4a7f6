"""peak_hbm_gib: the highest ``peak_bytes_in_use`` over the cell's chips
after the window, in GiB."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes else None
