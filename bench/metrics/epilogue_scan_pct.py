"""epilogue_scan_pct: the share of the bitmask epilogue's (slot, chunk)
pairs its kernel scanned, 100 x scanned / the pairs a full scan of every
row block takes (``RunStats.epilogue_scan_pct``: over every
``bits_to_cols`` call of the build's engine call, all ranks), mean over
the window's builds. Nothing where the program does not count it."""


def read(run):
    pct = [getattr(s, "epilogue_scan_pct", None) for s in run.stats]
    if not pct or None in pct:
        return None
    return sum(pct) / len(pct)
