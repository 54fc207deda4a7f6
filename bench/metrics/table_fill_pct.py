"""table_fill_pct: the share of the neighbour tables' slots copied to the
host that hold a pair, 100 x sum ``RunStats.pairs_selected`` / sum
``RunStats.table_slots`` over the window's builds."""


def read(run):
    slots = sum(getattr(s, "table_slots", 0) for s in run.stats)
    if not slots:
        return None
    return 100.0 * sum(s.pairs_selected for s in run.stats) / slots
