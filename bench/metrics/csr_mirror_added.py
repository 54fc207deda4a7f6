"""csr_mirror_added: directed entries per build that the row-table CSR
path added to make the point engine's neighbour table symmetric
(``RunStats.csr_mirror_added``), mean over the window's builds. 0 where
every pair of the table has its mirror."""


def read(run):
    added = [getattr(s, "csr_mirror_added", None) for s in run.stats]
    if not added or None in added:
        return None
    return sum(added) / len(added)
