"""graph_s: wall clock of every build_nng call in the window, summed, over
the number of calls (host points in, CSR on the host out)."""


def read(run):
    if not run.build_s:
        return None
    return sum(run.build_s) / len(run.build_s)
