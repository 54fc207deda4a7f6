"""ring_mib_per_build: MiB each chip sent on the ring per build
(``RunStats.ring_bytes``: the point engine's ``ppermute`` bytes per rank,
summed over the build's engine calls), mean over the window's builds.
Nothing where the program does not count it."""


def read(run):
    sent = [getattr(s, "ring_bytes", None) for s in run.stats]
    if not sent or None in sent:
        return None
    return sum(sent) / len(sent) / 2**20
