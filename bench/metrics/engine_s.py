"""engine_s: the engine's own clock, ``RunStats.elapsed_s`` (one
``block_until_ready`` engine call of ``drive``), mean over the window's
builds."""


def read(run):
    if not run.stats:
        return None
    return sum(s.elapsed_s for s in run.stats) / len(run.stats)
