"""engine_calls_per_build: engine invocations per build_nng call
(``RunStats.engine_calls``: warm runs, one more per grow, and the steady
re-run), mean over the window's builds."""


def read(run):
    calls = [getattr(s, "engine_calls", None) for s in run.stats]
    if not calls or None in calls:
        return None
    return sum(calls) / len(calls)
