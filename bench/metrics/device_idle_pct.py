"""device_idle_pct: the share of the traced window in which no operation
ran on the chip, 1 - union(op intervals) / window; on several chips the
highest."""


def read(run):
    tr = run.trace
    if tr is None or not tr.device_ids():
        return None
    return max(100.0 * (1.0 - tr.busy_s(d) / tr.window_s)
               for d in tr.device_ids()[:run.cell.chips])
