"""tile_roofline_pct: the least time the chip could take for the window's
fused L2 tile calls, over their device time, in percent. Each call's
operations and bytes come from its shapes in the trace
(``roofline.l2_tile_cost``); the peaks from ``peaks.json``. The bound that
binds is the MXU's bf16 rate, and the kernel runs its fp32 contraction at
Precision.HIGHEST, several MXU passes, so the share stays far below 100%.
"""
from bench.kernels import l2_tile_shape
from bench.roofline import l2_tile_cost, least_seconds, peaks


def read(run):
    tr = run.trace
    if tr is None:
        return None
    peak, least, spent = None, 0.0, 0.0
    for d in tr.device_ids()[:run.cell.chips]:
        for op in tr.ops[d]:
            shape = l2_tile_shape(op.name)
            if shape is None or op.end_ns <= tr.lo or op.start_ns >= tr.hi:
                continue
            peak = peak or peaks(run.device_kind)
            least += least_seconds(*l2_tile_cost(*shape), peak)[0]
            spent += (op.end_ns - op.start_ns) * 1e-9
    return 100.0 * least / spent if spent > 0 else None
