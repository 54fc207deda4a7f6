"""The four-chip ring cell ``sift-sparse-ring4`` and its readers.

The readers ``ring_exposed_ms``, ``ring_busy_spread_pct`` and
``ring_mib_per_build`` on synthetic traces and counters, on the traces
recorded on one chip and on four, and on a program without
``ring_bytes``; every traced reader of the cell on the four-chip trace;
then the harness on the cell itself, cut to 4 x 512 points on four host
devices in a subprocess: the sound build reads correct against the
reference, a build with one mirror dropped or one row's neighbour moved
reads incorrect, and ``ring_mib_per_build`` equals the bytes the ring's
shapes give.
"""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import load_cell, load_reader
from bench.ring import is_ring_hop
from bench.tests.util import CHECKOUT
from bench.trace import HOST_PLANE, OPS_LINE, WINDOW_SPAN, Event, Trace, \
    from_json

CELL = "sift-sparse-ring4"
RECORDED = Path(__file__).parent / "data" / "sift_sparse_2builds.json.gz"
RECORDED_RING = Path(__file__).parent / "data" / "sift_ring4_1build.json.gz"

START = ("%collective-permute-start.2 = (s32[512,896]{1,0}, s32[512,896]"
         "{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start("
         "s32[512,896]{1,0} %copy.93), channel_id=1, source_target_pairs="
         "{{0,3},{1,0},{2,1},{3,2}}")
DONE = ("%collective-permute-done.2 = s32[512,896]{1,0} "
        "collective-permute-done((s32[512,896]{1,0}, s32[512,896]{1,0}, "
        "u32[]{:S(2)}, u32[]{:S(2)}) %collective-permute-start.2)")
SYNC = ("%collective-permute.1 = f32[512,128]{1,0} collective-permute("
        "f32[512,128]{1,0} %x.1), source_target_pairs={{0,2},{1,3}}")
# operations that name a ring hop among their operands, and other
# collectives: none is a hop
OTHERS = ["%copy-start.5 = (s32[512]{0}, s32[512]{0}, u32[]{:S(2)}) "
          "copy-start(s32[512]{0} %collective-permute-done.3)",
          "%fusion.3 = s32[512,1792]{1,0} fusion(%collective-permute-done.2,"
          " %get-tuple-element.338), kind=kLoop",
          "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %copy-done.20)",
          "%all-gather.1 = f32[4,1,128]{2,1,0} all-gather(f32[1,128]{1,0} "
          "%copy-done.19), dimensions={0}",
          "%_tile_padded_call = (s32[1,512]{1,0}, s32[16,512]{1,0}) "
          "custom-call(f32[128,512]{1,0} %p)",
          "collective-permute-done", "%collective-permute-done.4"]


def _read(metric, run):
    return load_reader(load_cell(CELL), metric)(run)


def _op(dev, start, end, name="%fusion.1 = f32[8] fusion()"):
    return Event(f"/device:TPU:{dev}", OPS_LINE, name, float(start),
                 float(end))


def _run(events, builds=1, chips=4, stats=None):
    """What a reader sees of a traced window from 1000 to 11000 ns."""
    events = [Event(HOST_PLANE, "python3", WINDOW_SPAN, 1000.0, 11000.0)] \
        + events
    return SimpleNamespace(cell=SimpleNamespace(name="synthetic",
                                                chips=chips),
                           device_kind="TPU v5 lite",
                           stats=stats or [SimpleNamespace()] * builds,
                           trace=Trace(events))


def test_the_matcher_takes_collective_permutes_only():
    assert all(map(is_ring_hop, [START, DONE, SYNC]))
    assert not any(map(is_ring_hop, OTHERS))


def test_exposed_time_sums_the_permutes_of_the_busiest_chip():
    events = [_op(0, 500, 1200, START),        # clipped to 1000-1200
              _op(0, 1200, 1700, DONE),
              _op(0, 1700, 6000),               # compute: not counted
              _op(0, 6000, 6100, OTHERS[0]),
              _op(1, 2000, 2300, SYNC),
              _op(1, 3000, 3100, OTHERS[2]),
              _op(2, 1000, 9000), _op(3, 1000, 9000)]
    run = _run(events, builds=2)
    # chip 0: 200 + 500 ns of permutes; chip 1: 300 ns
    assert _read("ring_exposed_ms", run) == pytest.approx(700 / 2 * 1e-6)


def test_exposed_time_reads_nothing_without_permutes():
    run = _run([_op(d, 1000, 5000, OTHERS[d]) for d in range(4)])
    assert _read("ring_exposed_ms", run) is None
    # a permute on a chip past the cell's chips is not the cell's
    run = _run([_op(0, 1000, 5000), _op(1, 1000, 2000, DONE)], chips=1)
    assert _read("ring_exposed_ms", run) is None
    untraced = SimpleNamespace(trace=None, stats=[SimpleNamespace()],
                               cell=run.cell)
    assert _read("ring_exposed_ms", untraced) is None


def test_busy_spread_over_the_chips():
    # every chip's ops cover 9000 ns of the window, but chips 2 and 3
    # spend 5000 of them blocked in a hop: compute 8000, 8000, 4000, 4000
    events = [_op(0, 1000, 9000), _op(0, 9000, 10000, DONE),
              _op(1, 1000, 5000), _op(1, 3000, 9000),
              _op(1, 9000, 10000, SYNC),
              _op(2, 1000, 5000), _op(2, 5000, 10000, START),
              _op(3, 0, 3000), _op(3, 3000, 5000),
              _op(3, 5000, 10000, DONE)]
    run = _run(events)
    assert {run.trace.busy_s(d) for d in range(4)} == {9000e-9}
    assert _read("ring_busy_spread_pct", run) == pytest.approx(50.0)
    even = [_op(d, 1000, 4000) for d in range(4)]
    assert _read("ring_busy_spread_pct", _run(even)) == 0.0
    idle = _run([_op(d, 12000, 13000) for d in range(4)])
    assert _read("ring_busy_spread_pct", idle) is None
    hops_only = _run([_op(d, 1000, 4000, START) for d in range(4)])
    assert _read("ring_busy_spread_pct", hops_only) is None


def test_ring_readers_on_the_recorded_ring_trace():
    """One build of ``sift-sparse-ring4`` traced on four v5e chips: chips
    2 and 3 run 3 of the 5 tiles a call and wait out the halving round in
    the home hop, 2.29 s a call. Every traced reader of the cell reads it.
    """
    cell = load_cell(CELL)
    run = SimpleNamespace(cell=cell, device_kind="TPU v5 lite",
                          stats=[SimpleNamespace(elapsed_s=5.9359)],
                          trace=Trace(from_json(RECORDED_RING)))
    traced = [m["name"] for m in cell.per_layer
              if m["source"] in ("device_trace", "program_span")]
    assert len(traced) == 10
    got = {m: load_reader(cell, m)(run) for m in traced}
    assert None not in got.values(), got
    assert got["ring_exposed_ms"] == pytest.approx(4650.25, abs=0.01)
    assert got["ring_busy_spread_pct"] == pytest.approx(38.77, abs=0.01)
    assert 0 < got["tile_roofline_pct"] < 100


def test_ring_readers_read_nothing_on_one_chip():
    """The trace recorded on one chip: no permute, no spread."""
    run = SimpleNamespace(cell=load_cell("sift-sparse-point-tiles"),
                          device_kind="TPU v5 lite", stats=[1, 2],
                          trace=Trace(from_json(RECORDED)))
    assert _read("ring_exposed_ms", run) is None
    assert _read("ring_busy_spread_pct", run) is None


def test_ring_mib_per_build_takes_the_mean_of_the_counter():
    stats = [SimpleNamespace(ring_bytes=3 * 2**20),
             SimpleNamespace(ring_bytes=2**20)]
    assert _read("ring_mib_per_build",
                 SimpleNamespace(stats=stats, trace=None)) == 2.0
    assert _read("ring_mib_per_build",
                 SimpleNamespace(stats=[], trace=None)) is None
    # the program before it counted its ring's bytes
    parent = SimpleNamespace(engine_calls=2, csr_mirror_added=0)
    assert _read("ring_mib_per_build",
                 SimpleNamespace(stats=[parent] * 2, trace=None)) is None


# -- the cell on four host devices -----------------------------------------

_HARNESS_CODE = r"""
import json, sys
sys.path[:0] = [CHECKOUT, CHECKOUT + "/src"]
import numpy as np
from bench.data import config_points
from bench.tests.util import run_tiny, tiny_cell
from repro.core.graph import NNGraph
from repro.nng import build_nng

cell = tiny_cell("sift-sparse-ring4", n=N)
# the radius of the cut set's degree: the traffic's target mean degree,
# midway between two float64 distances
x = config_points(cell.config).astype(np.float64)
sq = (x * x).sum(1)
d2 = np.maximum(sq[:, None] + sq[None, :] - 2 * x @ x.T, 0)
vals = np.sort(d2[np.triu_indices(N, 1)])
k = cell.traffic["target_mean_degree"] * N // 2
cell.params = dict(cell.params, eps=float(np.sqrt(0.5 * (vals[k] + vals[k + 1]))))


def drop_mirror(pts, eps, **kw):
    g = build_nng(pts, eps, **kw)
    i = int(np.argmax(np.diff(g.row_ptr)))          # a row with neighbours
    cols = np.delete(g.col_ids, g.row_ptr[i])
    ptr = g.row_ptr.copy()
    ptr[i + 1:] -= 1
    return NNGraph(g.n, ptr, cols, stats=g.stats, meta=g.meta)


def move_neighbour(pts, eps, **kw):
    g = build_nng(pts, eps, **kw)
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    dst = g.col_ids.astype(np.int64)
    i = int(np.argmax(np.diff(g.row_ptr)))
    j = int(g.col_ids[g.row_ptr[i]])
    far = int(np.argmax(((pts - pts[i]) ** 2).sum(1)))
    keep = ~(((src == i) & (dst == j)) | ((src == j) & (dst == i)))
    src = np.append(src[keep], i)
    dst = np.append(dst[keep], far)
    return NNGraph.from_directed_pairs(g.n, src, dst, stats=g.stats,
                                       meta=g.meta)


out = {}
for name, build in [("sound", None), ("drop_mirror", drop_mirror),
                    ("move_neighbour", move_neighbour)]:
    result, counters = run_tiny(cell, build=build, trace=name == "sound")
    out[name] = {"result": result, "counters": counters}
print(json.dumps(out))
"""
N = 4 * 512


@pytest.fixture(scope="module")
def ring_runs():
    from tests.helpers import run_subprocess
    code = (f"CHECKOUT = {str(CHECKOUT)!r}\nN = {N}\n" + _HARNESS_CODE)
    return json.loads(run_subprocess(code, devices=4).splitlines()[-1])


def test_cell_on_four_devices_reads_correct(ring_runs):
    result = ring_runs["sound"]["result"]
    counters = ring_runs["sound"]["counters"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["device"]["count"] == 4
    assert counters["window_compiles"] == 0
    assert 40 < counters["mean_degree"] < 100
    assert counters["k_cap"] == load_cell(CELL).params["k_cap"]


def test_ring_mib_per_build_equals_the_shapes(ring_runs):
    metrics = ring_runs["sound"]["result"]["metrics"]
    k_cap = ring_runs["sound"]["counters"]["k_cap"]
    n_loc, dim, rounds = N // 4, 128, 2
    # the priming hop and ``rounds`` block hops carry the points and the
    # block's first id; ``rounds`` mirror hops and the hop home carry the
    # mirror accumulator's ids and counts; two engine calls per build
    point_hop = n_loc * dim * 4 + 4
    mirror_hop = n_loc * k_cap * 4 + n_loc * 4
    want = 2 * (rounds + 1) * (point_hop + mirror_hop) / 2**20
    assert metrics["ring_mib_per_build"]["value"] == pytest.approx(want)
    assert metrics["ring_mib_per_build"]["unit"] == "MiB"
    # a CPU trace has no chip plane: the device readers read nothing
    assert "ring_exposed_ms" not in metrics
    assert "ring_busy_spread_pct" not in metrics


@pytest.mark.parametrize("fault", ["drop_mirror", "move_neighbour"])
def test_fault_on_four_devices_reads_incorrect(ring_runs, fault):
    result = ring_runs[fault]["result"]
    assert result["correct"] is False, result["checks"]
    assert result["failed"] >= 1
