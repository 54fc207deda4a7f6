"""The lower-precision control fails the check at a size a test run
holds, while the program passes it on the same points.

On the chip the control runs at each cell's own size (``python -m
bench.control``); here the sift- and word2bits-shaped sets are cut to
8192 points and every row is compared."""
from __future__ import annotations

import numpy as np
import pytest

from bench.control import CONTROL_NUMBER, control_reading
from bench.data import clustered
from bench.reference import check_graphs
from bench.tests.util import tiny_cell

N = 8192
CELLS = ["sift-sparse-point-tiles", "sift-dense-point-tiles",
         "w2b-sparse-point-tiles"]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_incorrect_program_correct(name):
    from repro.nng import build_nng
    cell = tiny_cell(name, n=N)
    metric, eps = cell.config["metric"], cell.params["eps"]
    number = CONTROL_NUMBER[metric]
    limit = cell.params["limits"][number]
    pts = clustered(N, cell.config["dim"], metric, seed=1)
    rows = np.arange(N)
    g = build_nng(pts, eps, metric=metric, k_cap=512)
    [prog] = check_graphs([(g.row_ptr, g.col_ids)], pts, rows, eps, metric)
    assert prog[number] <= limit, prog
    assert control_reading(pts, rows, eps, metric) > limit
