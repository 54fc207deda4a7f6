"""The host side of a build as ``build_nng`` records it (``repro.obs``):
the ``nng.*`` span tree and the per-build counters on ``RunStats``, on the
point/tiles, spatial/tiles and point/tree engines at n = 512."""
import jax
import numpy as np
import pytest

from repro.nng import build_nng
from repro.obs import count, recording, span, totals

N, DIM = 512, 8
CONFIGS = [("point", "tiles"), ("spatial", "tiles"), ("point", "tree")]
TOP = {"nng.prepare", "nng.plan", "nng.run", "nng.grow", "nng.rerun",
       "nng.stats", "nng.fetch", "nng.csr"}
PARENTS = {"nng.forest": {"nng.prepare"},
           "nng.put": {"nng.run", "nng.rerun"},
           "nng.wait": {"nng.run", "nng.rerun"},
           "nng.check": {"nng.run"},
           "nng.csr.select": {"nng.csr"},
           "nng.csr.mirror": {"nng.csr"},
           "nng.csr.sort": {"nng.csr"},
           "nng.csr.rows": {"nng.csr"}}


def _points():
    return np.random.default_rng(7).normal(size=(N, DIM)).astype(np.float32)


def _build(partition, traversal, eps, k_cap):
    return build_nng(_points(), eps, partition=partition,
                     traversal=traversal, k_cap=k_cap)


def _names(stats):
    return [name for name, *_ in stats.spans]


def _check_tree(stats):
    """Every span sits inside an open span of its parent's name."""
    for name, parent, start, end in stats.spans:
        assert start <= end
        if parent is None:
            assert name in TOP, name
            continue
        assert parent in PARENTS[name], (name, parent)
        assert any(p == parent and ps <= start and end <= pe
                   for p, _, ps, pe in stats.spans), (name, parent)


def _seconds(stats, name):
    [s] = [end - start for nm, _, start, end in stats.spans if nm == name]
    return s


@pytest.mark.parametrize("partition,traversal", CONFIGS)
def test_span_tree_and_counters(partition, traversal):
    # an eps no other test uses: the first call compiles its programs
    eps = {"point": 2.501, "spatial": 2.502}[partition] + (
        0.01 if traversal == "tree" else 0.0)
    g = _build(partition, traversal, eps, k_cap=512)
    st = g.stats
    assert st.replans == 0 and st.engine_calls == 2
    names = _names(st)
    # the point engine's one row table takes the row-table CSR path, the
    # spatial engine's owned and ghost tables the general one
    general = {"nng.csr.sort", "nng.csr.rows"}
    skipped = {"nng.forest"} | (general if partition == "point"
                                else {"nng.csr.mirror"})
    expected = TOP - {"nng.grow"} | set(PARENTS) - skipped
    if traversal == "tree":
        expected.add("nng.forest")
        assert st.build_s == _seconds(st, "nng.forest") > 0
    else:
        assert st.build_s == 0.0
    assert set(names) == expected
    assert names.count("nng.run") == 1 and names.count("nng.rerun") == 1
    _check_tree(st)
    assert st.elapsed_s == _seconds(st, "nng.rerun")
    assert st.compiles > 0 and st.compile_s > 0
    k_cap = g.meta["plan"] if partition == "point" else g.meta["plan"].k_cap
    if partition == "point":
        assert st.table_slots == N * k_cap
        assert st.fetch_bytes == N * k_cap * 4
        assert st.pairs_selected == 2 * g.num_edges
        assert st.csr_mirror_added == 0
    else:
        # owned and ghost tables, ids fetched with them
        assert st.table_slots % k_cap == 0
        assert st.fetch_bytes == 4 * (st.table_slots + st.table_slots // k_cap)
        assert st.pairs_selected >= 2 * g.num_edges
    again = _build(partition, traversal, eps, k_cap=512).stats
    assert again.compiles == 0 and again.compile_s == 0.0
    assert again.engine_calls == 2
    assert again.spans is not st.spans


@pytest.mark.parametrize("partition,traversal", CONFIGS)
def test_forced_grow_runs_the_engine_three_times(partition, traversal):
    full = _build(partition, traversal, 2.5, k_cap=512)
    # below the longest list, so that one grow is needed: the max degree on
    # the point engine; on the spatial one, whose rows split their lists
    # into owned and ghost parts and whose grow doubles, 32 for this set
    k_cap = (32 if partition == "spatial"
             else int(full.degrees().max()) - 1)
    g = _build(partition, traversal, 2.5, k_cap=k_cap)
    st = g.stats
    assert g == full
    assert st.replans == 1 and st.engine_calls == 3
    names = _names(st)
    assert names.count("nng.run") == 2 and names.count("nng.grow") == 1
    assert names.count("nng.rerun") == 1
    _check_tree(st)
    assert st.elapsed_s == _seconds(st, "nng.rerun")


def test_span_without_a_recorder_still_times_itself():
    with span("nng.x") as s:
        count("engine_calls")
    assert s.seconds >= 0.0


def test_recording_keeps_parents_and_resets():
    with recording() as outer:
        with span("a"):
            with recording() as inner:
                with span("b"):
                    count("engine_calls", 2)
            with pytest.raises(ValueError):
                with span("c"):
                    raise ValueError
            count("engine_calls")
    assert [(n, p) for n, p, *_ in inner.spans] == [("b", None)]
    assert [(n, p) for n, p, *_ in outer.spans] == [("c", "a"), ("a", None)]
    assert inner.counts == {"engine_calls": 2}
    assert outer.counts == {"engine_calls": 1}
    assert outer.open == [] and inner.open == []
    with span("d"):                     # no recorder is active any more
        count("engine_calls")
    assert len(outer.spans) == 2 and outer.counts == {"engine_calls": 1}


def test_compiles_are_counted_into_the_active_recorder():
    f = jax.jit(lambda x: x * 3.25 + 0.5)
    with recording() as rec:
        f(np.float32(1.0)).block_until_ready()
    assert rec.counts["compiles"] == 1 and rec.counts["compile_s"] > 0
    with recording() as rec:
        f(np.float32(2.0)).block_until_ready()
    assert "compiles" not in rec.counts


def test_totals_sum_repeats_in_closing_order():
    spans = [("nng.wait", "nng.run", 0.0, 1.0), ("nng.run", None, 0.0, 2.0),
             ("nng.wait", "nng.rerun", 3.0, 3.5)]
    assert totals(spans) == {"nng.wait": 1.5, "nng.run": 2.0}
