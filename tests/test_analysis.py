"""repro.analysis: negative-case fixtures (each diagnostic code fires on a
deliberately broken miniature program), clean-repo positive checks, and the
8-rank collective-traffic audit cross-check (subprocess)."""
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.cache_key import lint_cache_keys
from repro.analysis.contracts import (KernelContract, check_all,
                                      check_contract, default_contracts)
from repro.analysis.diagnostics import (Diagnostic, is_baselined,
                                        load_baseline, split_baselined)
from repro.analysis.lints import (lint_f64, lint_host_sync,
                                  lint_int_accumulators,
                                  lint_threshold_literals)
from repro.analysis.traffic import CollectiveEvent, classify_events
from repro.kernels.nng_tile import _eps2_f32
from tests.helpers import run_subprocess

_F32V = jax.ShapeDtypeStruct((8,), np.float32)


def _codes(diags):
    return [d.code for d in diags]


# ---------------------------------------------------------------------------
# RA101 — float threshold literals
# ---------------------------------------------------------------------------

def test_ra101_python_float_fold_flagged():
    """float(eps) ** 2 folded into an fp32 compare — the PR 5 bug class."""
    eps = 0.1
    jaxpr = jax.make_jaxpr(lambda x: x <= float(eps) ** 2)(_F32V)
    diags = lint_threshold_literals(jaxpr, (_eps2_f32(eps),), subject="fx")
    assert _codes(diags) == ["RA101", "RA101"]  # near-miss + canonical absent
    assert "near-miss" in diags[0].message


def test_ra101_canonical_threshold_clean():
    eps = 0.1
    jaxpr = jax.make_jaxpr(
        lambda x: x <= jnp.float32(_eps2_f32(eps)))(_F32V)
    assert lint_threshold_literals(
        jaxpr, (_eps2_f32(eps),), subject="fx") == []


def test_ra101_trace_time_product_resolved():
    """jnp.float32(eps) ** 2 stays a mul-of-literals in the jaxpr; the
    resolver must fold it in fp32 and match the canonical value."""
    eps = 0.1
    def fn(x):
        e = jnp.float32(eps)
        return x <= e * e
    jaxpr = jax.make_jaxpr(fn)(_F32V)
    assert lint_threshold_literals(jaxpr, (_eps2_f32(eps),),
                                   subject="fx") == []


def test_ra101_canonical_absent():
    jaxpr = jax.make_jaxpr(lambda x: x <= jnp.float32(0.5))(_F32V)
    diags = lint_threshold_literals(jaxpr, (_eps2_f32(0.1),), subject="fx")
    assert _codes(diags) == ["RA101"]
    assert "not found" in diags[0].message


# ---------------------------------------------------------------------------
# RA102 — integer loop accumulators
# ---------------------------------------------------------------------------

def test_ra102_data_dependent_int_accumulator_flagged():
    def fn(x):
        return jax.lax.fori_loop(
            0, 8, lambda i, acc: acc + x[i], jnp.int32(0))
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), np.int32))
    diags = lint_int_accumulators(jaxpr, subject="fx")
    assert _codes(diags) == ["RA102"]


def test_ra102_literal_counter_and_f32_clean():
    def counter(x):
        return jax.lax.fori_loop(0, 8, lambda i, acc: acc + 1, jnp.int32(0))
    def f32acc(x):
        return jax.lax.fori_loop(
            0, 8, lambda i, acc: acc + x[i], jnp.float32(0))
    ji = jax.make_jaxpr(counter)(jax.ShapeDtypeStruct((8,), np.int32))
    jf = jax.make_jaxpr(f32acc)(_F32V)
    assert lint_int_accumulators(ji, subject="fx") == []
    assert lint_int_accumulators(jf, subject="fx") == []


# ---------------------------------------------------------------------------
# RA103 / RA104 — host sync, f64 leaks
# ---------------------------------------------------------------------------

def test_ra103_callback_flagged():
    def fn(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((8,), np.float32), x)
    jaxpr = jax.make_jaxpr(fn)(_F32V)
    diags = lint_host_sync(jaxpr, subject="fx")
    assert _codes(diags) == ["RA103"]
    assert lint_host_sync(jax.make_jaxpr(lambda x: x * 2)(_F32V),
                          subject="fx") == []


def test_ra104_f64_flagged():
    jax.config.update("jax_enable_x64", True)
    try:
        jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
            jax.ShapeDtypeStruct((4,), np.float64))
    finally:
        jax.config.update("jax_enable_x64", False)
    diags = lint_f64(jaxpr, subject="fx")
    assert _codes(diags) == ["RA104"]
    assert lint_f64(jax.make_jaxpr(lambda x: x * 2)(_F32V),
                    subject="fx") == []


# ---------------------------------------------------------------------------
# contracts: RA002/RA003/RA004 fixtures + the real registry
# ---------------------------------------------------------------------------

def _toy_contract(**kw):
    base = dict(
        name="toy",
        kernel_trace=lambda: (lambda x: (x.sum(0).astype(jnp.int32),),
                              (_F32V,)),
        oracle_trace=lambda: (lambda x: (x.sum(0).astype(jnp.int32),),
                              (_F32V,)),
    )
    base.update(kw)
    return KernelContract(**base)


def test_ra004_missing_oracle():
    diags = check_contract(_toy_contract(oracle_trace=None))
    assert "RA004" in _codes(diags)


def test_ra003_padding_invariant_violation():
    diags = check_contract(
        _toy_contract(shape_invariants=((130, 32, "tp % 32"),)))
    assert _codes(diags) == ["RA003"]
    assert "tp % 32" in diags[0].message


def test_ra002_kernel_oracle_mismatch():
    diags = check_contract(_toy_contract(
        oracle_trace=lambda: (lambda x: (x.sum(0),), (_F32V,))))
    assert "RA002" in _codes(diags)


def test_ra002_dtype_policy():
    diags = check_contract(_toy_contract(out_dtypes=(np.uint32,)))
    assert "RA002" in _codes(diags)


def test_default_contracts_all_clean():
    """Every registered Pallas kernel satisfies its contract — including
    eps_count, whose float(eps)**2 literal this PR fixed."""
    diags, contracts = check_all()
    assert len(contracts) == 17
    assert diags == [], [d.render() for d in diags]


def test_eps_count_threshold_regression():
    """Regression for the eps_count fix: the kernel must embed the exact
    fp32 canonical threshold, not the f64 square cast down."""
    eps = 0.1
    assert float(np.float32(float(eps) ** 2)) != _eps2_f32(eps)
    c = {c.name: c for c in default_contracts()}["eps_count"]
    fn, args = c.kernel_trace()
    jaxpr = jax.make_jaxpr(fn)(*args)
    assert lint_threshold_literals(jaxpr, (_eps2_f32(eps),),
                                   subject="eps_count") == []


# ---------------------------------------------------------------------------
# RA110 — cache-key completeness
# ---------------------------------------------------------------------------

def test_ra110_mutable_global_flagged(tmp_path):
    mod = tmp_path / "leaky.py"
    mod.write_text(textwrap.dedent("""
        import functools
        _mode = "fast"          # mutable module state
        TILE = 128              # const-style, fine

        @functools.lru_cache(maxsize=8)
        def build(eps):
            local = TILE * 2
            return (eps, local, _mode)
    """))
    diags = lint_cache_keys(mod)
    assert _codes(diags) == ["RA110"]
    assert "_mode" in diags[0].message and "TILE" not in diags[0].message


def test_ra110_device_builders_clean():
    from pathlib import Path
    import repro.core.distributed.device as dev
    assert lint_cache_keys(Path(dev.__file__)) == []


# ---------------------------------------------------------------------------
# RA301 — dead modules
# ---------------------------------------------------------------------------

def test_ra301_orphan_module(tmp_path):
    from repro.analysis.modgraph import dead_modules
    pkg = tmp_path / "repro"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "nng.py").write_text("from repro import used\n")
    (pkg / "used.py").write_text("X = 1\n")
    (pkg / "orphan.py").write_text("Y = 2\n")
    assert dead_modules(pkg, tmp_path) == ["repro.orphan"]


def test_repo_dead_modules_fully_baselined():
    from pathlib import Path
    from repro.analysis.modgraph import lint_dead_modules
    src_root = Path(__file__).resolve().parent.parent / "src" / "repro"
    fresh, known = split_baselined(
        lint_dead_modules(src_root), load_baseline())
    assert fresh == [], [d.render() for d in fresh]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_matching():
    d1 = Diagnostic("RA301", "repro.sharding", "whatever")
    d2 = Diagnostic("RA301", "repro.other", "whatever")
    base = [{"code": "RA301", "subject": "repro.sharding", "reason": "r"}]
    assert is_baselined(d1, base) and not is_baselined(d2, base)
    fresh, known = split_baselined([d1, d2], base)
    assert fresh == [d2] and known == [d1]


# ---------------------------------------------------------------------------
# RA201 — uncounted collective channel (classifier unit)
# ---------------------------------------------------------------------------

def test_ra201_unattributable_ppermute():
    ev = [CollectiveEvent("ppermute", (128, 7), np.dtype(np.float32), 1.0)]
    diags = classify_events(ev, n_loc=128, dim=8, k_cap=64,
                            met_dtype=np.float32, subject="fx")
    assert _codes(diags) == ["RA201"]
    assert ev[0].channel is None


def test_adjacency_inheritance():
    """An ambiguous payload right after an anchored one rides its channel
    — the (n_loc,) count vector after the (n_loc, k_cap) neighbor table."""
    evs = [
        CollectiveEvent("ppermute", (128, 64), np.dtype(np.int32), 4.0),
        CollectiveEvent("ppermute", (128,), np.dtype(np.int32), 4.0),
    ]
    diags = classify_events(evs, n_loc=128, dim=8, k_cap=64,
                            met_dtype=np.float32, subject="fx")
    assert diags == []
    assert [e.channel for e in evs] == ["ring_mirror", "ring_mirror"]


# ---------------------------------------------------------------------------
# the 8-rank traffic audit — acceptance criterion (subprocess)
# ---------------------------------------------------------------------------

_TRAFFIC_8DEV_CODE = r"""
import numpy as np
from repro.analysis.traffic import (audit_all, collect_collectives,
                                    classify_events)

diags, table, jaxprs = audit_all(nranks=8)
assert diags == [], [d.render() for d in diags]
assert len(table) == 7, sorted(table)
for subject, row in table.items():
    assert row["derived"] == row["formula"], (subject, row)
# systolic configs must account all four ring channels on the tree path
tree = table["systolic[traversal=tree,prune=True]"]["derived"]
assert set(tree) == {"ring_points", "ring_mirror", "ring_forest",
                     "ring_summary"}
# landmark ghost modes: the ring path must account its rotation under the
# ghost_ring channel and carry NO all-to-all ghost channel (and vice versa)
ring = table["landmark[traversal=tiles,ghost=ring]"]["derived"]
assert "ghost_ring" in ring and "ghost" not in ring, sorted(ring)
coll = table["landmark[traversal=tiles,ghost=coll]"]["derived"]
assert "ghost" in coll and "ghost_ring" not in coll, sorted(coll)

# negative fixture: a shard_map program with a rogue ppermute that maps to
# no accounted channel must raise RA201
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map

mesh = Mesh(np.asarray(jax.devices())[:8], ("ring",))
def rogue(x):
    perm = [(i, (i + 1) % 8) for i in range(8)]
    return jax.lax.ppermute(x, "ring", perm)
fn = jax.jit(shard_map(rogue, mesh, in_specs=(P("ring", None),),
                       out_specs=P("ring", None)))
jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((1024, 7), np.float32))
events, unknown = collect_collectives(jaxpr)
assert unknown == 0 and len(events) == 1
bad = classify_events(events, n_loc=128, dim=8, k_cap=64,
                      met_dtype=np.float32, subject="rogue")
assert [d.code for d in bad] == ["RA201"]
print("TRAFFIC_AUDIT_OK")
"""


def test_traffic_audit_8dev():
    out = run_subprocess(_TRAFFIC_8DEV_CODE, devices=8, timeout=1200)
    assert "TRAFFIC_AUDIT_OK" in out


# ---------------------------------------------------------------------------
# CLI (subprocess; the CLI sets its own XLA_FLAGS) — slow
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_check_passes(tmp_path):
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out_json = tmp_path / "report.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--check",
         "--out", str(out_json)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    import json
    report = json.loads(out_json.read_text())
    assert report["ok"] is True
    assert len(report["contracts"]["checked"]) == 17
    assert report["kernel_costs"], "per-kernel HLO cost rows missing"
