"""The benchmark harness: one cell, one seed, one measured window.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file (``configs/``), its traffic file (``traffic/``,
the engine path and the regime), its cell file (``cells/``, the radius,
the neighbour capacity and the limits of the check) and one reader per
metric (``metrics/<metric>.py``). A cell, a traffic mix or a metric is
added by adding files.

A run:

1. set-up: JAX on the cell's chips, the persistent compile cache, the
   point set from the seed, one warm-up ``build_nng`` that compiles or
   loads every program the window runs;
2. the window: ``build_nng`` calls back to back, host points in, CSR out,
   until ``seconds`` have passed; the build in flight is finished. With
   ``trace`` the profiler records the window;
3. the peak device memory, then the check of every graph the window
   returned against the float64 reference (``reference.py``);
4. the metrics, read by their readers, and the result line.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .data import run_inputs
from .reference import check_graphs, compared_numbers, worst
from .trace import BUILD_SPAN, WINDOW_SPAN, Trace, breakdown, load

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
SAMPLE_ROWS = 2048          # rows of every graph held to the reference


class BenchError(Exception):
    """The run cannot be measured: it exits non-zero with no result."""


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing {path}") from e


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    base: Path = BENCH


def load_cell(name: str, bench_file: Path | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files and the
    metrics it reports."""
    bench_file = bench_file or CHECKOUT / "BENCHMARK.json"
    bench = _json(bench_file)
    root = bench_file.parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {bench_file.name}: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    base = root / bench["paths"][0]
    traffic = _json(base / "traffic" / f"{w['traffic']}.json")
    params = _json(base / "cells" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, traffic, params, e2e, layer,
                base)


def load_reader(cell: Cell, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = cell.base / "metrics" / f"{metric}.py"
    if not path.exists():
        raise BenchError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileEvents:
    """Counts JAX's traces, backend compiles and persistent-cache hits and
    misses, per phase of the run."""

    NAMES = {"/jax/core/compile/jaxpr_trace_duration": "traces",
             "/jax/core/compile/backend_compile_duration": "compiles",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        self.phase = "setup"
        self.counts: dict[str, int] = {}

    def _on(self, event, *_, **__):
        name = self.NAMES.get(event)
        if name:
            key = f"{self.phase}_{name}"
            self.counts[key] = self.counts.get(key, 0) + 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_listener(self._on)
        jax.monitoring.unregister_event_duration_listener(self._on)

    def get(self, phase: str, name: str) -> int:
        return self.counts.get(f"{phase}_{name}", 0)


@dataclass
class Run:
    """What the readers read: one run's clocks, counters and trace."""
    cell: Cell
    device_kind: str
    setup_s: float
    build_s: list
    stats: list
    peak_bytes: int
    trace: object = None


def _settled_k_cap(plan, k_cap: int) -> int:
    """The capacity the warm-up's plan settled on: the plan itself on the
    point partition, its ``k_cap`` on the spatial one."""
    return int(plan) if isinstance(plan, (int, np.integer)) else int(
        getattr(plan, "k_cap", k_cap))


def _profile(trace_dir):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _finite(v):
    return float(np.finfo(np.float64).max) if v == math.inf else v


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, build=None):
    """Measure ``cell`` once. Returns (result, counters): the result line's
    object, and the program's counters for the line before it."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < cell.chips:
        raise BenchError(f"{cell.chips} chips asked for, {len(devs)} found")
    from jax.sharding import Mesh

    from repro.launch.cache import enable_compile_cache
    from repro.nng import build_nng

    build = build or build_nng
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cfg, traffic, params = cell.config, cell.traffic, cell.params
    metric = cfg["metric"]
    eps = params["eps"]
    mesh = Mesh(np.asarray(devs[:cell.chips]), ("ring",))
    opts = dict(metric=metric, partition=traffic["partition"],
                traversal=traffic["traversal"], mesh=mesh)
    with CompileEvents() as events:
        pts, rows = run_inputs(cfg, seed, SAMPLE_ROWS)
        g = build(pts, eps, k_cap=params["k_cap"], **opts)
        k_cap = _settled_k_cap(g.meta.get("plan"), params["k_cap"])
        warm_replans = g.stats.replans
        del g
        setup_s = time.perf_counter() - t_start

        events.phase = "window"
        graphs, build_s, stats, failed = [], [], [], 0
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            _profile(trace_dir)
        annotate = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        try:
            with annotate(WINDOW_SPAN) if trace else nullcontext():
                while True:
                    with annotate(BUILD_SPAN) if trace else nullcontext():
                        tb = time.perf_counter()
                        g = build(pts, eps, k_cap=k_cap, **opts)
                        build_s.append(time.perf_counter() - tb)
                    graphs.append((g.row_ptr, g.col_ids))
                    stats.append(g.stats)
                    if time.perf_counter() - t0 >= seconds:
                        break
        except Exception as e:  # noqa: BLE001 — an answer that never came
            failed = 1
            print(f"bench: build {len(graphs) + 1} of the window raised "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        finally:
            if trace:
                jax.profiler.stop_trace()
    tr = None
    if trace:
        tr = Trace(load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    used = list(mesh.devices.flat)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    readings = check_graphs(graphs, pts, rows, eps, metric)
    got = worst(readings, metric)
    limits = params["limits"]
    checks = {k: {"value": _finite(got[k]), "limit": limits[k]}
              for k in compared_numbers(metric)}
    attempted = len(graphs) + failed
    bad = sum(any(r[k] > limits[k] for k in checks) for r in readings)
    correct = failed == 0 and bool(graphs) and bad == 0

    ctx = Run(cell, devs[0].device_kind, setup_s, build_s, stats, peak, tr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(cell, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted,
              "failed": failed + bad, "metrics": metrics, "device": device}
    if tr is not None:
        ids = tr.device_ids()[:cell.chips]
        device["busy_s"] = (sum(tr.busy_s(d) for d in ids) / len(ids)
                            if ids else 0.0)
        device["window_s"] = tr.window_s
        result["breakdown"] = breakdown(tr, cell.chips)
    result["checks"] = checks

    last = stats[-1] if stats else None
    counters = {
        "builds": len(graphs), "edges": int(g.num_edges) if graphs else 0,
        "mean_degree": float(g.avg_degree) if graphs else 0.0,
        "k_cap": k_cap, "warmup_replans": int(warm_replans),
        "window_replans": int(sum(s.replans for s in stats)),
        "window_traces": events.get("window", "traces"),
        "window_compiles": events.get("window", "compiles"),
        "setup_compiles": events.get("setup", "compiles"),
        "cache_hits": events.get("setup", "cache_hits")
        + events.get("window", "cache_hits"),
        "cache_misses": events.get("setup", "cache_misses")
        + events.get("window", "cache_misses"),
        "dists_evaluated": last.dists_evaluated if last else 0.0,
        "tiles_skipped": last.tiles_skipped if last else 0.0,
        "comm_bytes": last.total_comm_bytes if last else 0.0,
        "graphs_checked": len(readings), "sample_rows": len(rows)}
    return result, counters


def emit(result: dict, counters: dict) -> None:
    """The counters line, the result line last on stdout, and each number
    compared beside its limit as the last lines on stderr."""
    print(json.dumps({"counters": counters}), flush=True)
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
