"""Benchmark harness entry: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; the device benches also emit
machine-readable JSONs so CI can track the perf trajectory:
``BENCH_landmark.json`` (edges/s, comm bytes, grouped-tile skip rate,
dense-vs-bitmask tile-byte accounting), ``BENCH_systolic.json``
(edges/s, per-channel ring bytes, double-buffered vs serial ring overlap
A/B, and the edges/s-vs-nranks strong-scaling curve), and
``BENCH_forest_build.json`` (host vs on-device forest-construction wall
clock; both engine JSONs also carry ``build_s`` + the same A/B entry), and
``BENCH_stream.json`` (online maintenance: delta-traversal distance work
vs a full rebuild, insert throughput, compaction amortization).

  python benchmarks/run.py                  # full sweep
  python benchmarks/run.py --only landmark  # just the landmark JSON bench
"""
import argparse
import os
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)  # so `benchmarks.tables` resolves when run as a script

# 8 simulated devices for the device-engine benches (must precede jax
# import; APPEND so a pre-existing XLA_FLAGS — e.g. --xla_dump_to — doesn't
# silently drop the forcing and produce an incomparable nranks=1 JSON)
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this substring")
    ap.add_argument("--landmark-json", default="BENCH_landmark.json",
                    help="output path for the landmark perf JSON")
    ap.add_argument("--systolic-json", default="BENCH_systolic.json",
                    help="output path for the systolic perf JSON")
    ap.add_argument("--forest-json", default="BENCH_forest_build.json",
                    help="output path for the forest-build perf JSON")
    ap.add_argument("--stream-json", default="BENCH_stream.json",
                    help="output path for the online-maintenance perf JSON")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import tables
    benches = [
        ("datasets", tables.bench_datasets),              # Table I
        ("covertree_vs_snn", tables.bench_covertree_vs_snn),  # Table III
        ("speedup_over_snn", tables.bench_speedup_over_snn),  # Table II
        ("strong_scaling", tables.bench_strong_scaling),  # Fig 2
        ("phase_breakdown", tables.bench_phase_breakdown),  # Figs 3-5
        ("block_pruning", tables.bench_block_pruning),    # systolic skip rates
        ("landmark_device",                               # landmark fast path
         lambda: tables.bench_landmark_device(args.landmark_json)),
        ("systolic_device",                               # systolic fast path
         lambda: tables.bench_systolic_device(args.systolic_json)),
        ("forest_build_device",                           # on-device builder
         lambda: tables.bench_forest_build(args.forest_json)),
        ("stream_updates",                                # online maintenance
         lambda: tables.bench_stream(args.stream_json)),
        ("distance_kernels", tables.bench_distance_kernels),  # kernel layer
    ]
    selected = [(n, f) for n, f in benches
                if not args.only or args.only in n]
    if not selected:
        raise SystemExit(f"--only {args.only!r} matched no bench "
                         f"(have: {', '.join(n for n, _ in benches)})")
    print("name,us_per_call,derived")
    for _, fn in selected:
        fn()


if __name__ == "__main__":
    main()
