"""Benchmark harness: one function per paper table (Figs. 5-12) plus the
beyond-paper builder/kernel/serving benches. Prints ``table,dataset,algo,
value`` CSV; ``--json PATH`` additionally writes the machine-readable
``{suite: [rows]}`` mapping consumed by the CI perf-trajectory artifacts
(`BENCH_*.json`). ``--quick`` trims dataset sizes for CI; ``--only`` takes
a comma-separated suite list; ``--check`` gates the run against the
COMMITTED baselines at the repo root (fails on > 1.3x regression of any
tracked metric — see CHECK_GATES), seeding the perf trajectory the CI
artifacts extend."""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------- schema
# The --json artifacts (BENCH_serving.json / BENCH_kernels.json) are CI's
# perf trajectory; this schema gate keeps them from silently drifting —
# a suite that stops emitting a tracked metric fails the run instead of
# producing a quietly thinner artifact (tests/test_bench_schema.py holds
# the same gate against a tiny in-process run).
ROW_KEYS = ("table", "dataset", "algo", "value")

# per-suite metrics that must be present in every artifact (subset — new
# rows may always be added; removing one of these is a schema break)
REQUIRED_ALGOS = {
    "serving": {"qps", "qps_sharded", "us_per_query", "us_per_query_sharded",
                "sharded_speedup", "profile_levels", "profile_us_per_query",
                "profile_loop_us_per_query", "profile_speedup",
                "ragged_buckets", "ragged_us_per_query",
                "bucket_pair_us_per_query", "ragged_speedup",
                "rowsharded_ragged_us_per_query",
                "rowsharded_bucket_pair_us_per_query",
                "rowsharded_ragged_speedup", "compressed_bytes_ratio",
                "update_apply_us", "compact_us", "delta_query_overhead",
                "serve_p50_us", "serve_p99_us", "dma_overlap_speedup",
                "degraded_mode_overhead", "wal_append_us"},
    "label_store": {"entries", "padded_bytes", "csr_bytes",
                    "dense_us_per_query", "seg_us_per_query"},
}

# ------------------------------------------------------- regression gates
# ``--check`` re-runs the suites and compares these metrics against the
# COMMITTED baselines at the repo root (BENCH_serving.json /
# BENCH_kernels.json): a tracked metric that got > CHECK_TOLERANCE x worse
# than its committed value fails the run.
CHECK_TOLERANCE = float(os.environ.get("REPRO_BENCH_TOL", "1.3"))

# suite -> {algo: "lower" (smaller is better) | "higher"}. Only metrics
# whose value is comparable ACROSS MACHINES carry the relative gate: the
# kernel suites' analytic traffic/compare ratios are deterministic — any
# drift is a real code regression, never runner noise. Absolute
# wall-clock metrics (us_per_query et al.) are archived in the artifacts
# but NOT relatively gated: the committed baseline and the CI runner are
# different machines, so a 1.3x wall-clock delta measures hardware, not
# code. Wall-clock trends are gated through the same-run speedup FLOORS
# below instead (both sides of a speedup share one process, so machine
# speed cancels).
CHECK_GATES = {
    "kernel_query": {"traffic_ratio": "higher"},
    "kernel_segmented": {"hbm_ratio": "higher", "cmp_ratio": "higher"},
    "kernel_cin": {"ratio": "higher"},
}

# absolute floors independent of the baseline (acceptance trends): the
# ragged megakernel must stay >= 2x over the bucket-pair dispatch loop on
# the >= 8-bucket skewed store (observed 5.8-11.6x), including with the
# store row-sharded (one tile gather + one launch per device vs the
# per-bucket-pair collective loop), and the compressed arena must keep
# >= 1.8x the rows per byte of the uncompressed one (observed ~2.35x).
# dma_overlap_speedup (quad-buffered tile-DMA ring vs the nbuf=1
# single-buffer baseline, same worklist, same run) is a real overlap
# ratio only on TPU; under CI's interpret emulation the copies are
# synchronous either way (observed ~0.7-1.1x with interpret-loop timing
# noise), so its floor of 0.5 only guards the ring against ADDING
# overhead — a 2x collapse, not jitter.
CHECK_FLOORS = {
    "serving": {"ragged_speedup": 2.0, "ragged_buckets": 8.0,
                "rowsharded_ragged_speedup": 2.0,
                "compressed_bytes_ratio": 1.8,
                "dma_overlap_speedup": 0.5},
}

# absolute ceilings, the floors' smaller-is-better mirror: serving
# through a NON-EMPTY delta-extended arena must stay within 1.15x of the
# static ragged path (observed ~1.0x: the delta only redirects tile
# pointers inside the one launch per flush). Like the floors, ceilings
# are same-run ratios, so machine speed cancels — with one exception:
# serve_p99_us is an absolute wall-clock SLO guard on the continuous-
# batching epoch (enqueue->deliver p99). It is deliberately slack (CI
# observes low single-digit ms, but one interpret-mode compile of an
# unseen padded batch shape landing in-band costs ~300ms) because
# runner speed varies; what it catches is pathological serialization —
# a flush re-running the whole backlog, a deadline that never fires, a
# request parked until epoch end — which shows up as many seconds, not
# percent. The resilience rows (docs/resilience.md §benchmarks) ride the
# same logic: degraded_mode_overhead is a same-run ratio (bucket_pair
# fallback rung vs csr-ragged primary on identical-size flushes —
# observed ~1-3x on CI's interpret path; the ceiling of 100x catches a
# fallback rung that silently became an effective outage, not dispatch
# jitter), and wal_append_us is an absolute wall-clock guard on the
# fsync'd per-batch WAL append (observed ~100us-2ms depending on the
# runner's disk; the 50ms ceiling catches a WAL that serializes update
# ingestion, e.g. an accidental rewrite-the-log-per-append).
CHECK_CEILINGS = {
    "serving": {"delta_query_overhead": 1.15,
                "serve_p99_us": 1_000_000.0,
                "degraded_mode_overhead": 100.0,
                "wal_append_us": 50_000.0},
}

# which committed artifact holds each suite's baseline rows
BASELINE_FILES = {
    "serving": "BENCH_serving.json",
    "kernel_query": "BENCH_kernels.json",
    "kernel_segmented": "BENCH_kernels.json",
    "kernel_cin": "BENCH_kernels.json",
}


def check_against_baseline(suite: str, rows, base_rows,
                           tol: float = None) -> list[str]:
    """Failure strings for every gated metric of ``suite`` that regressed
    by more than ``tol`` x vs the baseline rows, or fell under its
    absolute floor. Metrics present only in the fresh run (new rows) are
    ignored; a gated BASELINE metric missing from the fresh run is itself
    a failure (the artifact thinned out)."""
    tol = CHECK_TOLERANCE if tol is None else tol
    gates = CHECK_GATES.get(suite, {})
    fresh = {(r["table"], r["dataset"], r["algo"]): r["value"] for r in rows}
    failures = []
    for r in base_rows:
        key = (r["table"], r["dataset"], r["algo"])
        direction = gates.get(key[2])
        if direction is None:
            continue
        new = fresh.get(key)
        if new is None:
            failures.append(f"{suite} {key}: gated metric missing from "
                            "fresh run")
            continue
        old = r["value"]
        if old <= 0 or new <= 0:
            continue
        worse = (new / old) if direction == "lower" else (old / new)
        if worse > tol:
            failures.append(
                f"{suite} {key}: {worse:.2f}x worse than baseline "
                f"({old:.6g} -> {new:.6g}, tolerance {tol}x)")
    for algo, floor in CHECK_FLOORS.get(suite, {}).items():
        vals = [v for k, v in fresh.items() if k[2] == algo]
        for v in vals:
            if v < floor:
                failures.append(f"{suite} {algo}: {v:.6g} under the "
                                f"absolute floor {floor}")
    for algo, ceiling in CHECK_CEILINGS.get(suite, {}).items():
        vals = [v for k, v in fresh.items() if k[2] == algo]
        for v in vals:
            if v > ceiling:
                failures.append(f"{suite} {algo}: {v:.6g} over the "
                                f"absolute ceiling {ceiling}")
    return failures


def validate_rows(suite: str, rows) -> None:
    """Raise ValueError unless ``rows`` conforms to the artifact schema:
    a non-empty list of {table, dataset, algo, value} with string labels
    and real-number values, carrying every required metric of ``suite``."""
    if not isinstance(rows, list) or not rows:
        raise ValueError(f"suite {suite!r}: expected a non-empty row list, "
                         f"got {type(rows).__name__}")
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            raise ValueError(f"suite {suite!r} row {i}: not a dict")
        missing = [k for k in ROW_KEYS if k not in row]
        if missing:
            raise ValueError(f"suite {suite!r} row {i}: missing {missing}")
        for k in ("table", "dataset", "algo"):
            if not isinstance(row[k], str) or not row[k]:
                raise ValueError(f"suite {suite!r} row {i}: {k!r} must be a "
                                 f"non-empty string, got {row[k]!r}")
        if isinstance(row["value"], bool) or \
                not isinstance(row["value"], (int, float)):
            raise ValueError(f"suite {suite!r} row {i}: value must be a "
                             f"number, got {row['value']!r}")
    have = {r["algo"] for r in rows}
    lost = REQUIRED_ALGOS.get(suite, set()) - have
    if lost:
        raise ValueError(f"suite {suite!r} artifact dropped tracked "
                         f"metrics: {sorted(lost)}")


def _serving_in_subprocess(args) -> list:
    """Run the serving suite in a child process so its virtual-device
    topology (`xla_force_host_platform_device_count`) cannot leak into the
    other suites' measurements — jax locks the device count at first
    initialization, so one process cannot serve both. The caller runs it
    BEFORE its own process imports jax: a chip belongs to one process at a
    time, so the child must hold it alone and exit first."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    cmd = [sys.executable, "-m", "benchmarks.run", "--only", "serving",
           "--json", path, "--host-devices", str(args.host_devices)]
    if args.quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO_ROOT,
                       env={**os.environ})
    if r.returncode != 0:
        raise RuntimeError(f"serving sub-bench failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    with open(path) as f:
        rows = json.load(f)["serving"]
    os.unlink(path)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", help="comma-separated suite names")
    ap.add_argument("--json", dest="json_path", metavar="PATH",
                    help="write {suite: [rows]} JSON next to the CSV")
    ap.add_argument("--host-devices", type=int, default=8,
                    help="virtual host devices for the sharded serving "
                         "bench (must be set before jax initializes)")
    ap.add_argument("--check", action="store_true",
                    help="compare the run against the committed perf "
                         "baselines (BENCH_serving.json / "
                         "BENCH_kernels.json at the repo root) and fail "
                         f"on a > {CHECK_TOLERANCE}x regression of any "
                         "gated metric. Baselines are read BEFORE the run "
                         "writes --json, so the same paths may be reused.")
    args = ap.parse_args()

    baselines = {}
    if args.check:
        # read the committed baselines up front: --json may legitimately
        # point at the same files this run regenerates
        for fname in set(BASELINE_FILES.values()):
            path = os.path.join(REPO_ROOT, fname)
            if os.path.exists(path):
                with open(path) as f:
                    baselines[fname] = json.load(f)

    only = set(args.only.split(",")) if args.only else None
    # the serving suite compares the sharded engine against single-device
    # on a multi-device topology. When it is the ONLY suite, fix the
    # virtual device count in-process (appending — never clobbering — any
    # pre-existing XLA_FLAGS) BEFORE anything imports jax; when it runs
    # alongside other suites it goes to a child process, run to completion
    # before this process imports jax, so every other row keeps the
    # default topology and only one process at a time holds the device.
    serving_in_proc = only == {"serving"}
    serving_rows = None
    if serving_in_proc and args.host_devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.host_devices}").strip()
    elif only is None or "serving" in only:
        serving_rows = _serving_in_subprocess(args)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks import bench_indexing, bench_kernels, bench_wcsd
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    suites = {
        "indexing": lambda: bench_wcsd.bench_indexing(
            datasets={"NY(s)": ("road", "NY(s)"),
                      "MV(s)": ("social", "MV(s)")} if args.quick else None),
        "query": lambda: bench_wcsd.bench_query(
            n_queries=100 if args.quick else 400),
        "large_w": lambda: bench_wcsd.bench_large_w(
            n_levels=8 if args.quick else 20),
        "batched": bench_wcsd.bench_batched_builder,
        "index_build": lambda: bench_indexing.bench_build_paths(
            configs=bench_indexing.QUICK_CONFIGS if args.quick else None),
        "serving": (lambda: bench_wcsd.bench_serving(
            batch=1024 if args.quick else 4096)) if serving_in_proc
        else lambda: serving_rows,
        "label_store": lambda: bench_wcsd.bench_label_store(
            dataset="MV(s)" if args.quick else "SO(s)",
            n_queries=256 if args.quick else 2048),
        "kernel_query": bench_kernels.bench_query_kernel,
        "kernel_segmented": lambda: bench_kernels.bench_segmented_kernel(
            B=256 if args.quick else 2048, V=800 if args.quick else 4000),
        "kernel_cin": bench_kernels.bench_cin_traffic,
    }
    if only:
        unknown = only - suites.keys()
        if unknown:
            raise SystemExit(f"unknown suites: {sorted(unknown)}; "
                             f"available: {sorted(suites)}")
        suites = {k: v for k, v in suites.items() if k in only}
    results: dict[str, list] = {}
    print("table,dataset,algo,value")
    for name, fn in suites.items():
        rows = fn()
        validate_rows(name, rows)
        results[name] = rows
        for row in rows:
            print(f"{row['table']},{row['dataset']},{row['algo']},"
                  f"{row['value']:.6g}", flush=True)
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(results, f, indent=1)
        print(f"# wrote {args.json_path} ({sum(map(len, results.values()))} "
              f"rows, {len(results)} suites)", file=sys.stderr)
    if args.check:
        failures = []
        checked = 0
        for suite, rows in results.items():
            fname = BASELINE_FILES.get(suite)
            if fname is None:
                continue
            base = baselines.get(fname, {}).get(suite)
            if base is None and CHECK_GATES.get(suite):
                # a gated suite without committed baseline rows must not
                # silently pass — the gate would rot open
                failures.append(f"{suite}: no committed baseline rows in "
                                f"{fname}; seed them with --json {fname}")
            checked += 1
            # floors are baseline-independent: they apply to the fresh
            # rows even when no baseline exists yet
            failures += check_against_baseline(suite, rows, base or [])
        print(f"# --check: {checked} suites vs committed baselines, "
              f"{len(failures)} regressions", file=sys.stderr)
        if failures:
            for f_ in failures:
                print(f"REGRESSION: {f_}", file=sys.stderr)
            raise SystemExit(1)


if __name__ == "__main__":
    main()
