"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The cell (``BENCHMARK.json`` ``workloads``)
names a configuration and a traffic mix; ``--seed`` draws the traffic and
picks the graph of the configuration's pool. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number the correctness check compared beside its limit.
The same checks end standard error. Progress lines go to standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. The harness's own tests (``bench/tests``)
drive the same path on the CPU at tiny sizes, below this entry point.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def fail(code: int, msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
        return fail(2, f"no program under {CHECKOUT}/src/repro")
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    sys.path.insert(0, BENCH_DIR)
    from harness.spec import Bench
    bench = Bench(CHECKOUT)
    try:
        cell = bench.cell(args.workload)
    except KeyError as e:
        return fail(2, str(e))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        return fail(3, f"JAX found no TPU (backend {platform!r})")
    devices = jax.devices()
    if len(devices) < cell.chips:
        return fail(3, f"{args.workload} needs {cell.chips} chips, JAX sees "
                       f"{len(devices)}")
    devices = devices[:cell.chips]
    print(f"bench: {args.workload} seed {args.seed} on {cell.chips} x "
          f"{devices[0].device_kind}, compile cache {cache}",
          file=sys.stderr, flush=True)

    from harness.cell_run import run_cell
    out, checks = run_cell(bench, cell, args.seed, args.seconds,
                           bool(args.trace), T_START, devices, platform)
    for c in checks:
        print(f"check {c.name}: {c.value} "
              f"({'at least' if c.at_least else 'at most'} {c.limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
