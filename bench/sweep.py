"""Find the knee of a cell: the highest offered rate at which
the backlog does not grow through the window.

    python bench/sweep.py --workload road-uniform --seed 7 \
        --rates 10000,20000,40000 --seconds 8

Stands the cell's server up once, warms it with the cell's own traffic,
then offers each rate for ``--seconds`` in turn (the mix's pairs and
levels, Poisson arrivals at that rate). For each rate it prints one JSON
line: the median latency of the first and of the last quarter of the
requests, p99, how late the generator ran, and the requests still
unanswered at the close. A backlog that grows shows as a last quarter far
slower than the first. Run it on the chip; the rate found goes into the
mix's ``rate_per_s``, at four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    sys.path.insert(0, BENCH_DIR)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np

    from harness import drive
    from harness.cell_run import drive_mix, stand_up, warm_up
    from harness.spec import Bench
    from harness.traffic import rng_for

    if jax.default_backend() != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 3
    bench = Bench(CHECKOUT)
    cell = bench.cell(args.workload)
    srv, src, rows = stand_up(bench, cell, args.seed,
                              jax.devices()[:cell.chips])
    counter = drive.CompileCounter()
    warm_up(srv, src, args.seed, cell.mix, counter, rows)
    rng = rng_for(args.seed, "window")
    kinds = rng_for(args.seed, "kind", "window")
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        lowered = counter.lowered
        batches = srv.stats.batches
        req, start = drive_mix(srv, mix, src, rng, args.seconds,
                              drive.HostClock(), kinds=kinds)
        lat = (req.deliver - req.due) * 1e3
        q = len(lat) // 4
        close = start + args.seconds
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "p50_first_q_ms": float(np.median(lat[:q])),
            "p50_last_q_ms": float(np.median(lat[-q:])),
            "p99_ms": float(np.percentile(lat, 99)),
            "gen_lag_p99_ms": float(np.percentile(
                (req.submit - req.due) * 1e3, 99)),
            "open_at_close": int(np.count_nonzero(req.deliver > close)),
            "flushes": srv.stats.batches - batches,
            "lowered": counter.lowered - lowered}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
