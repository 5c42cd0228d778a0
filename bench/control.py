"""The control of the correctness check: put the reference with its
quality guarantee broken (one level too loose, `Reference.control`) in the
program's place, on a cell's own window traffic and at its own size, and
run the harness's own check (`check.judge`) over what it answered.

    python bench/control.py --workload road-uniform --seeds 1,2,3

Every request of the window is answered by the control, stamped as a
device answer delivered on time, and judged with the run's own sample: a
point request with the loosened reference at its level, a profile request
with it at every level 0..W. Prints one JSON line per seed: ``correct``
(which has to read false) and each check's number. The benchmark's own
runs never run this; the reading sets the upper end of the ``wrong`` and
``profile_wrong`` checks' limits (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def control_run(cell, seed: int, seconds: float) -> dict:
    """Judge the control's answers to the window's requests of one run."""
    import numpy as np

    from harness import graphs
    from harness.check import duplicates, judge
    from harness.drive import Requests
    from harness.reference import Reference
    from harness.traffic import POINT, PairSource, open_schedule, rng_for

    ref = Reference(graphs.make_graph(cell.config))
    rng = rng_for(seed, "window")
    due = open_schedule(cell.mix, seconds, rng)
    n = len(due)
    src = PairSource(cell.mix, ref.V, ref.num_levels, seed)
    req = Requests.empty(*src.draw(rng, n),
                         src.kinds(rng_for(seed, "kind", "window"), n),
                         ref.num_levels)
    req.n = n
    req.due = req.submit = due
    req.deliver = due + 1e-3
    point = req.kind == POINT
    req.answer[point] = ref.control(req.s[point], req.t[point],
                                    req.w[point])
    if req.profile is not None:
        levels = ref.num_levels + 1
        req.profile[~point] = ref.control(
            np.repeat(req.s[~point], levels),
            np.repeat(req.t[~point], levels),
            np.tile(np.arange(levels), int((~point).sum()))
        ).reshape(-1, levels)
    dup = duplicates(req.s, req.t, req.w, req.submit, req.deliver, ref.V,
                     ref.num_levels, req.kind)
    work = np.ones(n, np.int64)
    checks, counts = judge(req, n, ref, dup, work, seed,
                           {"mode": "primary", "retries": 0,
                            "demotions": 0})
    return {"seed": seed, "requests": n,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: c.as_json() for c in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    from harness.spec import Bench
    cell = Bench(CHECKOUT).cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(dict(control_run(cell, seed, args.seconds),
                              workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
