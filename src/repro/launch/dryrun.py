import os
import sys

# The XLA_FLAGS line MUST run before any other import (including repro.*):
# jax locks the device count at first initialization. The compile matrix
# wants the full 512-chip virtual topology; --serve and --chaos actually
# EXECUTE the serving stack, so they run on 8 virtual host devices instead.
_N_DEV = "8" if ("--serve" in sys.argv or "--chaos" in sys.argv) else "512"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_N_DEV}"

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes — (16,16)=256 chips single-pod and
(2,16,16)=512 chips multi-pod — and record memory/cost/collective analysis
for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all          # full 40-cell matrix x 2
                                               # meshes, one subprocess per
                                               # cell (bounds compile RAM)
  python -m repro.launch.dryrun --serve        # run the sharded WCSD
                                               # serving stack end-to-end
                                               # on 8 virtual host devices
  python -m repro.launch.dryrun --chaos        # seeded fault-injection
                                               # schedule (docs/resilience
                                               # .md) across engine modes
"""


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str) -> dict:
    import jax
    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch import hlo_analysis

    mod = get_arch(arch)
    cell = mod.make_cell(shape, multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape, "kind": cell.kind,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips,
           "meta": {k: v for k, v in cell.meta.items()}}
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
    rec.update(
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        memory=dict(
            argument_bytes=int(ma.argument_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            peak_bytes=int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        ),
        xla_cost=dict(flops=float(ca.get("flops", 0.0)),
                      bytes_accessed=float(ca.get("bytes accessed", 0.0))),
        hlo=hlo_analysis.analyze(txt, default_group=16),
        hlo_chars=len(txt),
    )
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape}__{rec['mesh'].replace('x', '-')}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def run_serve(quick: bool) -> None:
    """Execute (not just compile) the sharded serving stack on the virtual
    host devices: every engine mode vs the single-device engine, bit for
    bit, on differential-harness-style instances, plus an async-flush
    `WCSDServer` epoch over the sharded backend."""
    import numpy as np
    import jax
    from repro.configs.wcsd_serve import smoke_serve_config
    from repro.core.generators import erdos_renyi, random_queries
    from repro.core.query import DeviceQueryEngine, ShardedQueryEngine
    from repro.core.serve import WCSDServer
    from repro.core.wc_index import build_wc_index
    from repro.launch.mesh import make_serving_mesh

    n_dev = len(jax.devices())
    assert n_dev >= 8, f"expected >= 8 virtual devices, got {n_dev}"
    cfg = smoke_serve_config()
    instances = [(12, 3.5, 3, 5), (10, 2.5, 2, 11)] if quick else \
        [(12, 3.5, 3, 5), (10, 2.5, 2, 11), (60, 4.0, 4, 7),
         (120, 3.0, 5, 13)]
    t0 = time.time()
    for V, deg, W, seed in instances:
        g = erdos_renyi(V, deg, num_levels=W, seed=seed)
        idx = build_wc_index(g)
        if V <= 16:  # full (s, t, w) grid on the tiny instances
            s, t, wl = np.meshgrid(np.arange(V), np.arange(V),
                                   np.arange(W + 1), indexing="ij")
            s, t, wl = (a.ravel().astype(np.int32) for a in (s, t, wl))
        else:
            s, t, wl = random_queries(g, 512, seed=seed + 1)
        for layout, dispatch in (("csr", "ragged"), ("csr", "bucket_pair"),
                                 ("padded", "ragged")):
            # every layout x dispatch x placement combo; dispatch only
            # differentiates the csr layout (the ragged megakernel vs the
            # bucket-pair oracle loop)
            dev_eng = DeviceQueryEngine(
                idx, layout=layout, use_pallas=cfg.use_pallas,
                interpret=cfg.interpret, dispatch=dispatch)
            exp = np.asarray(dev_eng.query(s, t, wl))
            # profile expectation: the per-level loop the one-pass replaces
            exp_prof = np.stack(
                [np.asarray(dev_eng.query(
                    s, t, np.full(len(s), w, np.int32)))
                 for w in range(W + 1)], axis=1)
            if not np.array_equal(np.asarray(dev_eng.query_profile(s, t)),
                                  exp_prof):
                raise SystemExit(f"MISMATCH V={V} layout={layout} "
                                 "device profile vs per-level loop")
            # the compressed arena rides the csr-ragged legs only (it is
            # the megakernel's format); hop distances here stay below the
            # bf16 exact-integer range, so even compressed answers are
            # bit-identical to the uncompressed expectation
            comp_legs = ((False, True) if (layout, dispatch)
                         == ("csr", "ragged") else (False,))
            for multi_pod in (False, True):
                mesh = make_serving_mesh(multi_pod=multi_pod)
                for budget in (None, 1):  # replicated / sharded_labels
                    for compressed in comp_legs:
                        eng = ShardedQueryEngine(
                            idx, mesh=mesh, layout=layout,
                            use_pallas=cfg.use_pallas,
                            interpret=cfg.interpret,
                            device_budget_bytes=budget, dispatch=dispatch,
                            compressed=compressed)
                        got = np.asarray(eng.query(s, t, wl))
                        tag = (f"V={V} layout={layout} "
                               f"dispatch={eng.dispatch} "
                               f"mesh={'2x4' if multi_pod else '8'} "
                               f"mode={eng.mode}"
                               + (" compressed" if eng.compressed else ""))
                        if not np.array_equal(got, exp):
                            raise SystemExit(
                                f"MISMATCH {tag}: "
                                f"{np.flatnonzero(got != exp)[:8]}")
                        got_prof = np.asarray(eng.query_profile(s, t))
                        if not np.array_equal(got_prof, exp_prof):
                            raise SystemExit(f"MISMATCH profile {tag}")
                        print(f"OK {tag}: {len(s)} queries + profiles "
                              "bit-identical", flush=True)
        # async double-buffered server over the sharded backend
        srv = WCSDServer(idx, mesh=make_serving_mesh(),
                         **{**cfg.server_kwargs(), "max_batch": 64})
        got = srv.query_many(s, t, wl)
        if not np.array_equal(got, exp):
            raise SystemExit(f"MISMATCH async server V={V}")
        assert not srv.results, "read-once delivery left results behind"
        if not np.array_equal(srv.query_profile_many(s, t), exp_prof):
            raise SystemExit(f"MISMATCH async server profiles V={V}")
        assert not srv.profile_results, "profile read-once left results"
        print(f"OK V={V} async server (+profiles): {srv.stats.batches} "
              f"batches, {srv.stats.memo_hits} memo hits", flush=True)
        # continuous-batching epoch: deadline + opportunistic flushes on,
        # same stream of submissions, answers identical to the epoch-flush
        # server (docs/serving.md §1a)
        srv_cb = WCSDServer(idx, mesh=make_serving_mesh(),
                            **{**cfg.server_kwargs(), "max_batch": 64,
                               "max_wait_us": 200.0, "min_batch": 4})
        srv_cb.tracer.start()
        rids = [srv_cb.submit(int(a), int(b), int(c))
                for a, b, c in zip(s, t, wl)]
        srv_cb.flush()
        got = np.array([srv_cb.result(r) for r in rids], dtype=np.int32)
        if not np.array_equal(got, exp):
            raise SystemExit(f"MISMATCH continuous-batching server V={V}")
        lat = srv_cb.latency_summary()
        st = srv_cb.stats
        print(f"OK V={V} continuous batching: {st.batches} batches "
              f"({st.opportunistic_flushes} opportunistic, "
              f"{st.deadline_flushes} deadline), p50 {lat['p50_us']:.0f}us "
              f"p99 {lat['p99_us']:.0f}us", flush=True)
    print(f"serve dryrun PASS on {n_dev} virtual devices "
          f"({time.time() - t0:.1f}s)")


def run_chaos(quick: bool) -> None:
    """Seeded chaos schedules (docs/resilience.md §6) over several engine
    configurations: injected engine raises / flush hangs / bit-flips /
    torn WAL tails plus one mid-`apply_updates` crash with a WAL-replay
    warm restart — every answer differentially checked against the BFS
    oracle, server back in its top mode at the end."""
    import tempfile

    import jax
    from repro.checkpoint.fault import run_chaos_schedule
    from repro.launch.mesh import make_serving_mesh

    n_dev = len(jax.devices())
    assert n_dev >= 8, f"expected >= 8 virtual devices, got {n_dev}"
    # (tag, steps, seed, crash_step, server_kwargs-overrides)
    legs = [("csr-ragged-device", 200, 3, 100, {}),
            ("csr-ragged-sharded", 120 if quick else 200, 7, 60, {
                "backend": "sharded", "mesh": make_serving_mesh()})]
    if not quick:
        legs += [("compressed-sharded", 200, 11, 110, {
                     "backend": "sharded", "mesh": make_serving_mesh(),
                     "compressed": True}),
                 # pallas-interpret primary so the ladder has a real
                 # pure-jnp oracle rung below it (a padded no-pallas
                 # primary IS the oracle — one rung, nothing to demote to)
                 ("padded-single", 200, 13, 90, {
                     "layout": "padded", "use_pallas": True,
                     "interpret": True})]
    if quick:
        legs[0] = ("csr-ragged-device", 120, 3, 60, {})
    t0 = time.time()
    for tag, steps, seed, crash_step, overrides in legs:
        with tempfile.TemporaryDirectory() as tmp:
            s = run_chaos_schedule(server_kwargs=overrides, steps=steps,
                                   seed=seed, crash_step=crash_step,
                                   workdir=tmp)
        assert s["final_mode"] == "primary", s
        assert s["answered"] == s["submitted"], s
        assert s["injected"] > 0 and s["crashes"] == 1, s
        print(f"OK chaos {tag}: {s['submitted']} answered, "
              f"{s['injected']} faults injected "
              f"({s['error_retries']}err/{s['timeout_retries']}to retries, "
              f"{s['demotions']} demotions, {s['promotions']} promotions), "
              f"{s['replayed_records']} WAL records replayed, "
              f"final mode {s['final_mode']}", flush=True)
    print(f"chaos dryrun PASS on {n_dev} virtual devices "
          f"({time.time() - t0:.1f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.serve:
        run_serve(quick=args.quick)
        return

    if args.chaos:
        run_chaos(quick=args.quick)
        return

    if args.all:
        from repro.configs import ARCHS, get_arch
        jobs = []
        for arch in ARCHS:
            for shape in get_arch(arch).SHAPES:
                for mesh in (["single", "multi"] if args.mesh == "both"
                             else [args.mesh]):
                    jobs.append((arch, shape, mesh))
        failures = []
        for i, (arch, shape, mesh) in enumerate(jobs):
            mtag = "2-16-16" if mesh == "multi" else "16-16"
            fname = os.path.join(args.out, f"{arch}__{shape}__{mtag}.json")
            if args.skip_existing and os.path.exists(fname):
                print(f"[{i+1}/{len(jobs)}] skip {arch} {shape} {mesh}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--out", args.out]
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ})
            ok = r.returncode == 0
            print(f"[{i+1}/{len(jobs)}] {arch:18s} {shape:14s} {mesh:6s} "
                  f"{'OK' if ok else 'FAIL'} {time.time()-t0:6.1f}s",
                  flush=True)
            if not ok:
                failures.append((arch, shape, mesh))
                print(r.stdout[-2000:])
                print(r.stderr[-4000:])
        print(f"done: {len(jobs) - len(failures)}/{len(jobs)} OK")
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        return

    for mesh in (["single", "multi"] if args.mesh == "both"
                 else [args.mesh]):
        try:
            rec = run_cell(args.arch, args.shape, mesh == "multi", args.out)
            m = rec["memory"]
            print(f"{rec['arch']} {rec['shape']} {rec['mesh']}: compile "
                  f"{rec['compile_s']}s peak/device "
                  f"{m['peak_bytes']/2**30:.2f} GiB, hlo_flops "
                  f"{rec['hlo']['flops']:.3e}, coll "
                  f"{rec['hlo']['collective_bytes_total']/2**20:.1f} MiB")
        except Exception:
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
