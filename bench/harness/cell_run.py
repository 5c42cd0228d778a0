"""One run of one cell: set up, warm up, measure one window, check, report.

The order is fixed. Set-up loads (or, once per checkout, builds) the
index, stands the production server up on the cell's chips and warms
every shape the cell's traffic flushes. The window drives the server with
the cell's own traffic. Then the device memory peak is read, the server
is freed, and only then does the reference check what the window
delivered.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import time

import numpy as np

from . import drive, graphs, index_cache, trace
from .check import duplicates, judge
from .reference import Reference
from .traffic import PROFILE, PairSource, open_schedule, rng_for

WARM_TRAFFIC_S = 4.0     # the cell's own traffic before the window
WARM_SLICE_S = 1.0       # further slices while they still compile
WARM_SLICES_MAX = 6
TRACE_S = 4.0            # a --trace 1 run traces the window's last seconds


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric reader sees. Times are seconds from the window's open."""
    seconds: float
    setup_s: float
    n: int
    s: np.ndarray
    t: np.ndarray
    due: np.ndarray
    submit: np.ndarray
    deliver: np.ndarray
    memo: np.ndarray          # answered from the memo
    dup: np.ndarray           # rode an identical request's batch slot
    stats: dict               # ServeStats over the window
    host_s: dict              # seconds inside submit / poll / result
    row_entries: np.ndarray   # label entries per vertex
    peaks: dict | None
    trace: object | None      # trace.TraceSummary of [trace_from, seconds)
    trace_from: float
    kind: np.ndarray | None = None   # POINT or PROFILE; None: all points


def _stats(srv) -> dict:
    return dict(dataclasses.asdict(srv.stats))


def warm_up(srv, src, seed, mix, counter, row_entries) -> None:
    """Every flush shape the traffic can give, then the cell's own traffic
    until a slice lowers nothing new (and the memo is at steady state)."""
    from repro.core.wc_index import LANE
    rng = rng_for(seed, "warmup")
    kinds = rng_for(seed, "kind", "warmup")
    tile_cnt = np.maximum(-(-row_entries // LANE), 1)
    sent = drive.warm_shapes(srv, src, rng, tile_cnt, kinds)
    log(f"warm-up: {sent} requests over every flush shape, "
        f"{counter.lowered} programs lowered, {counter.compiled} compiled")
    slices = [WARM_TRAFFIC_S] + [WARM_SLICE_S] * WARM_SLICES_MAX
    for k, secs in enumerate(slices):
        before = counter.lowered
        drive_mix(srv, mix, src, rng, secs, drive.HostClock(), kinds=kinds)
        if k and counter.lowered == before:
            break
    log(f"warm-up: traffic {WARM_TRAFFIC_S + WARM_SLICE_S * k:.0f} s, "
        f"{counter.lowered} programs lowered, {counter.compiled} compiled, "
        f"memo {len(srv.memo)} entries")


def drive_mix(srv, mix, src, rng, seconds, clock,
              hook=(float("inf"), None), kinds=None):
    """Drive the mix for ``seconds``; returns the Requests and the time
    the window opened. Requests are drawn before it opens, their kinds
    from ``kinds`` (the kind stream; a mix without profiles needs
    none)."""
    offsets = open_schedule(mix, seconds, rng)
    n = len(offsets)
    req = drive.Requests.empty(*src.draw(rng, n), src.kinds(kinds, n), src.W)
    start = drive.pc() + 0.005
    req.due = start + offsets
    drive.run_open(srv, req, start + seconds, clock, hook)
    return req, start


def stand_up(bench, cell, seed: int, devices):
    """Load (once per checkout: build) the configuration's index and stand
    the production server up on ``devices``. Returns (server, pair source,
    label entries per vertex)."""
    from repro.configs.wcsd_serve import serve_config
    from repro.core.serve import WCSDServer
    from repro.launch.mesh import make_serving_mesh

    cfg = cell.config
    build_s = index_cache.ensure_index(bench.checkout, bench.bench_dir, cfg)
    t0 = time.perf_counter()
    idx = index_cache.load_index(bench.checkout, bench.bench_dir, cfg)
    log(f"index: {'built' if build_s else 'loaded'} {cfg['name']} graph "
        f"seed {cfg['graph_seed']}: {idx.size_entries()} label entries; "
        f"build {build_s:.1f} s, load {time.perf_counter() - t0:.2f} s")
    offsets = np.asarray(idx.labels.offsets)
    kwargs = serve_config().server_kwargs()
    kwargs.update(cfg.get("serve", {}))
    srv = WCSDServer(idx, mesh=make_serving_mesh(devices), **kwargs)
    src = PairSource(cell.mix, idx.num_nodes, idx.num_levels, seed)
    return srv, src, offsets[1:] - offsets[:-1]


def run_cell(bench, cell, seed: int, seconds: float, traced: bool, t_start,
             devices, platform: str) -> dict:
    import jax

    cfg, mix = cell.config, cell.mix
    srv, src, row_entries = stand_up(bench, cell, seed, devices)
    counter = drive.CompileCounter()
    warm_up(srv, src, seed, mix, counter, row_entries)
    # what set-up built stays for the run: keep it out of the collector's
    # full passes, as a long-running server does after start-up
    gc.collect()
    gc.freeze()

    # ---------------------------------------------------------- the window
    clock = drive.HostClock()
    before = _stats(srv)
    lowered, compiled = counter.lowered, counter.compiled
    trace_dir = os.path.join(bench.bench_dir, ".cache", "trace", cell.name)
    marks = {}

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python call tracing (every call of the server would be an
        # event); host level 1 keeps the anchor annotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks["anchor"] = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.ANCHOR):
            pass
        clock.span_from = marks["anchor"]

    rng = rng_for(seed, "window")
    hook_t = (time.perf_counter() + max(seconds - TRACE_S, 0.0) if traced
              else float("inf"))
    req, open_at = drive_mix(srv, mix, src, rng, seconds, clock,
                            (hook_t, start_trace),
                            kinds=rng_for(seed, "kind", "window"))
    setup_s = open_at - t_start
    close = open_at + seconds
    if traced:
        jax.profiler.stop_trace()
    after = _stats(srv)
    in_window = {"lowered": counter.lowered - lowered,
                 "compiled": counter.compiled - compiled}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    server = {"mode": srv.mode,
              "retries": (after["error_retries"] + after["timeout_retries"]
                          + after["exhausted"]),
              "demotions": after["demotions"]}
    log(f"window: {req.n} requests in {seconds:g} s; "
        f"{after['batches'] - before['batches']} flushes (largest "
        f"{after['max_batch']}); programs lowered in the window "
        f"{in_window['lowered']}, compiled {in_window['compiled']}")
    if req.profile is not None:
        log(f"window: {after['profile_requests'] - before['profile_requests']}"
            " of the requests are profiles")

    summary = None
    if traced:
        events = trace.load_events(trace_dir)
        if events["anchor_ns"] is not None and events["devices"]:
            off = events["anchor_ns"] - marks["anchor"] * 1e9
            summary = trace.reduce(events, events["anchor_ns"],
                                   close * 1e9 + off, clock.spans, off)
        shutil.rmtree(trace_dir, ignore_errors=True)
    del srv
    gc.collect()

    # --------------------------------------------------- correctness check
    n = req.n
    edges = graphs.make_graph(cfg)
    ref = Reference(edges)
    dup = duplicates(req.s[:n], req.t[:n], req.w[:n], req.submit[:n],
                     req.deliver[:n], ref.V, ref.num_levels, req.kind[:n])
    work = (row_entries[req.s[:n]].astype(np.int64)
            * row_entries[req.t[:n]])
    t0 = time.perf_counter()
    checks, counts = judge(req, n, ref, dup, work, seed, server)
    log(f"check: {sum(counts.values())} answers against the reference "
        f"({counts['device']} device, {counts['memo']} memo, "
        f"{counts['dup']} duplicate) in {time.perf_counter() - t0:.1f} s")
    if "profile_device" in counts:
        nprof = int(np.sum(req.kind[:n] == PROFILE))
        log(f"check: of them staircases of {nprof} profile requests: "
            f"{counts['profile_device']} device, {counts['profile_memo']} "
            f"memo, {counts['profile_dup']} duplicate")

    peaks = None
    if platform == "tpu":
        peaks = bench.peaks(devices[0].device_kind)
    delta = {k: after[k] - before[k] for k in after
             if isinstance(after[k], (int, float))}
    rel = lambda a: a[:n] - open_at  # noqa: E731
    run = Run(seconds=seconds, setup_s=setup_s, n=n,
              s=req.s[:n], t=req.t[:n], due=rel(req.due),
              submit=rel(req.submit),
              deliver=rel(req.deliver), memo=req.mode[:n] == 1, dup=dup,
              stats=delta, host_s=dict(clock.seconds), row_entries=row_entries,
              peaks=peaks, trace=summary,
              trace_from=(marks["anchor"] - open_at) if traced else seconds,
              kind=req.kind[:n])
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": all(c.ok for c in checks), "attempted": n,
           "failed": int(np.isnan(req.deliver[:n]).sum()),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": summary.top_ops(10),
            "idle_gaps": sorted(([k, v] for k, v in
                                 summary.idle_by_host.items()),
                                key=lambda x: -x[1])[:10]}
    out["checks"] = {c.name: c.as_json() for c in checks}
    return out, checks
