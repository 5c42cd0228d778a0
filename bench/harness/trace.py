"""Reduce one profiler trace of the measured window to device numbers.

`load_events` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps only what the reduction needs: for each device plane, the events of
its ``XLA Ops`` line (name, start, duration), and the start of the
benchmark's ``bench.window`` anchor annotation, which puts the harness's
own host spans on the trace's clock. `reduce` turns that into, per
device, busy seconds (the union of op intervals inside the traced window),
seconds per op name, and the idle gaps attributed to what the host was
doing in them.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os

ANCHOR = "bench.window"
OPS_LINE = "XLA Ops"


def load_events(trace_dir: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "anchor_ns": float | None}`` from the newest trace under trace_dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, anchor = {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [[op_name(ev.name), ev.start_ns,
                                            ev.duration_ns]
                                           for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns
    return {"devices": devices, "anchor_ns": anchor}


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each op event by the
    whole instruction text (``%wcsd_query_ragged.1 = s32[...] custom-call(
    ...)``); keep ``wcsd_query_ragged.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged [start, end) intervals of a list of (start, end)."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: dict[str, float]               # device -> busy seconds
    op_s: dict[str, dict[str, float]]      # device -> op name -> seconds
    idle_by_host: dict[str, float]         # host activity -> idle seconds
    longest_gaps: list[tuple[str, float]]  # (main host activity, seconds)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def op_seconds(self, match) -> float:
        """Seconds of every op whose name ``match(name)`` accepts, summed
        over devices."""
        return sum(s for ops in self.op_s.values()
                   for name, s in ops.items() if match(name))

    def top_ops(self, k: int = 10) -> list[list]:
        tot = {}
        for ops in self.op_s.values():
            for name, s in ops.items():
                tot[name] = tot.get(name, 0.0) + s
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
                ][:k]


def _attribute(gap, spans, starts) -> dict[str, float]:
    """Split the gap [a, b) by what the host was doing in it: the server
    call each span times, and "client" (the harness's own loop: drawing,
    waiting for due times) for the part no span covers. Spans come from
    one thread and never overlap."""
    import bisect
    a, b = gap
    over = {}
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(spans) and spans[i][1] < b:
        kind, s0, s1 = spans[i]
        o = min(b, s1) - max(a, s0)
        if o > 0:
            over[kind] = over.get(kind, 0.0) + o
        i += 1
    rest = (b - a) - sum(over.values())
    if rest > 0:
        over["client"] = rest
    return over


def reduce(events: dict, lo_ns: float, hi_ns: float,
           host_spans=(), span_offset_ns: float = 0.0) -> TraceSummary:
    """Busy, per-op and idle numbers inside [lo_ns, hi_ns) on the trace's
    clock. ``host_spans`` are (kind, start_ns, end_ns) on the harness's
    clock; ``span_offset_ns`` moves them onto the trace's."""
    spans = sorted(((k, a + span_offset_ns, b + span_offset_ns)
                    for k, a, b in host_spans), key=lambda x: x[1])
    starts = [s[1] for s in spans]
    busy, ops, idle, gaps = {}, {}, {}, []
    for dev, evs in sorted(events["devices"].items()):
        ivs, per = [], {}
        for name, start, dur in evs:
            a, b = max(start, lo_ns), min(start + dur, hi_ns)
            if b <= a:
                continue
            ivs.append((a, b))
            per[name] = per.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(ivs)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        ops[dev] = per
        edges = [lo_ns] + [x for iv in merged for x in iv] + [hi_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                split = _attribute((a, b), spans, starts)
                for kind, ns in split.items():
                    idle[kind] = idle.get(kind, 0.0) + ns * 1e-9
                top = max(split.items(), key=lambda x: x[1])[0]
                gaps.append((top, (b - a) * 1e-9))
    gaps.sort(key=lambda x: -x[1])
    return TraceSummary(window_s=(hi_ns - lo_ns) * 1e-9, busy_s=busy,
                        op_s=ops, idle_by_host=idle, longest_gaps=gaps[:10])
