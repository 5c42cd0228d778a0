"""Split the device trace's idle time by the server's own spans.

The server's tracer (``WCSDServer.tracer``, core/tracing.py) stamps its
spans with ``time.perf_counter_ns``, the clock of the harness's host
spans, so the offset `trace.reduce` takes (``bench.window`` anchor minus
its ``perf_counter`` reading) moves them onto the trace's clock too.
`idle_by_span` reads the events `trace.load_events` returns and the
``spans`` table of a tracer snapshot; it leaves `trace.reduce` and its
readers as they are.
"""
from __future__ import annotations

import bisect

from .trace import _union

# innermost first: a gap inside engine.build is the build's, not the
# launch's or the flush's
NESTING = ("engine.build", "engine.plan", "engine.launch", "flush.stage",
           "drain.wait", "drain.deliver")
NONE = "none"


def _segments(spans: dict, names, offset_ns: float):
    """Disjoint (start, end, name) pieces of the spans' union, each
    labelled with the innermost span open over it, in time order."""
    edges = []
    for k, a, b in zip(spans["name"], spans["start_ns"], spans["end_ns"]):
        if b > a:
            edges.append((a + offset_ns, 1, names[int(k)]))
            edges.append((b + offset_ns, -1, names[int(k)]))
    edges.sort(key=lambda e: e[0])
    open_ = dict.fromkeys(NESTING, 0)
    segs, prev = [], None
    for t, step, name in edges:
        if prev is not None and t > prev:
            inner = next((n for n in NESTING if open_[n]), None)
            if inner is not None:
                if segs and segs[-1][2] == inner and segs[-1][1] == prev:
                    segs[-1] = (segs[-1][0], t, inner)
                else:
                    segs.append((prev, t, inner))
        open_[name] += step
        prev = t
    return segs


def idle_by_span(events: dict, lo_ns: float, hi_ns: float, snap: dict,
                 offset_ns: float = 0.0) -> dict[str, float]:
    """Seconds of device idle time inside [lo_ns, hi_ns) of the trace's
    clock, summed over devices, under each program span (the innermost
    one open), and under none (``"none"``). The values add up to the
    idle time `trace.reduce` finds in the same window. ``snap`` is a
    tracer snapshot on the program's clock; ``offset_ns`` moves it onto
    the trace's."""
    segs = _segments(snap["spans"], snap["span_names"], offset_ns)
    starts = [s[0] for s in segs]
    out = dict.fromkeys(NESTING + (NONE,), 0.0)
    for evs in events["devices"].values():
        merged = _union([(max(a, lo_ns), min(a + d, hi_ns))
                         for _, a, d in evs
                         if min(a + d, hi_ns) > max(a, lo_ns)])
        edges = [lo_ns] + [x for iv in merged for x in iv] + [hi_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(segs) and segs[i][0] < b:
                s0, s1, name = segs[i]
                over = min(b, s1) - max(a, s0)
                if over > 0:
                    out[name] += over
                    covered += over
                i += 1
            out[NONE] += (b - a) - covered
    return {k: v * 1e-9 for k, v in out.items()}
