"""The device's idle time in the traced window, charged to the stage of the
serve loop that the serving thread was in.

The engine (``serving/executor.py``) records profiler spans on the thread
that runs ``MergeAwareEngine.serve``, which is the thread that holds the
window (``trace.load`` keeps its spans).  They nest: ``engine.serve`` holds
``engine.poll`` sleeps and ``engine.microbatch`` spans, and four stages tile
each micro-batch, in order: ``engine.pad``, ``engine.dispatch``,
``engine.wait``, ``engine.complete``.

Each idle interval of the first device is split exactly at the spans'
boundaries, and each piece is charged to the innermost span over it.  The
names are fixed here: a span the program renames reads as absent, so its
metrics fall to None instead of following the new name unseen.
"""
from __future__ import annotations

from collections import defaultdict

from bench import trace

SERVE = "engine.serve"
SPANS = (SERVE, "engine.poll", "engine.microbatch", "engine.pad",
         "engine.dispatch", "engine.wait", "engine.complete")
# per-layer metric -> the spans whose innermost idle time it counts
STAGES = {
    "prep": ("engine.pad", "engine.dispatch"),
    "wait": ("engine.wait",),
    "complete": ("engine.complete",),
    "loop": (SERVE, "engine.poll", "engine.microbatch"),
}


def idle_intervals(tr: trace.Trace) -> list:
    """Disjoint (start, end) intervals of the window in which the first
    device ran nothing."""
    lo, hi = tr.window
    busy = trace.union((max(e.start_ns, lo), min(e.end_ns, hi))
                       for e in tr.devices[0]
                       if e.end_ns > lo and e.start_ns < hi)
    gaps, cur = [], lo
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def innermost(spans) -> list:
    """Disjoint (start, end, name) segments, in time order, each named for
    the innermost of the nested ``spans`` (``trace.Event``) over it; time
    under no span has no segment."""
    segs: list = []
    stack: list = []  # open spans, outermost first
    cur = None

    def emit(until, name):
        if cur is not None and until > cur:
            segs.append((cur, until, name))

    for sp in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= sp.start_ns:
            top = stack.pop()
            emit(top.end_ns, top.name)
            cur = max(cur, top.end_ns)
        if stack:
            emit(sp.start_ns, stack[-1].name)
        cur = sp.start_ns if cur is None else max(cur, sp.start_ns)
        stack.append(sp)
    while stack:
        top = stack.pop()
        emit(top.end_ns, top.name)
        cur = max(cur, top.end_ns)
    return segs


def idle_by_span(tr: trace.Trace) -> dict:
    """Seconds of the window's device idle time under each program span,
    each piece charged to the innermost span over it.  None where the trace
    has no device plane or no ``engine.serve`` span."""
    spans = [e for e in tr.host if e.name in SPANS and e.dur_ns > 0]
    if not tr.devices or not any(e.name == SERVE for e in spans):
        return None
    segs = innermost(spans)
    tot: dict = defaultdict(float)
    j = 0
    for s, t in idle_intervals(tr):  # both in time order: one sweep
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < t:
            a, b, name = segs[k]
            tot[name] += (min(b, t) - max(a, s)) / 1e9
            k += 1
    return dict(tot)


def idle_share(ctx, stage: str):
    """Percent of the traced window in which the device was idle while the
    serving thread's innermost span was one of ``STAGES[stage]``."""
    if ctx.trace is None:
        return None
    by_span = idle_by_span(ctx.trace)
    if by_span is None:
        return None
    return 100.0 * sum(by_span.get(n, 0.0) for n in STAGES[stage]) / ctx.trace.window_s
