"""The program's own spans (``repro.utils.spans``) on the device trace's clock.

The program stamps its spans with ``time.perf_counter``; the trace keeps
its events on the profiler's clock.  The benchmark's own ``admit`` and
``step`` spans stand on both: as ``(kind, t0, t1, segments)`` in
``run.records["steps"]`` (perf_counter seconds) and as profiler
annotations in ``run.trace_result.spans`` (trace nanoseconds).  Matching
the two lists gives the offset between the clocks; the spread of the
per-span offsets is the residual of the mapping.

A program without the recorder, or whose recorder pushed out spans of the
window, gives nothing to read: every function here then returns ``None``.
"""

from __future__ import annotations

from bench import trace as tr

#: the benchmark's spans that stand on both clocks
_BOTH = ("admit", "step")


def recorded():
    """``(spans, dropped)`` of the program's recorder, or ``None`` where
    the program has none."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans.spans(), spans.dropped()


def kept(run, names) -> list | None:
    """The program's spans called one of ``names``, ``[(name, t0, t1,
    attrs)]`` in perf_counter seconds; ``None`` without a recorder or when
    the bound may have pushed out a span that ends inside the window (the
    recorder keeps spans in order of their ends)."""
    rec = recorded()
    if rec is None or run.window is None:
        return None
    spans, dropped = rec
    if dropped and (not spans or spans[0][2] > run.window[0]):
        return None
    return [s for s in spans if s[0] in names]


def clock(run) -> tuple[float, float] | None:
    """``(offset_ns, residual_ns)``: trace time = perf_counter seconds x 1e9
    + offset, from the benchmark's spans on both clocks (midpoints); the
    residual is the spread (max - min) of the per-span offsets.  ``None``
    without a trace or when the two lists cannot be matched by kind."""
    trace, steps = run.trace_result, run.records.get("steps")
    if trace is None or not steps:
        return None
    traced = [s for s in trace.spans if s[0] in _BOTH]
    if not traced or len(traced) > len(steps):
        return None
    mid = [(s + e) / 2 for _, s, e, _ in traced]
    best = None
    # the traced calls are a run of consecutive calls: find where it
    # starts, keeping the start whose offsets spread least
    for k in range(len(steps) - len(traced) + 1):
        lo = hi = None
        for i, sp in enumerate(traced):
            kind, t0, t1, _ = steps[k + i]
            if kind != sp[0]:
                break
            off = mid[i] - (t0 + t1) / 2 * 1e9
            lo = off if lo is None else min(lo, off)
            hi = off if hi is None else max(hi, off)
            if best is not None and hi - lo >= best[1]:
                break
        else:
            if best is None or hi - lo < best[1]:
                best = ((lo + hi) / 2, hi - lo)
    return best


def on_trace(run, names) -> list | None:
    """The program's spans called one of ``names`` whose midpoint falls
    inside the traced calls, on the trace's clock and clipped to them:
    ``[(name, start_ns, end_ns, attrs)]``; ``None`` where there is nothing
    to map."""
    spans = kept(run, names)
    cl = clock(run)
    if spans is None or cl is None:
        return None
    off = cl[0]
    traced = [s for s in run.trace_result.spans if s[0] in _BOTH]
    lo, hi = traced[0][1], max(e for _, _, e, _ in traced)
    out = []
    for name, t0, t1, attrs in spans:
        s, e = round(t0 * 1e9 + off), round(t1 * 1e9 + off)
        if lo <= (s + e) // 2 < hi:
            out.append((name, max(s, lo), min(e, hi), attrs))
    return out


def idle_share(run, name: str) -> float | None:
    """Device idle seconds inside the program's ``name`` spans (mean over
    the devices) over the traced window's seconds, in percent."""
    trace = run.trace_result
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    spans = on_trace(run, (name,))
    if not spans:
        return None
    within = tr.union((s, e) for _, s, e, _ in spans)

    def idle_ns(dev):
        busy = tr.union((s, e) for _, s, e, k in trace.devices[dev] if k != "control")
        return tr.length(within) - tr.overlap(within, busy)

    idle_s = sum(idle_ns(d) for d in trace.devices) / len(trace.devices) / 1e9
    return 100.0 * idle_s / trace.window_s
