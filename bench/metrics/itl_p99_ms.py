"""itl_p99_ms: 99th percentile of every gap between consecutive tokens of a
request, over all requests, whose later token the host saw in the window."""

from bench.stats import percentile


def read(run):
    toks = run.records.get("tokens")
    if toks is None:
        return None
    t0, t1 = run.window
    gaps = [(b - a) * 1e3 for ts in toks.values()
            for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    return percentile(gaps, 99)
