"""serve.queue_p50_ms: the median, over the requests due in the window, of
the time from when a request was due to the start of the admission wave
that took it."""

from bench.stats import percentile


def read(run):
    rec = run.records
    if "in_window" not in rec:
        return None
    vals = [(rec["admitted"][rid] - rec["due"][rid]) * 1e3
            for rid in rec["in_window"] if rid in rec["admitted"]]
    return percentile(vals, 50)
