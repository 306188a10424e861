"""ttft_p50_ms: the median, over every request due in the window, of the
time from when it was due to when the host saw its first token."""

from bench.stats import percentile


def read(run):
    rec = run.records
    if "in_window" not in rec:
        return None
    vals = [(rec["tokens"][rid][0] - rec["due"][rid]) * 1e3
            for rid in rec["in_window"] if rec["tokens"].get(rid)]
    return percentile(vals, 50)
