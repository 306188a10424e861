"""tokens_per_s: output tokens the host saw emitted inside the window, over
the window."""


def read(run):
    toks = run.records.get("tokens")
    if toks is None:
        return None
    t0, t1 = run.window
    n = sum(1 for ts in toks.values() for t in ts if t0 <= t < t1)
    return n / (t1 - t0)
