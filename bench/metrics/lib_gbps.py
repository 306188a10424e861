"""lib_gbps: the algorithmic bytes (``work/<op>.py``) of every library call
completed in the window, over the whole window, in GB/s."""


def read(run):
    calls = run.records.get("calls")
    if not calls:
        return None
    ops = run.records["ops"]
    t0, t1 = run.window
    return sum(ops[c[0]]["work"]["bytes"] for c in calls) / (t1 - t0) / 1e9
