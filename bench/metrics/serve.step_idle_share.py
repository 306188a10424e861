"""serve.step_idle_share: the share of the time inside the benchmark's spans
around ``Engine.admit_batch`` and ``Engine.step`` in which the device ran
nothing, in percent (traced window)."""


def read(run):
    tr = run.trace_result
    if tr is None or not tr.devices or "steps" not in run.records:
        return None
    span_s, busy_s = tr.busy_within(("admit", "step"))
    if span_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / span_s)
