"""lib.kernel_roofline: the least time of the window's library calls (their
work at the chip's peaks, shared over the chips a call runs on) over the
device's busy time: every operation the calls ran, Pallas kernels and XLA
ops alike, in percent.  Work moved out of a kernel into an XLA op stays
in the denominator, so the share cannot rise by it."""

from bench.peaks import least_seconds


def read(run):
    tr, calls = run.trace_result, run.records.get("calls")
    if tr is None or not tr.devices or not calls:
        return None
    busy_s = tr.busy_s()
    if busy_s <= 0:
        return None
    ops = run.records["ops"]
    least = sum(least_seconds(ops[c[0]]["work"], run.peaks) for c in calls)
    return 100.0 * least / len(run.devices) / busy_s
