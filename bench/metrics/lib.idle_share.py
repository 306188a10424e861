"""lib.idle_share: the share of the traced window in which no operation ran
on the device, mean over the cell's chips, in percent."""


def read(run):
    tr = run.trace_result
    if tr is None or not tr.devices or not run.records.get("calls") or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
