"""dist.exposed_collective_share: the share of the traced window in which a
collective ran on a device and no other operation did, mean over the
devices, in percent."""


def read(run):
    tr = run.trace_result
    if tr is None or not tr.devices or len(run.devices) < 2 or tr.window_s <= 0:
        return None
    if tr.kind_s("collective") <= 0:
        return None
    return 100.0 * tr.exposed_collective_s() / tr.window_s
