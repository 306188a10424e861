"""moe.dispatch_roofline: the least time of the MoE dispatch over the
device time of its kernels, in percent, over the traced window's engine
calls: the ``gather_combine_blocked`` events (the combine back to token
order) and, for each ``grouped_swiglu`` event, the last
``gather_rows_blocked`` event before it (the gather that fed it: the
same kernel also unpacks each admission's KV rows, between programs).
The least time is the work of ``work/moe_layer.py`` (``dispatch``) at
the chip's peaks, from the ``moe_rows_real`` counts of the program's
engine spans."""

from bench.harness import plugin
from bench.peaks import least_seconds

GATHER, COMBINE = "gather_rows_blocked", "gather_combine_blocked"


def read(run):
    gmm = plugin("metrics", "moe.gmm_roofline", run.bench)
    trace = run.trace_result
    if trace is None or not trace.devices or run.peaks is None:
        return None
    spans = gmm.moe_spans(run)
    if spans is None:
        return None
    lo, hi = min(s for _, s, _, _ in spans), max(e for _, _, e, _ in spans)
    kernel_ns = 0
    for evs in gmm.kernel_events(trace, (gmm.KERNEL, GATHER, COMBINE), lo, hi).values():
        fed = set()
        last_gather = None
        for i, (name, s, e) in enumerate(evs):
            if name == GATHER:
                last_gather = i
            elif name == COMBINE:
                kernel_ns += e - s
            elif last_gather is not None:
                fed.add(last_gather)
        kernel_ns += sum(evs[i][2] - evs[i][1] for i in fed)
    if kernel_ns <= 0:
        return None
    work = plugin("work", "moe_layer", run.bench).dispatch
    least = sum(least_seconds(work(run.config, a["moe_rows_real"]), run.peaks)
                for _, _, _, a in spans)
    return 100.0 * least / (kernel_ns / 1e9 / len(trace.devices))
