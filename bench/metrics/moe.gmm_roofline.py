"""moe.gmm_roofline: the least time of the held experts' grouped matmul
over the device time of its ``grouped_swiglu`` kernel events, in percent,
over the traced window's engine calls.  The least time is the work of
``work/moe_layer.py`` (``gmm``) at the chip's peaks, from the
``moe_rows_real`` and ``moe_groups`` counts of the program's
``engine.admit``, ``engine.chunk_wave`` and ``engine.decode`` spans,
each span's work bounded by the larger of its FLOPs and its bytes."""

from bench import program_spans
from bench.harness import plugin
from bench.peaks import least_seconds
from bench.trace import _NUMBERED

SPANS = ("engine.admit", "engine.chunk_wave", "engine.decode")
KERNEL = "grouped_swiglu"


def moe_spans(run):
    """The traced engine spans that carry MoE counts, or ``None``."""
    spans = program_spans.on_trace(run, SPANS)
    spans = [s for s in spans or () if "moe_rows_real" in s[3]]
    return spans or None


def kernel_events(trace, names, lo, hi):
    """Per device, ``(name, start, end)`` of the kernel events called one
    of ``names`` that overlap ``[lo, hi)``, in start order."""
    return {d: sorted(((_NUMBERED.sub("", n), s, e) for n, s, e, k in evs
                       if k == "kernel" and _NUMBERED.sub("", n) in names and s < hi and e > lo),
                      key=lambda ev: ev[1])
            for d, evs in trace.devices.items()}


def read(run):
    trace = run.trace_result
    if trace is None or not trace.devices or run.peaks is None:
        return None
    spans = moe_spans(run)
    if spans is None:
        return None
    lo, hi = min(s for _, s, _, _ in spans), max(e for _, _, e, _ in spans)
    events = kernel_events(trace, (KERNEL,), lo, hi)
    kernel_ns = sum(e - s for evs in events.values() for _, s, e in evs)
    if kernel_ns <= 0:
        return None
    work = plugin("work", "moe_layer", run.bench).gmm
    least = sum(least_seconds(work(run.config, a["moe_rows_real"], a["moe_groups"]), run.peaks)
                for _, _, _, a in spans)
    return 100.0 * least / (kernel_ns / 1e9 / len(trace.devices))
