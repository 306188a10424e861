"""serve.decode_attn_roofline: the least time of the traced window's decode
attention over the device time of its ``flash_decode`` and
``decode_combine`` kernel events, in percent.  The least time is the work
of ``work/flash_decode.py`` at the chip's peaks, from the ``slots`` and
``kv_rows`` counts of the program's ``engine.decode`` spans."""

from bench import program_spans
from bench.harness import plugin
from bench.peaks import least_seconds
from bench.trace import _NUMBERED

KERNELS = ("flash_decode", "decode_combine")


def read(run):
    trace = run.trace_result
    if trace is None or not trace.devices or run.peaks is None:
        return None
    spans = program_spans.on_trace(run, ("engine.decode",))
    if not spans:
        return None
    lo, hi = min(s for _, s, _, _ in spans), max(e for _, _, e, _ in spans)
    kernel_ns = sum(e - s for d in trace.devices for name, s, e, kind in trace.devices[d]
                    if kind == "kernel" and _NUMBERED.sub("", name) in KERNELS
                    and s < hi and e > lo)
    if kernel_ns <= 0:
        return None
    work = plugin("work", "flash_decode", run.bench).work
    least = sum(least_seconds(work(run.config, a["slots"], a["kv_rows"]), run.peaks)
                for _, _, _, a in spans)
    return 100.0 * least / (kernel_ns / 1e9 / len(trace.devices))
