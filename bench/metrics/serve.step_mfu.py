"""serve.step_mfu: useful model FLOPs of the engine calls in the window
(``work/dense_decoder.py``: real tokens only) over their summed wall time
times the chip's bf16 peak, in percent."""

from bench.harness import plugin


def read(run):
    steps = run.records.get("steps")
    if not steps or run.peaks is None:
        return None
    t0, t1 = run.window
    inside = [s for s in steps if t0 <= s[1] < t1]
    wall = sum(s[2] - s[1] for s in inside)
    if wall <= 0:
        return None
    flops = plugin("work", "dense_decoder", run.bench).flops
    useful = sum(flops(run.config, s[3]) for s in inside)
    return 100.0 * useful / (wall * run.peaks["flops_bf16"] * len(run.devices))
