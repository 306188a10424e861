"""Closed loop over a library traffic mix.

One caller runs the mix's ops round robin and waits for each result
before it makes the next call, as a library user who needs the result
does.  Every op is compiled ahead of time, checked to hold a Pallas
kernel, and called once before the window opens: the window calls the
compiled program, as a caller who jits the library does, so the
library's planners run at trace time in set-up and the window times the
runtime's dispatch and the device.  Inside the window each
call is recorded; the output of one call of each op, drawn from the seed
over all of that op's calls, is kept and compared bit for bit with the
op's plain reference once the window has closed.
"""

from __future__ import annotations

import random
import time


def build(run, log):
    """Every op of the mix at its timed size, compiled and warmed:
    a list of ``(entry, op, executable, work)``."""
    import jax

    from bench import libops
    from bench.harness import Refused, plugin

    key = run.key()
    built = []
    for i, entry in enumerate(run.traffic["ops"]):
        t = time.perf_counter()
        op = plugin("ops", entry["op"], run.bench).build(
            entry, jax.random.fold_in(key, i), run.devices)
        exe = jax.jit(op.program).lower(*op.args).compile()
        if run.require_chip and not libops.expects_kernel(exe.as_text()):
            raise Refused(f"{op.label}: the timed program holds no tpu_custom_call")
        work = plugin("work", entry["op"], run.bench).work(**op.work)
        jax.block_until_ready(exe(*op.args))
        log(f"  {op.label}: {work['bytes'] / 1e9:.3f} GB a call, "
            f"set up in {time.perf_counter() - t:.1f} s")
        built.append((entry, op, exe, work))
    return built


def window(run, built, seconds: float):
    """Call the ops round robin for ``seconds``; returns the call records
    ``(op index, t_call, t_dispatched, t_done)`` and one output of each op,
    drawn uniformly from its calls by the seed (reservoir sampling)."""
    import jax

    rng = random.Random(run.seed)
    kept = [None] * len(built)
    seen = [0] * len(built)
    calls = []
    t0 = run.start_window()
    end = t0 + seconds
    with run.tracing():
        t_done = t0
        while t_done < end:
            i = len(calls) % len(built)
            op, exe = built[i][1], built[i][2]
            with run.spans.span("call", op=op.label):
                t_call = time.perf_counter()
                out = exe(*op.args)
                t_disp = time.perf_counter()
                jax.block_until_ready(out)
                t_done = time.perf_counter()
            calls.append((i, t_call, t_disp, t_done))
            seen[i] += 1
            if rng.random() * seen[i] < 1.0:
                kept[i] = out
            del out
    run.end_window(t0, calls[-1][3])
    return calls, kept


def compare(run, built, kept, log) -> None:
    """Each kept output against the op's reference, bit for bit; the
    count of differing elements of each op has the limit 0."""
    import jax

    from bench import libops

    for i, (entry, op, _, _) in enumerate(built):
        want = jax.jit(op.reference, out_shardings=op.out_shardings)(*op.args)
        n = libops.mismatches(kept[i], want)
        del want
        run.check(f"{op.label}.mismatches", n, 0)
        log(f"  {op.label}: {n} elements differ from the reference")


def run(run, log) -> None:
    """Set up, measure, read the memory peak, then check the outputs."""
    from bench.harness import memory_peak

    log(f"set-up: {len(run.traffic['ops'])} ops")
    built = build(run, log)
    calls, kept = window(run, built, run.seconds)
    run.memory_peak_bytes = memory_peak(run)
    run.attempted = len(calls)
    run.records["calls"] = calls
    run.records["ops"] = [
        {"label": op.label, "work": work, "entry": entry}
        for entry, op, _, work in built
    ]
    t0, t1 = run.window
    log(f"window: {len(calls)} calls in {t1 - t0:.3f} s")
    compare(run, built, kept, log)


def readings(run, seeds, log) -> list:
    """The numbers the limits are set from: for each seed, each op's
    differing elements for the program (one call) and for the control
    (the reference computed in the lower precision), each with the
    verdict of the harness's comparison."""
    import jax

    from bench import libops
    from bench.harness import within

    out = []
    for seed in seeds:
        run.seed = seed
        built = build(run, log)
        for entry, op, exe, _ in built:
            want = jax.jit(op.reference, out_shardings=op.out_shardings)(*op.args)
            got = exe(*op.args)
            prog = libops.mismatches(got, want)
            del got
            low = jax.jit(libops.control(op.reference), out_shardings=op.out_shardings)(*op.args)
            ctrl = libops.mismatches(low, want)
            del low, want
            out.append({"seed": seed, "check": f"{op.label}.mismatches",
                        "program": prog, "control": ctrl, "limit": 0,
                        "program_passes": within(prog, 0), "control_passes": within(ctrl, 0)})
            log(f"  seed {seed} {op.label}: program {prog}, control {ctrl}")
        del built
    return out
