"""lib.host_us_per_call: mean host time of a library call from its entry
to the return of its dispatch, in microseconds (the benchmark's span).

The window calls each op's compiled program, as a caller who jits the
library does, so this is the runtime's dispatch: the planners run once,
when the program is traced in set-up, and show in ``setup_s``."""


def read(run):
    calls = run.records.get("calls")
    if not calls:
        return None
    return sum(c[2] - c[1] for c in calls) / len(calls) * 1e6
