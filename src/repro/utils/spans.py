"""Host spans of the program, with the counts of the work each one covers.

    with span("engine.decode", slots=3, kv_rows=1200):
        ...

Each span is kept as ``(name, t0, t1, attrs)`` in a bounded buffer of the
process (``time.perf_counter`` seconds; ``attrs`` the integer counts given
at entry), and is also a ``jax.profiler.TraceAnnotation`` of the same name
and attributes, so a profiler trace shows it next to the device ops.  The
recorder is always on: a span costs one tuple append and one TraceMe,
which is inactive unless a profiler trace is running.
"""

from __future__ import annotations

import collections
import time

import jax

#: Spans kept before the oldest are pushed out.  The serving engine records
#: at most four spans a call (a chunk wave and a decode, each with its
#: sample) and makes under 40 calls a second at qwen2-7b widths on a TPU
#: v5e, where the benchmark's chat cell recorded 3,947 spans over its warm-up,
#: 10 s lead-in, 51 s window and drain.  Its longest possible run (a 60 s
#: drain, four spans every call) records under 20,000: 131,072 is more than
#: 4x that.
MAX_SPANS = 1 << 17

_buffer: collections.deque = collections.deque(maxlen=MAX_SPANS)
_recorded = 0


class span:
    """Record the enclosed block as the span ``name`` with counts ``attrs``
    (a class: cheaper to enter than a generator-based context manager)."""

    __slots__ = ("name", "attrs", "t0", "_annotation")

    def __init__(self, name: str, **attrs: int):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _recorded
        _buffer.append((self.name, self.t0, time.perf_counter(), self.attrs))
        _recorded += 1
        self._annotation.__exit__(*exc)


def spans() -> list:
    """The kept spans, oldest end first: ``[(name, t0, t1, attrs)]``."""
    return list(_buffer)


def dropped() -> int:
    """How many spans the bound has pushed out since the last reset."""
    return _recorded - len(_buffer)


def reset() -> None:
    """Forget every span."""
    global _recorded
    _buffer.clear()
    _recorded = 0
