"""Reduction of a profiler trace to device busy time, kernel time,
exposed collective time and idle gaps labelled by the host's spans.

A trace is kept as plain data: for each device the intervals of its
operations, each with its HLO instruction name and a kind (``kernel`` for
a Pallas kernel, ``collective``, ``control`` for a loop, or ``op``), and
the benchmark's host spans, all on the trace's one clock in nanoseconds.
``Trace.to_json`` / ``from_json`` keep a small recorded trace for the tests.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

#: names of the host spans the benchmark writes (``harness.Spans``)
SPAN_NAMES = ("call", "admit", "step")
_COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|send|recv)")
_NUMBERED = re.compile(r"[.\-_]\d+$")


def parse_op(text: str) -> tuple[str, str]:
    """(name, opcode) of a device event, whose name on a TPU is the HLO
    instruction's text: ``%copy.1 = f32[8,128]{1,0} custom-call(...), ...``."""
    lhs, _, rhs = text.partition(" = ")
    name = lhs.strip().lstrip("%")
    if not rhs:
        return name, ""
    if rhs.startswith("("):  # a tuple result type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rhs[i + 1:]
    else:
        rest = rhs.partition(" ")[2]
    return name, rest.strip().partition("(")[0]


def op_kind(opcode: str) -> str:
    """``kernel`` for a Pallas kernel (an XLA custom call), ``collective``
    for a communication op, ``control`` for a loop or call whose body's
    operations are events of their own, ``op`` for any other."""
    if opcode == "custom-call":
        return "kernel"
    if _COLLECTIVE.search(opcode):
        return "collective"
    if opcode in ("while", "conditional", "call"):
        return "control"
    return "op"


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    """Total length of disjoint intervals."""
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> int:
    """Length of the intersection of two lists of disjoint sorted intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Trace:
    """One traced window: device operations and host spans (ns)."""

    window_s: float  #: the window's length on the host clock
    devices: dict = field(default_factory=dict)  #: name -> [(op, t0, t1, kind)]
    spans: list = field(default_factory=list)  #: [(name, t0, t1, label)]

    # -- per device ---------------------------------------------------------

    def _busy(self, dev) -> list:
        return union((s, e) for _, s, e, k in self.devices[dev] if k != "control")

    def _mean(self, fn) -> float:
        if not self.devices:
            return 0.0
        return sum(fn(d) for d in self.devices) / len(self.devices)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, mean over the devices."""
        return self._mean(lambda d: length(self._busy(d))) / 1e9

    def kind_s(self, kind: str) -> float:
        """Summed duration of operations of ``kind``, mean over the devices."""
        return self._mean(lambda d: sum(e - s for _, s, e, k in self.devices[d]
                                        if k == kind)) / 1e9

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective ran and no other operation did,
        mean over the devices."""
        def one(d):
            coll = union((s, e) for _, s, e, k in self.devices[d] if k == "collective")
            comp = union((s, e) for _, s, e, k in self.devices[d]
                         if k not in ("collective", "control"))
            return length(coll) - overlap(coll, comp)

        return self._mean(one) / 1e9

    def busy_within(self, names) -> tuple[float, float]:
        """(span seconds, device-busy seconds inside them) over the host
        spans called one of ``names``, busy taken as the mean over the
        devices."""
        sp = union((s, e) for n, s, e, _ in self.spans if n in names)
        busy = self._mean(lambda d: overlap(sp, self._busy(d)))
        return length(sp) / 1e9, busy / 1e9

    # -- what the next reader sees -------------------------------------------

    def gaps(self, top: int = 10) -> list:
        """The longest idle gaps of the first device inside the spanned
        window, each named by the host span it fell in."""
        if not self.devices or not self.spans:
            return []
        dev = sorted(self.devices)[0]
        lo = min(s for _, s, _, _ in self.spans)
        hi = max(e for _, _, e, _ in self.spans)
        busy = [(max(s, lo), min(e, hi)) for s, e in self._busy(dev) if e > lo and s < hi]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        holes.sort(key=lambda h: h[0] - h[1])
        out = []
        for s, e in holes[:top]:
            mid = (s + e) // 2
            inside = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
            label = min(inside, key=lambda sp: sp[2] - sp[1])[3] if inside else "host between spans"
            out.append([label, (e - s) / 1e9])
        return out

    def top_ops(self, top: int = 10) -> list:
        """Device operations by summed time (first device), numbered
        copies of one operation taken together, each name tagged with its
        kind: a Pallas kernel and an XLA op of one name (``copy``) stay
        apart."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        tot: dict[str, int] = {}
        for name, s, e, kind in self.devices[dev]:
            if kind == "control":
                continue
            key = f"{_NUMBERED.sub('', name)} [{kind}]"
            tot[key] = tot.get(key, 0) + (e - s)
        return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict:
        """The result line's ``breakdown``."""
        return {"device_ops": self.top_ops(), "idle_gaps": self.gaps()}

    # -- storage -------------------------------------------------------------

    def to_json(self) -> str:
        return json.dumps({"window_s": self.window_s, "devices": self.devices,
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(d["window_s"], {k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["spans"]])


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def from_profile(pd, window_s: float) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    devices = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs = devices.setdefault(plane.name, [])
                for e in line.events:
                    s = int(e.start_ns)
                    name, opcode = parse_op(e.name)
                    evs.append((name, s, s + int(e.duration_ns), op_kind(opcode)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES:
                        st = _stats(e)
                        label = " ".join([e.name] + [str(v) for k, v in sorted(st.items())
                                                     if not k.startswith("_")])
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns), label))
    return Trace(window_s, devices, sorted(spans, key=lambda x: x[1]))


def load_dir(path, window_s: float) -> Trace:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    from jax.profiler import ProfileData

    files = sorted(Path(path).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {path}")
    return from_profile(ProfileData.from_file(str(files[-1])), window_s)
