"""Run one cell of ``BENCHMARK.json``: find its files by name, check the
device, hand the cell to its runner, reduce what the runner recorded to
the cell's metrics, and print the result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name.  A configuration file names its runner (``runners/<runner>.py``); a
metric is read by ``metrics/<metric>.py``; a library op is built by
``ops/<op>.py`` and its work is counted by ``work/<op>.py``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: dispatch switches that would take the kernels off the chip
FORBIDDEN_ENV = {
    "REPRO_PALLAS_INTERPRET": None,
    "REPRO_DISABLE_PALLAS": None,
    "REPRO_FLASH_KERNEL": "0",
    "REPRO_DECODE_KERNEL": "0",
    "REPRO_ATTN_IDENTITY": "1",
}


class Refused(Exception):
    """The run cannot measure what the cell asks for; it prints no result."""


def refuse_env(environ) -> None:
    """Refuse a run whose environment takes kernels off the chip."""
    for var, bad in FORBIDDEN_ENV.items():
        val = environ.get(var)
        if val is not None and (bad is None or val == bad):
            raise Refused(f"refusing to run with {var}={val}")


# -- finding files by name ---------------------------------------------------


def load_module(path: Path):
    """Import the Python file ``path`` under a name of its own."""
    name = "bench_plugin" + "".join(c if c.isalnum() else "_" for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(kind: str, name: str, suffix: str, root: Path = BENCH) -> Path:
    """``<root>/<kind>/<name><suffix>``; raises ``FileNotFoundError``."""
    path = root / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                                f"file for {name!r}: {path}")
    return path


def plugin(kind: str, name: str, root: Path = BENCH):
    """The module ``<root>/<kind>/<name>.py``."""
    return load_module(find(kind, name, ".py", root))


def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    """The JSON file ``<root>/<kind>/<name>.json``."""
    return json.loads(find(kind, name, ".json", root).read_text())


@dataclass
class Cell:
    """One entry of ``workloads`` with what it refers to."""

    workload: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``name`` of the benchmark file, with its metrics."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in {spec_path.name}; "
                      f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        workload=w,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


# -- spans ------------------------------------------------------------------


class Spans:
    """Host spans of the benchmark's own calls into the program.  While a
    trace runs each is a profiler annotation, so the trace holds it on the
    device's clock and idle gaps can be named by it."""

    def __init__(self):
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name, **attrs):
                yield
        else:
            yield


# -- the run ----------------------------------------------------------------


@dataclass
class Run:
    """What a runner is given, and what it records for the readers."""

    cell: Cell
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float  #: perf_counter at process start
    require_chip: bool = True
    root: Path = ROOT
    bench: Path = BENCH
    spans: Spans = field(default_factory=Spans)
    peaks: dict | None = None
    devices: list = field(default_factory=list)
    # filled by the runner
    window: tuple[float, float] | None = None  #: perf_counter seconds
    setup_s: float | None = None
    records: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)  #: name -> (value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None
    trace_result: object = None  #: a ``bench.trace.Trace`` of the window
    compiles_in_window: int = 0
    events: list = field(default_factory=list)  #: JAX monitoring event names

    def key(self):
        """The JAX PRNG key of ``seed`` (any non-negative integer)."""
        import jax

        k = jax.random.PRNGKey(0)
        s = int(self.seed)
        while True:
            k = jax.random.fold_in(k, s & 0xFFFFFFFF)
            s >>= 32
            if not s:
                return k

    def start_window(self) -> float:
        """Mark the end of set-up; returns the window's start time."""
        t = time.perf_counter()
        self.setup_s = t - self.t_process
        self._events_at_start = len(self.events)
        return t

    def end_window(self, t0: float, t1: float) -> None:
        """Record the window and the compiles that fell inside it."""
        self.window = (t0, t1)
        self.compiles_in_window = sum(
            1 for e in self.events[self._events_at_start:] if _is_compile(e)
        )

    @contextlib.contextmanager
    def tracing(self):
        """Profile the enclosed window when ``--trace 1``; the reduced
        trace lands on ``trace_result``."""
        if not self.trace:
            yield
            return
        import jax

        from bench import trace as tr

        out = self.root / ".bench_trace" / self.cell.name
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        self.spans.annotate = True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from TraceAnnotation
        jax.profiler.start_trace(str(out), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.spans.annotate = False
        self.trace_result = tr.load_dir(out, window_s=t1 - t0)
        shutil.rmtree(out, ignore_errors=True)

    def check(self, name: str, value: float, limit: float) -> None:
        """Record one number compared with its limit (passes if <=)."""
        self.checks[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return (bool(self.checks) and self.failed == 0
                and all(within(v, lim) for v, lim in self.checks.values()))


def within(value, limit) -> bool:
    """The comparison that decides ``correct``: a number compared passes
    when it is no more than its limit (a missing number never passes)."""
    return value is not None and float(value) <= float(limit)


def _is_compile(event: str) -> bool:
    return "backend_compile" in event or event.endswith("/cache_hits")


# -- device -----------------------------------------------------------------


def device_check(run: Run) -> None:
    """Refuse a run without enough accelerators of a known kind."""
    import jax

    from bench import peaks

    devs = jax.devices()
    want = int(run.cell.workload["chips"])
    if run.require_chip:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < want:
            raise Refused(f"the cell asks for {want} chips, JAX found {len(devs)}")
        try:
            run.peaks = peaks.peaks_for(devs[0].device_kind)
        except peaks.UnknownDevice as e:
            raise Refused(str(e)) from None
    run.devices = devs[:want]


def memory_peak(run: Run) -> int | None:
    """Peak bytes in use on the fullest chip the cell used."""
    vals = []
    for d in run.devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


def _listen(run: Run) -> None:
    import jax

    jax.monitoring.register_event_listener(lambda e, **_: run.events.append(e))
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **_: run.events.append(e))


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (``JAX_COMPILATION_CACHE_DIR`` where set), caching every program."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- metrics and the result line -----------------------------------------------


def read_metrics(run: Run, metrics: list) -> dict:
    """Each metric's reader applied to the run; a reader that finds
    nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for m in metrics:
        val = plugin("metrics", m["name"], run.bench).read(run)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def result_line(run: Run) -> dict:
    """The last line of standard output, as the contract lays it out."""
    metrics = read_metrics(run, run.cell.per_layer if run.trace else run.cell.end_to_end)
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": run.correct, "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": device}
    if run.trace and run.trace_result is not None:
        tr = run.trace_result
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = tr.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()}
    return line


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_process: float, require_chip: bool = True,
            config: dict | None = None, traffic: dict | None = None,
            cache: bool = True, log=print) -> Run:
    """Drive the cell and return the finished :class:`Run`.

    ``config`` / ``traffic`` replace the cell's files, ``require_chip=False``
    skips the device check and ``cache=False`` leaves JAX's persistent
    compilation cache off: all three are for tests."""
    cell = load_cell(workload)
    run = Run(
        cell=cell,
        config=config if config is not None else load_json("configs", cell.workload["config"]),
        traffic=traffic if traffic is not None else load_json("traffic", cell.workload["traffic"]),
        seed=seed, seconds=seconds, trace=trace, t_process=t_process,
        require_chip=require_chip,
    )
    device_check(run)
    dev = run.devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(run.devices)}")
    if cache:
        log(f"compile cache: {enable_cache()}")
    _listen(run)
    plugin("runners", run.config["runner"], run.bench).run(run, log)
    log(f"compiles inside the window: {run.compiles_in_window}")
    return run


def main(argv=None, *, t_process: float) -> int:
    """Command line: ``--workload --seed --seconds --trace``."""
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, flush=True)

    try:
        refuse_env(os.environ)
        if args.seed < 0:
            raise Refused("--seed must be a non-negative integer")
        sys.path.insert(0, str(ROOT / "src"))
        run = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_process=t_process, log=log)
    except (Refused, ImportError, FileNotFoundError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 2
    line = result_line(run)
    for k, (v, lim) in run.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
