"""The program's span recorder (`repro.utils.spans`): nesting, counts, the
bound, and the profiler annotation each span enters."""

import jax
import pytest

from repro.utils import spans


@pytest.fixture(autouse=True)
def empty():
    spans.reset()
    yield
    spans.reset()


def test_spans_nest_and_keep_their_counts():
    with spans.span("outer", n=2):
        with spans.span("inner", rows_real=5, rows_computed=8):
            pass
    (inner, outer) = spans.spans()  # kept in order of their ends
    assert inner[0] == "inner" and outer[0] == "outer"
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    assert inner[3] == {"rows_real": 5, "rows_computed": 8}
    assert outer[3] == {"n": 2}
    assert spans.dropped() == 0


def test_a_span_is_kept_when_its_block_raises():
    with pytest.raises(ValueError):
        with spans.span("failing", n=1):
            raise ValueError("boom")
    assert [s[0] for s in spans.spans()] == ["failing"]


def test_the_bound_pushes_out_the_oldest():
    extra = 5
    for i in range(spans.MAX_SPANS + extra):
        with spans.span("s", i=i):
            pass
    kept = spans.spans()
    assert len(kept) == spans.MAX_SPANS
    assert spans.dropped() == extra
    assert kept[0][3] == {"i": extra}
    spans.reset()
    assert spans.spans() == [] and spans.dropped() == 0


def test_each_span_enters_a_trace_annotation(monkeypatch):
    entered = []

    class Recording:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            entered.append((self.name, self.attrs))

        def __exit__(self, *exc):
            entered.append("exit")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    with spans.span("engine.decode", slots=3, kv_rows=40):
        assert entered == [("engine.decode", {"slots": 3, "kv_rows": 40})]
    assert entered[-1] == "exit"
    assert spans.spans()[0][3] == {"slots": 3, "kv_rows": 40}


def test_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("engine.sample", n=7):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = [e.name for p in pd.planes for line in p.lines for e in line.events]
    assert "engine.sample" in names
