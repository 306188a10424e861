"""moe.routed_row_util: the real (token, held expert) assignments over the
rows the grouped expert kernel computed for them (tile padding counted),
over the program's ``engine.admit``, ``engine.chunk_wave`` and
``engine.decode`` spans that start in the window, in percent: the
``moe_rows_real`` and ``moe_rows_computed`` counts the engine records
with each span.  A program that records no such counts reads nothing."""

from bench import program_spans

SPANS = ("engine.admit", "engine.chunk_wave", "engine.decode")


def read(run):
    spans = program_spans.kept(run, SPANS)
    if spans is None:
        return None
    t0, t1 = run.window
    inside = [a for _, s, _, a in spans if t0 <= s < t1 and "moe_rows_computed" in a]
    computed = sum(a["moe_rows_computed"] for a in inside)
    if computed <= 0:
        return None
    return 100.0 * sum(a["moe_rows_real"] for a in inside) / computed
