"""serve.prefill_row_util: prompt tokens prefilled over the rows computed
for them, over the program's ``engine.admit`` (packed admission) and
``engine.chunk_wave`` spans that start in the window, in percent: the
``rows_real`` and ``rows_computed`` counts the engine records with each
span."""

from bench import program_spans


def read(run):
    spans = program_spans.kept(run, ("engine.admit", "engine.chunk_wave"))
    if spans is None:
        return None
    t0, t1 = run.window
    inside = [a for _, s, _, a in spans if t0 <= s < t1]
    computed = sum(a["rows_computed"] for a in inside)
    if computed <= 0:
        return None
    return 100.0 * sum(a["rows_real"] for a in inside) / computed
