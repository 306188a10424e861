"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python bench/tests/record_trace.py <out.json>

On a TPU: a few library calls (a Pallas copy kernel and an XLA op) inside
the benchmark's own spans, traced, reduced by ``bench.trace`` and written
with the numbers the reduction gave, so a later change to the reduction
that reads the same events differently fails the test.
"""

import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main(out: str) -> int:
    import time

    import jax
    import jax.numpy as jnp

    from bench import harness, trace
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.float32)
    copy = jax.jit(ops.copy).lower(x).compile()
    scale = jax.jit(lambda a: a * 2.0 + 1.0).lower(x).compile()
    jax.block_until_ready((copy(x), scale(x)))
    spans = harness.Spans()
    spans.annotate = True
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t0 = time.perf_counter()
        for _ in range(3):
            for name, exe in (("copy", copy), ("scale", scale)):
                with spans.span("call", op=name):
                    jax.block_until_ready(exe(x))
            time.sleep(0.002)
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        t = trace.load_dir(d, window_s=t1 - t0)
    rec = {"recorded_on": jax.devices()[0].device_kind, "trace": json.loads(t.to_json()),
           "expect": {"kernel_s": t.kind_s("kernel"), "busy_s": t.busy_s()}}
    Path(out).write_text(json.dumps(rec, indent=1))
    print(json.dumps(rec["expect"]), t.gaps(), t.top_ops())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
