"""Batched serving driver.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b-smoke \
      --requests 8 --prompt-len 48 --max-new 16 --chunk 32

Set ``REPRO_SERVE_FLAGS=1`` (or pass ``--serve-flags``) to apply the XLA
inference preset (`repro.launch.xla_flags`) before the backend starts.
"""

from __future__ import annotations

import argparse
import time

from repro.launch import xla_flags


def main() -> None:
    """CLI driver: synthetic requests through the continuous-batching engine."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--prefill-mode", choices=["ragged", "bucket"], default=None,
        help="admission route (default: ragged when the arch supports it)",
    )
    ap.add_argument(
        "--chunk", type=int, default=None,
        help="tokens prefilled per engine step (ragged mode); "
             "default: whole prompt at admit",
    )
    ap.add_argument(
        "--serve-flags", action="store_true",
        help="apply the REPRO_SERVE_FLAGS XLA inference preset",
    )
    args = ap.parse_args()

    merged = xla_flags.apply_serve_flags(force=args.serve_flags)
    if args.serve_flags and merged is None:
        print("serve-flags: no TPU runtime detected, preset skipped")

    # import after the flag preset: XLA reads XLA_FLAGS at backend init
    import jax
    import numpy as np

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as tf
    from repro.serve.engine import Engine, Request
    from repro.utils import spans

    enable_compile_cache()
    cfg = configs.get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = tf.init_params(key, cfg)
    rng = np.random.default_rng(args.seed)

    engine = Engine(
        cfg, params, batch_slots=args.slots, s_max=args.s_max,
        prefill_mode=args.prefill_mode, chunk=args.chunk,
    )
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
            max_new=args.max_new,
        )
        for i in range(args.requests)
    ]
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s) [mode={engine.mode} chunk={engine.chunk}]")
    for r in done[:3]:
        print(f"  req {r.rid}: first tokens {r.out[:8]}")
    print(engine_totals(spans.spans()))


def engine_totals(recorded) -> str:
    """One line of the engine's totals from its spans: prompt rows over the
    rows prefilled for them, and seconds spent sampling on the host and
    unpacking admitted KV rows."""
    rows = [a for name, _, _, a in recorded if name in ("engine.admit", "engine.chunk_wave")]
    real = sum(a["rows_real"] for a in rows)
    computed = sum(a["rows_computed"] for a in rows)

    def seconds(name):
        return sum(t1 - t0 for n, t0, t1, _ in recorded if n == name)

    use = f"{100 * real / computed:.1f}%" if computed else "n/a"
    return (f"engine: prefill rows {real}/{computed} used ({use}), "
            f"{seconds('engine.sample'):.3f} s in engine.sample, "
            f"{seconds('engine.write_ragged'):.3f} s in engine.write_ragged")


if __name__ == "__main__":
    main()
