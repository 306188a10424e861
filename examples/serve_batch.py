"""Batched serving example: continuous batching over fixed decode slots.

Where each serving stage lowers through the plan engines:

* **ragged admission** — every admission wave packs its prompts into ONE
  ``qo_indptr``-style prefill batch (`core/index_plan.py`'s
  ``ragged_layout``, DESIGN.md §12); the packed KV rows move into the
  decode slots via a masked ``ragged_rows`` IndexPlan gather — the §4
  index-set engine with ``-1`` sentinels zero-filling each ring tail.
* **chunked prefill** — prompts longer than ``chunk`` stream through
  `models.transformer.prefill_chunk` a slice per engine step, interleaved
  with decode, so a long prompt never stalls the live slots.
* **decode** — every step threads a per-slot position vector through
  `models.transformer.decode_step`; on kernel backends the attention is
  the split-KV `kernels.flash.flash_decode` two-stage reduce (§12), whose
  split count x block_k tile registers with the §11 autotuner.
* **MoE archs** — dispatch/combine is the §4 two-kernel sort path
  (`models/moe.py`); on a mesh, the expert-parallel variant
  (`moe_sort_ep`) wraps the same kernels in the §10 distributed planner.

The example asserts output identity: the engine's greedy tokens — across
slot reuse, ragged packing and chunked prefill — must equal a clean
per-request greedy decode (unpadded prefill + stepwise decode) on the
same fixed seed.

  PYTHONPATH=src python examples/serve_batch.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import transformer as tf
from repro.serve.engine import Engine, Request, _write_slot

S_MAX = 128


def reference_greedy(cfg, params, prompt, max_new):
    """Single-request greedy decode: unpadded prefill + scalar-pos steps."""
    logits, c1 = tf.prefill(params, cfg, jnp.asarray(prompt)[None])
    ring = _write_slot(tf.init_cache(cfg, 1, S_MAX), c1, 0, S_MAX)
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(out) < max_new and pos < S_MAX:
        lg, ring, _ = tf.decode_step(
            params, cfg, jnp.asarray([out[-1]], np.int32), ring, jnp.int32(pos)
        )
        pos += 1
        out.append(int(jnp.argmax(lg[0])))
    return out


def main() -> None:
    cfg = configs.get_config("qwen2-7b-smoke")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(
        cfg, params, batch_slots=4, s_max=S_MAX, prompt_bucket=32,
        prefill_mode="ragged", chunk=16,  # ragged admission + chunked prefill
    )

    rng = np.random.default_rng(0)
    requests = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, int(rng.integers(8, 40))).astype(np.int32),
            max_new=12,
        )
        for i in range(10)  # 10 requests through 4 slots
    ]
    t0 = time.time()
    done = engine.run(requests)
    dt = time.time() - t0
    tokens = sum(len(r.out) for r in done)
    print(f"{len(done)} requests, {tokens} new tokens, {dt:.2f}s ({tokens/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.rid} (prompt {len(r.prompt)} toks) -> {r.out[:6]}...")

    # identity with the clean per-request greedy decode on the same seed
    for r in done:
        ref = reference_greedy(cfg, params, r.prompt, r.max_new)
        assert r.out == ref, f"req {r.rid}: engine {r.out} != reference {ref}"
    print(f"identity: all {len(done)} outputs match the per-request reference")


if __name__ == "__main__":
    main()
